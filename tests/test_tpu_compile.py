"""The main serving path's Pallas kernels compile for a TPU v5e.

Interpret-mode tests cannot see what the chip's compiler refuses (block
shapes off the (8, 128) tiling, VMEM or SMEM overflow), so these tests
compile each kernel at yi-6b head widths (32 q heads, 4 KV heads, head_dim
128) for a *described* v5e — nothing runs — and check that the compiled
program holds the kernel (`tpu_custom_call`).  The topology is described
inside a fixture, so importing this file never loads the TPU library, and
the tests skip where it cannot be described.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.ops import flash_attention, flash_decode

B, H, K, D = 8, 32, 4, 128          # yi-6b decode batch and head geometry
PAGES, PAGE, TABLE = 512, 128, 16   # pool pages, page size, pages/request
SEQ = 2048                          # prefill length for the flash kernels


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _paged_decode(q_tokens, kv_dtype, page=PAGE):
    def fn(q, pk, pv, index, tables, *scales):
        ks, vs = scales or (None, None)
        return flash_decode(q, pk, pv, index, tables=tables,
                            kv_len=TABLE * page, k_scale=ks, v_scale=vs,
                            block_kv=512, interpret=False)

    args = [((B, q_tokens, H, D), jnp.bfloat16),
            ((PAGES, page, K, D), kv_dtype), ((PAGES, page, K, D), kv_dtype),
            ((B,), jnp.int32), ((B, TABLE), jnp.int32)]
    if kv_dtype != jnp.bfloat16:
        args += [((PAGES, K), jnp.float32)] * 2
    return fn, args


def _flash(grad: bool):
    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=512,
                               block_kv=512, interpret=False)

    fn = fwd
    if grad:
        fn = jax.grad(lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
                      argnums=(0, 1, 2))
    return fn, [((1, SEQ, H, D), jnp.bfloat16),
                ((1, SEQ, K, D), jnp.bfloat16),
                ((1, SEQ, K, D), jnp.bfloat16)]


CASES = {
    "paged_decode_bf16": lambda: _paged_decode(1, jnp.bfloat16),
    "paged_verify_s4_bf16": lambda: _paged_decode(4, jnp.bfloat16),
    "paged_decode_int8_scales": lambda: _paged_decode(1, jnp.int8),
    "paged_verify_s4_int8_scales": lambda: _paged_decode(4, jnp.int8),
    # the gcd clamp streams 16-row int8 blocks, half int8's 32-row tile
    "paged_decode_int8_page16": lambda: _paged_decode(1, jnp.int8, page=16),
    "flash_attention_fwd": lambda: _flash(False),
    "flash_attention_grad": lambda: _flash(True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, specs = CASES[case]()
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
