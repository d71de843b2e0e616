"""Family `dense_gqa`: the Llama / Qwen2 decoder, dense and grouped-query.

Per layer: RMSNorm, q/k/v projections (with biases for Qwen2), rotate-half
RoPE, causal grouped-query attention, output projection and residual;
RMSNorm, SiLU-gated MLP and residual.  A final RMSNorm and the untied
output head give the logits.  The program runs it as
`repro.models.lm.TransformerLM` with one scanned group of layers.

A family module gives the harness, by these names: `dims`, `toy`,
`model_config`, `canonical`, `program_params`, `served_logits`,
`forward_flops` and `decode_bytes` (see `bench/harness.py`, FAMILY_API).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import reference as ref
from bench import weights
from bench.yardstick import causal_keys

# per-layer tensors in plain names: shape as a function of the dimensions
LAYER_SHAPES = {
    "norm1": lambda d: (d["d"],),
    "wq": lambda d: (d["d"], d["H"] * d["D"]),
    "wk": lambda d: (d["d"], d["K"] * d["D"]),
    "wv": lambda d: (d["d"], d["K"] * d["D"]),
    "wo": lambda d: (d["H"] * d["D"], d["d"]),
    "norm2": lambda d: (d["d"],),
    "w_gate": lambda d: (d["d"], d["F"]),
    "w_up": lambda d: (d["d"], d["F"]),
    "w_down": lambda d: (d["F"], d["d"]),
}
BIAS_SHAPES = {
    "bq": lambda d: (d["H"] * d["D"],),
    "bk": lambda d: (d["K"] * d["D"],),
    "bv": lambda d: (d["K"] * d["D"],),
}
GLOBAL_SHAPES = {
    "embed": lambda d: (d["V"], d["d"]),
    "final_norm": lambda d: (d["d"],),
    "head": lambda d: (d["d"], d["V"]),
}
NORMS = ("norm1", "norm2", "final_norm")

# where each plain name sits in the program's parameter tree
PROGRAM_PATHS = {
    ("embed", "table"): "embed",
    ("final_norm", "w"): "final_norm",
    ("head", "w"): "head",
    ("blocks0", "block", "norm1", "w"): "norm1",
    ("blocks0", "block", "attn", "wq"): "wq",
    ("blocks0", "block", "attn", "wk"): "wk",
    ("blocks0", "block", "attn", "wv"): "wv",
    ("blocks0", "block", "attn", "wo"): "wo",
    ("blocks0", "block", "attn", "bq"): "bq",
    ("blocks0", "block", "attn", "bk"): "bk",
    ("blocks0", "block", "attn", "bv"): "bv",
    ("blocks0", "block", "norm2", "w"): "norm2",
    ("blocks0", "block", "ffn", "wg", "w"): "w_gate",
    ("blocks0", "block", "ffn", "wi", "w"): "w_up",
    ("blocks0", "block", "ffn", "wo", "w"): "w_down",
}

# the widths knee.py serves on the CPU: a scheduling decision depends on
# none of them
TOY = {"hidden_size": 32, "intermediate_size": 64, "num_attention_heads": 2,
       "num_key_value_heads": 1, "head_dim": 16, "num_hidden_layers": 1,
       "vocab_size": 256}


def dims(conf: dict) -> dict:
    """Model dimensions from a configuration file's published keys."""
    d = int(conf["hidden_size"])
    H = int(conf["num_attention_heads"])
    return {
        "d": d, "H": H, "K": int(conf["num_key_value_heads"]),
        "D": int(conf.get("head_dim") or d // H),
        "F": int(conf["intermediate_size"]), "V": int(conf["vocab_size"]),
        "L": int(conf["num_hidden_layers"]),
        "bias": conf.get("model_type") == "qwen2"
        or bool(conf.get("attention_bias", False)),
        "tied": bool(conf.get("tie_word_embeddings", False)),
        "theta": float(conf["rope_theta"]),
        "eps": float(conf["rms_norm_eps"]),
    }


def toy(conf: dict) -> dict:
    """`conf` cut to toy widths, for serving on the CPU."""
    return dict(conf, **TOY)


def model_config(conf: dict):
    """The program's ModelConfig at the configuration file's numbers."""
    from repro.models.registry import get_config

    dm = dims(conf)
    return get_config(conf["registry"]).replace(
        name=conf["name"], num_layers=dm["L"], d_model=dm["d"],
        n_heads=dm["H"], kv_heads=dm["K"], head_dim=dm["D"], d_ff=dm["F"],
        vocab=dm["V"], rope_theta=dm["theta"], tie_embeddings=dm["tied"],
        qkv_bias=dm["bias"], activation=conf["hidden_act"], layer_groups=())


# -- weights ------------------------------------------------------------------


def _role(name: str) -> str:
    if name in NORMS:
        return "norm"
    if name == "embed":
        return "embed"
    return "bias" if name in BIAS_SHAPES else "matrix"


def _layer_table(dm: dict) -> dict:
    shapes = dict(LAYER_SHAPES, **(BIAS_SHAPES if dm["bias"] else {}))
    return {n: (f(dm), _role(n)) for n, f in shapes.items()}


def _make(seed_words, *, dm_items):
    dm = dict(dm_items)
    base = weights.base_key(seed_words)
    out = weights.draw_stack(base, _layer_table(dm), dm["L"])
    for n, f in GLOBAL_SHAPES.items():
        if n == "head" and dm["tied"]:
            continue
        out[n] = weights.draw(weights.name_key(base, n), f(dm), _role(n))
    return out


def canonical(dm: dict, seed: int, device=None) -> dict:
    """Every tensor of the model, per-layer ones stacked: {name: array}."""
    return weights.canonical(_make, dm, seed, device)


def program_params(canon: dict, abstract_tree: dict) -> dict:
    return weights.place(canon, abstract_tree, PROGRAM_PATHS)


# -- the plain float32 reference ----------------------------------------------


@functools.partial(jax.jit, static_argnames=("dm_items", "control"))
def _layer(h, layer, W, *, dm_items, control):
    dm = dict(dm_items)
    H, K, D, eps = dm["H"], dm["K"], dm["D"], dm["eps"]
    S = h.shape[0]
    w = {n: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
         for n, a in W.items()}
    pos = jnp.arange(S, dtype=jnp.int32)

    x = ref.rms(h, w["norm1"], eps)

    def proj(name, heads):
        y = ref.rows(lambda r: ref.dot(r, w["w" + name], control), x)
        if dm["bias"]:
            y = y + w["b" + name].astype(jnp.float32)
        return ref.rope(y.reshape(S, heads, D), pos, dm["theta"]) \
            if name in ("q", "k") else y.reshape(S, heads, D)

    o = ref.causal_attention(proj("q", H), proj("k", K), proj("v", K), pos)
    h = h + ref.rows(lambda r: ref.dot(r, w["wo"], control), o)

    x = ref.rms(h, w["norm2"], eps)

    def mlp(r):
        g = ref.dot(r, w["w_gate"], control)
        u = ref.dot(r, w["w_up"], control)
        return ref.dot(jax.nn.silu(g) * u, w["w_down"], control)

    return h + ref.rows(mlp, x)


@functools.partial(jax.jit, static_argnames=("dm_items", "control"))
def _head(h, final_norm, head, *, dm_items, control):
    dm = dict(dm_items)
    return ref.dot(ref.rms(h, final_norm, dm["eps"]), head, control)


def served_logits(canon: dict, dm: dict, seqs, *, control: bool = False):
    """The reference's logits at the positions that predicted served
    tokens (`reference.over_sequences`); `control` rounds every matrix
    product's operands to float8."""
    dm_items = tuple(sorted(dm.items()))
    layer_w = {n: a for n, a in canon.items() if n not in GLOBAL_SHAPES}
    head = canon["embed"].T if dm["tied"] else canon["head"]

    def forward(ids, first, n):
        h = jnp.take(canon["embed"], ids, axis=0).astype(jnp.float32)
        for layer in range(dm["L"]):
            h = _layer(h, layer, layer_w, dm_items=dm_items, control=control)
        return _head(h[first:n], canon["final_norm"], head,
                     dm_items=dm_items, control=control)

    return ref.over_sequences(seqs, forward)


# -- the yardstick: operations and bytes the model needs ----------------------
# A matrix is read once per step, the key/value cache is read up to each
# sequence's live length and written for each new token.


def matrix_params(dm: dict) -> int:
    """Parameters that every token multiplies: the layers' matrices and
    biases and the output head (the embedding is a row lookup)."""
    d, H, K, D, F = dm["d"], dm["H"], dm["K"], dm["D"], dm["F"]
    layer = d * H * D + 2 * d * K * D + H * D * d + 3 * d * F
    if dm["bias"]:
        layer += H * D + 2 * K * D
    return dm["L"] * layer + d * dm["V"]


def kv_bytes_per_token(dm: dict, itemsize: int = 2) -> int:
    return dm["L"] * 2 * dm["K"] * dm["D"] * itemsize


def weight_bytes(dm: dict) -> int:
    """Bytes one step reads of the weights: bfloat16 matrices and head,
    float32 norms."""
    norms = (2 * dm["L"] + 1) * dm["d"] * 4
    return 2 * matrix_params(dm) + norms


def decode_bytes(dm: dict, kv_lens) -> int:
    """Least bytes a decode step over sequences of live lengths `kv_lens`
    (counting the new token) moves: weights once, embedding rows, the live
    cache read and the new tokens' keys and values written."""
    b = len(kv_lens)
    per_tok = kv_bytes_per_token(dm)
    return (weight_bytes(dm) + b * dm["d"] * 2
            + sum(kv_lens) * per_tok + b * per_tok)


def attention_flops(dm: dict, start: int, stop: int) -> int:
    """Score and value products of the tokens at positions start..stop-1,
    each attending causally to itself and everything before it."""
    return 4 * dm["L"] * dm["H"] * dm["D"] * causal_keys(start, stop)


def forward_flops(dm: dict, start: int, stop: int) -> int:
    """Model operations for computing tokens start..stop-1 of a sequence."""
    return 2 * matrix_params(dm) * (stop - start) \
        + attention_flops(dm, start, stop)
