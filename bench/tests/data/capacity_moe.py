"""Family `capacity_moe`, a fixture of the harness's tests: a Mixtral-style
decoder whose feed-forward is a mixture of experts, served by the
program's capacity-routed `repro.nn.moe.MoEMLP`.  Only
`small_moe.json` beside it names it; it shows that an architecture other
than the dense decoder enters the benchmark as files of its own.

Per layer: RMSNorm, q/k/v projections, rotate-half RoPE, causal
grouped-query attention, output projection and residual; RMSNorm, then a
router (d, E) whose softmax picks each token's top k experts, their gates
renormalised to sum to 1, each a SiLU-gated MLP of width F; residual.  A
final RMSNorm and the untied head give the logits.

The reference routes every token to its top k experts, with no capacity.
The program drops a token that finds its expert full, so the fixture is
served where none can: MoEMLP gives each expert ceil(g * k * 1.25 / E)
slots in a group of g tokens, and with k * 1.25 = E (4 of 5 experts) that
is g, as many as the group has tokens.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import reference as ref
from bench import weights
from bench.yardstick import causal_keys

GLOBALS = ("embed", "final_norm", "head")
PROGRAM_PATHS = {
    ("embed", "table"): "embed",
    ("final_norm", "w"): "final_norm",
    ("head", "w"): "head",
    ("blocks0", "block", "norm1", "w"): "norm1",
    ("blocks0", "block", "attn", "wq"): "wq",
    ("blocks0", "block", "attn", "wk"): "wk",
    ("blocks0", "block", "attn", "wv"): "wv",
    ("blocks0", "block", "attn", "wo"): "wo",
    ("blocks0", "block", "norm2", "w"): "norm2",
    ("blocks0", "block", "ffn", "router"): "router",
    ("blocks0", "block", "ffn", "wg"): "w_gate",
    ("blocks0", "block", "ffn", "wi"): "w_up",
    ("blocks0", "block", "ffn", "wo"): "w_down",
}


def dims(conf: dict) -> dict:
    d = int(conf["hidden_size"])
    return {
        "d": d, "H": int(conf["num_attention_heads"]),
        "K": int(conf["num_key_value_heads"]), "D": int(conf["head_dim"]),
        "F": int(conf["intermediate_size"]),
        "E": int(conf["num_local_experts"]),
        "top": int(conf["num_experts_per_tok"]),
        "V": int(conf["vocab_size"]), "L": int(conf["num_hidden_layers"]),
        "theta": float(conf["rope_theta"]),
        "eps": float(conf["rms_norm_eps"]),
    }


def toy(conf: dict) -> dict:
    return dict(conf, hidden_size=32, intermediate_size=32, head_dim=16,
                num_hidden_layers=1, vocab_size=256)


def model_config(conf: dict):
    from repro.models.registry import get_config

    dm = dims(conf)
    return get_config(conf["registry"]).replace(
        name=conf["name"], num_layers=dm["L"], d_model=dm["d"],
        n_heads=dm["H"], kv_heads=dm["K"], head_dim=dm["D"], d_ff=dm["F"],
        vocab=dm["V"], num_experts=dm["E"], top_k=dm["top"],
        rope_theta=dm["theta"], tie_embeddings=False, qkv_bias=False,
        activation=conf["hidden_act"], attn_window=None, layer_groups=())


def _make(seed_words, *, dm_items):
    dm = dict(dm_items)
    d, H, K, D, F, E = (dm[k] for k in ("d", "H", "K", "D", "F", "E"))
    base = weights.base_key(seed_words)
    table = {
        "norm1": ((d,), "norm"), "wq": ((d, H * D), "matrix"),
        "wk": ((d, K * D), "matrix"), "wv": ((d, K * D), "matrix"),
        "wo": ((H * D, d), "matrix"), "norm2": ((d,), "norm"),
        "router": ((d, E), "matrix"), "w_gate": ((E, d, F), "matrix"),
        "w_up": ((E, d, F), "matrix"), "w_down": ((E, F, d), "matrix"),
    }
    out = weights.draw_stack(base, table, dm["L"])
    for n, shape, role in (("embed", (dm["V"], d), "embed"),
                           ("final_norm", (d,), "norm"),
                           ("head", (d, dm["V"]), "matrix")):
        out[n] = weights.draw(weights.name_key(base, n), shape, role)
    return out


def canonical(dm: dict, seed: int, device=None) -> dict:
    return weights.canonical(_make, dm, seed, device)


def program_params(canon: dict, abstract_tree: dict) -> dict:
    return weights.place(canon, abstract_tree, PROGRAM_PATHS)


@functools.partial(jax.jit, static_argnames=("dm_items", "control"))
def _layer(h, layer, W, *, dm_items, control):
    dm = dict(dm_items)
    H, K, D, E, eps = dm["H"], dm["K"], dm["D"], dm["E"], dm["eps"]
    S = h.shape[0]
    w = {n: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
         for n, a in W.items()}
    pos = jnp.arange(S, dtype=jnp.int32)

    x = ref.rms(h, w["norm1"], eps)
    q, k, v = (ref.rows(lambda r, n=n: ref.dot(r, w[n], control), x)
               for n in ("wq", "wk", "wv"))
    q = ref.rope(q.reshape(S, H, D), pos, dm["theta"])
    k = ref.rope(k.reshape(S, K, D), pos, dm["theta"])
    o = ref.causal_attention(q, k, v.reshape(S, K, D), pos)
    h = h + ref.rows(lambda r: ref.dot(r, w["wo"], control), o)

    x = ref.rms(h, w["norm2"], eps)

    def moe(r):
        gates = jax.nn.softmax(ref.dot(r, w["router"], control), axis=-1)
        top, chosen = jax.lax.top_k(gates, dm["top"])
        top = top / jnp.sum(top, axis=-1, keepdims=True)
        share = jnp.sum(jax.nn.one_hot(chosen, E) * top[..., None], axis=1)
        out = jnp.zeros_like(r)
        for e in range(E):
            g = ref.dot(r, w["w_gate"][e], control)
            u = ref.dot(r, w["w_up"][e], control)
            out = out + share[:, e:e + 1] * ref.dot(
                jax.nn.silu(g) * u, w["w_down"][e], control)
        return out

    return h + ref.rows(moe, x)


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def _head(h, final_norm, head, *, eps, control):
    return ref.dot(ref.rms(h, final_norm, eps), head, control)


def served_logits(canon: dict, dm: dict, seqs, *, control: bool = False):
    dm_items = tuple(sorted(dm.items()))
    layer_w = {n: a for n, a in canon.items() if n not in GLOBALS}

    def forward(ids, first, n):
        h = jnp.take(canon["embed"], ids, axis=0).astype(jnp.float32)
        for layer in range(dm["L"]):
            h = _layer(h, layer, layer_w, dm_items=dm_items, control=control)
        return _head(h[first:n], canon["final_norm"], canon["head"],
                     eps=dm["eps"], control=control)

    return ref.over_sequences(seqs, forward)


def forward_flops(dm: dict, start: int, stop: int) -> int:
    """Attention and output projections, the router, the chosen experts and
    the head for each token, and the attention products."""
    d, H, K, D = dm["d"], dm["H"], dm["K"], dm["D"]
    layer = 2 * d * H * D + 2 * d * K * D + d * dm["E"] \
        + dm["top"] * 3 * d * dm["F"]
    per_token = dm["L"] * layer + d * dm["V"]
    return 2 * per_token * (stop - start) \
        + 4 * dm["L"] * H * D * causal_keys(start, stop)


def decode_bytes(dm: dict, kv_lens):
    """None: which experts a step reads is the router's choice, not a
    function of the lengths."""
    return None
