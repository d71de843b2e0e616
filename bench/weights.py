"""Weights of a configuration, drawn on the device from `--seed`: the part
that every family (`families/<name>.py`) shares.

A family's `canonical(dm, seed, device)` hands `canonical` below a `make`
function that builds every tensor of its model under plain names (per-layer
tensors stacked on a leading layer axis, `draw_stack`) from a seed's two
words, each tensor drawn from its own key (`name_key`) with the spread its
role asks for (`draw`).  `canonical` runs `make` as one jitted call, in the
dtype the weights are served in.  The seed is a traced argument, so the
call compiles once for all seeds, and a second call with the same seed
gives the same bits: the program serves the first set and the reference
rebuilds its own from the seed once the program's state is freed.  `place`
lays the same arrays out as the program's parameter tree, by a family's
table of paths.
"""

from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np

# standard deviation of each role's entries; "matrix" is 1/sqrt(fan-in),
# "norm" is a float32 scale of 1 + 0.1 U (the program stores norms so)
ROLE_STD = {"embed": 1.0, "bias": 0.5}


def seed_words(seed: int) -> np.ndarray:
    """A seed of any size as two uint32 words (low, high)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    return np.asarray([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF],
                      np.uint32)


def base_key(words):
    """The key of a whole set of weights, from `seed_words`."""
    return jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])


def name_key(base, name: str):
    """The key of the tensor called `name`."""
    return jax.random.fold_in(base, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def draw(key, shape: tuple[int, ...], role: str):
    """One tensor: uniform with the standard deviation its role asks for,
    bfloat16 but for norms.  A matrix's fan-in is its second-last axis."""
    u = jax.random.uniform(key, shape, jnp.float32, -1.0, 1.0) * np.sqrt(3.0)
    if role == "norm":
        return 1.0 + 0.1 * u
    std = ROLE_STD.get(role) or 1.0 / np.sqrt(shape[-2])
    return (u * std).astype(jnp.bfloat16)


def draw_stack(base, table: dict, count: int) -> dict:
    """`count` layers of the tensors in `table` ({name: (shape, role)}),
    stacked on a leading axis; layer i of `name` is drawn from
    `name_key(base, name)` folded with i."""
    keys = {n: name_key(base, n) for n in table}

    def one_layer(layer):
        return {n: draw(jax.random.fold_in(keys[n], layer), shape, role)
                for n, (shape, role) in table.items()}

    return jax.lax.map(one_layer, jnp.arange(count))


@functools.lru_cache(maxsize=None)
def _maker(make, dm_items, device):
    sharding = jax.sharding.SingleDeviceSharding(device)
    return jax.jit(functools.partial(make, dm_items=dm_items),
                   out_shardings=sharding)


def canonical(make, dm: dict, seed: int, device=None) -> dict:
    """`make(words, dm_items=...)` run on `device` as one jitted call for
    the seed's words: every tensor of the model, {name: array}."""
    device = device or jax.devices()[0]
    words = jax.device_put(seed_words(seed), device)
    return _maker(make, tuple(sorted(dm.items())), device)(words)


def place(canon: dict, abstract_tree: dict, paths: dict) -> dict:
    """`canon` laid out as the program's parameter tree, whose shapes and
    dtypes `abstract_tree` gives; `paths` maps each leaf's key path to a
    plain name.  Every leaf must map, and shapes and dtypes must agree."""
    def one(path, leaf):
        keys = tuple(getattr(p, "key", p) for p in path)
        name = paths.get(keys)
        if name is None or name not in canon:
            raise KeyError(f"no benchmark tensor for program leaf {keys}")
        arr = canon[name]
        if arr.shape != leaf.shape or arr.dtype != leaf.dtype:
            raise ValueError(f"{keys}: benchmark {arr.shape} {arr.dtype} "
                             f"!= program {leaf.shape} {leaf.dtype}")
        return arr

    return jax.tree_util.tree_map_with_path(one, abstract_tree)
