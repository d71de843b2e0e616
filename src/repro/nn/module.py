"""Minimal composable module system for the ANTAREX-JAX framework.

Design goals (see DESIGN.md §2): the *functional* model definition is a tree
of `Module` objects with explicit parameter specs carrying *logical axis
names*.  All extra-functional concerns — dtype policies, kernel
implementation selection, sharding rules, remat, monitoring taps — live in a
`Ctx` object that the ANTAREX weaver builds from aspects.  The model code
consults the Ctx; it is never edited.

Parameters are plain nested dicts of jax arrays (a pytree), so they compose
with jit/grad/scan without any framework magic.
"""

from __future__ import annotations

import dataclasses
import fnmatch
import functools
import zlib
from typing import Any, Callable, Iterator, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.nn.dtypes import DTypePolicy, PolicyResolver

# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

Initializer = str  # "normal" | "zeros" | "ones" | "scaled" | "embedding"


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """Declarative description of one parameter tensor.

    ``axes`` holds one *logical* axis name (or None) per dimension; the
    distributed layer maps logical axes to mesh axes (distributed/sharding).
    ``dtype`` of None means "the woven dtype policy decides" (the common
    case); norms etc. may pin fp32.
    """

    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    init: Initializer = "normal"
    scale: float | None = None  # stddev for "normal", fan-in override for "scaled"
    dtype: Any | None = None  # None -> policy param_dtype

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(
                f"ParamSpec shape {self.shape} and axes {self.axes} rank mismatch"
            )

    def instantiate(self, key: jax.Array, policy: DTypePolicy) -> jax.Array:
        """Draw this tensor on the device that holds `key`.  One jitted
        program per spec: the fp32 normal draw fuses into the cast, so a
        stacked (layers, d, f) leaf never has a full-size fp32 transient."""
        dtype = self.dtype if self.dtype is not None else policy.param_dtype
        if self.init not in ("zeros", "ones", "normal", "scaled", "embedding"):
            raise ValueError(f"unknown initializer {self.init!r}")
        return _draw(key, spec=self, dtype=jnp.dtype(dtype))

    def shape_dtype(self, policy: DTypePolicy) -> jax.ShapeDtypeStruct:
        dtype = self.dtype if self.dtype is not None else policy.param_dtype
        return jax.ShapeDtypeStruct(self.shape, dtype)


# keep_unused: zeros/ones never read the key, but it names the device
@functools.partial(jax.jit, static_argnames=("spec", "dtype"),
                   keep_unused=True)
def _draw(key: jax.Array, *, spec: ParamSpec, dtype) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    if spec.init == "normal":
        std = spec.scale if spec.scale is not None else 0.02
    elif spec.init == "scaled":  # 1/sqrt(fan_in) truncated-normal-ish
        fan_in = spec.scale if spec.scale is not None else spec.shape[0]
        std = 1.0 / np.sqrt(max(fan_in, 1))
    else:  # "embedding"
        std = spec.scale if spec.scale is not None else 1.0

    def normal(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)

    if spec.axes and spec.axes[0] == "layers":
        # a stacked (layers, ...) leaf draws one layer slice per key: the
        # TPU compiler builds that ~10x faster than one whole-stack draw
        keys = jax.random.split(key, spec.shape[0])
        return jax.lax.map(lambda k: normal(k, spec.shape[1:]), keys)
    return normal(key, spec.shape)


# ---------------------------------------------------------------------------
# Weave-time context
# ---------------------------------------------------------------------------


class Ctx:
    """Carries every woven extra-functional decision through `apply`.

    The weaver (repro/core) builds one of these; model code only *reads* it.
    All fields are trace-time constants except `taps`, which accumulates
    monitor values (jax arrays) during tracing.
    """

    def __init__(
        self,
        *,
        policies: PolicyResolver | None = None,
        impls: Sequence[tuple[str, str, str]] = (),  # (pattern, op_kind, impl)
        mesh: jax.sharding.Mesh | None = None,
        rules: Mapping[str, Any] | None = None,  # logical axis -> mesh axes
        taps_enabled: Sequence[str] = (),  # glob patterns of tap names to record
        deterministic: bool = True,
        rng: jax.Array | None = None,
        extra: Mapping[str, Any] | None = None,
    ):
        self.policies = policies or PolicyResolver.default()
        self.impls = list(impls)
        self.mesh = mesh
        self.rules = dict(rules or {})
        self.taps_enabled = list(taps_enabled)
        self.deterministic = deterministic
        self.rng = rng
        self.extra = dict(extra or {})
        self.taps: dict[str, jax.Array] = {}
        self._path: list[str] = []

    # -- path scoping --------------------------------------------------------

    def scope(self, name: str) -> "_Scope":
        return _Scope(self, name)

    @property
    def path(self) -> str:
        return "/".join(self._path)

    # -- policy / impl resolution --------------------------------------------

    def policy(self) -> DTypePolicy:
        return self.policies.resolve(self.path)

    def impl(self, op_kind: str, default: str) -> str:
        """Resolve the woven implementation for an op kind at current path."""
        chosen = default
        for pattern, kind, impl in self.impls:
            if kind == op_kind and fnmatch.fnmatch(self.path, pattern):
                chosen = impl
        return chosen

    # -- monitoring taps -------------------------------------------------------

    def tap(self, name: str, value: jax.Array) -> None:
        full = f"{self.path}/{name}" if self.path else name
        for pattern in self.taps_enabled:
            if fnmatch.fnmatch(full, pattern):
                self.taps[full] = jnp.asarray(value, jnp.float32)
                return

    # -- sharding constraints --------------------------------------------------

    def constrain(self, x: jax.Array, logical_axes: tuple[str | None, ...]) -> jax.Array:
        if self.mesh is None or not self.rules:
            return x
        from repro.distributed.sharding import logical_to_pspec

        if len(logical_axes) != x.ndim:
            return x
        # activations use "embed_act" (params' "embed" may be FSDP-sharded
        # over the data axis — never wanted on activations)
        axes = tuple("embed_act" if a == "embed" else a for a in logical_axes)
        spec = logical_to_pspec(axes, self.rules, self.mesh, x.shape)
        if spec is None:
            return x
        sharding = jax.sharding.NamedSharding(self.mesh, spec)
        return jax.lax.with_sharding_constraint(x, sharding)


class _Scope:
    def __init__(self, ctx: Ctx, name: str):
        self.ctx, self.name = ctx, name

    def __enter__(self):
        self.ctx._path.append(self.name)
        return self.ctx

    def __exit__(self, *exc):
        self.ctx._path.pop()
        return False


# ---------------------------------------------------------------------------
# Module base
# ---------------------------------------------------------------------------


class Module:
    """A named tree node with parameter specs and a pure apply.

    Subclasses define ``kind`` (the joinpoint kind the ANTAREX selectors match
    on), implement ``spec()`` returning ``{name: ParamSpec | Module}``, and a
    ``__call__(params, ..., ctx=ctx)``.
    """

    kind: str = "module"
    name: str = "module"

    def spec(self) -> dict[str, "ParamSpec | Module"]:
        raise NotImplementedError

    # Attributes exposed to ANTAREX selectors (LARA joinpoint attributes).
    def attrs(self) -> dict[str, Any]:
        out = {}
        for k, v in vars(self).items():
            if isinstance(v, (int, float, str, bool, tuple)) and not k.startswith("_"):
                out[k] = v
        return out

    # -- tree walking ----------------------------------------------------------

    def walk(self, prefix: str = "") -> Iterator[tuple[str, "Module"]]:
        """Yield (path, module) for this module and all descendants."""
        path = f"{prefix}/{self.name}" if prefix else self.name
        yield path, self
        for child_name, child in self.spec().items():
            if isinstance(child, Module):
                yield from child.walk(path)

    def param_specs(self, prefix: str = "") -> dict[str, Any]:
        """Nested dict mirroring the params pytree, of ParamSpec leaves."""
        out: dict[str, Any] = {}
        for child_name, child in self.spec().items():
            if isinstance(child, Module):
                out[child_name] = child.param_specs()
            else:
                out[child_name] = child
        return out


# ---------------------------------------------------------------------------
# Param tree utilities
# ---------------------------------------------------------------------------


def _key_for(path: str, key: jax.Array) -> jax.Array:
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def _walk_spec(value, path: str, leaf_fn) -> Any:
    """Generic recursion over spec trees (Module | dict | ParamSpec leaves)."""
    if isinstance(value, Module):
        sub_path = f"{path}/{value.name}" if path else value.name
        return {
            name: _walk_spec(child, sub_path, leaf_fn)
            for name, child in value.spec().items()
        }
    if isinstance(value, Mapping):
        return {
            name: _walk_spec(child, f"{path}/{name}" if path else name, leaf_fn)
            for name, child in value.items()
        }
    return leaf_fn(value, path)


def flatten_specs(module: Module) -> dict[str, ParamSpec]:
    """Flat {path: ParamSpec} (paths relative to, and including, module.name)."""
    flat: dict[str, ParamSpec] = {}

    def leaf(spec: ParamSpec, path: str):
        flat[path] = spec
        return spec

    _walk_spec(module, "", leaf)
    return flat


def init_params(
    module: Module, key: jax.Array, policies: PolicyResolver | None = None,
    *, device: jax.Device | None = None,
) -> dict[str, Any]:
    """Materialize the parameter pytree (nested dicts keyed by module names),
    leaf by leaf on `device` (default: JAX's default device)."""
    policies = policies or PolicyResolver.default()
    if device is not None:
        key = jax.device_put(key, device)

    def leaf(spec: ParamSpec, path: str):
        return spec.instantiate(_key_for(path, key), policies.resolve(path))

    return _walk_spec(module, "", leaf)


def abstract_params(
    module: Module, policies: PolicyResolver | None = None
) -> dict[str, Any]:
    """ShapeDtypeStruct pytree — used by the dry-run (no allocation)."""
    policies = policies or PolicyResolver.default()

    def leaf(spec: ParamSpec, path: str):
        return spec.shape_dtype(policies.resolve(path))

    return _walk_spec(module, "", leaf)


def param_axes(module: Module) -> dict[str, Any]:
    """Pytree of logical-axes tuples matching the params pytree structure."""
    return _walk_spec(module, "", lambda spec, path: spec.axes)


def param_count(module: Module) -> int:
    return int(sum(np.prod(s.shape) for s in flatten_specs(module).values()))


def cast(x: jax.Array, dtype) -> jax.Array:
    return x if x.dtype == dtype else x.astype(dtype)
