import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (assignment deliverable e).

For every (architecture x input shape) cell, on the single-pod (16,16) and
multi-pod (2,16,16) meshes: build the paper-faithful default weave, lower
the step with explicit in_shardings, .compile(), print memory_analysis()
(proves the per-device footprint) and cost_analysis() FLOPs/bytes, parse
the collective schedule, and write the roofline artifact JSON that
EXPERIMENTS.md §Dry-run/§Roofline and benchmarks/roofline_report.py read.

Usage:
  python -m repro.launch.dryrun --arch yi-6b --shape train_4k
  python -m repro.launch.dryrun --all            # every cell, both meshes
  python -m repro.launch.dryrun --all --mesh pod # 40-cell baseline table
  ... --set accum_steps=8 --set opt_state_dtype=bfloat16   (hillclimb knobs)
"""

import argparse
import json
import time
import traceback
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import SHAPES
from repro.core.program import Program
from repro.distributed.sharding import input_shardings, param_shardings
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.launch.weave import default_weave
from repro.models.registry import ARCHS, cells, get_config, input_specs, skipped_cells
from repro.nn.module import abstract_params
from repro.optim import adamw
from repro.optim.adamw import AdamWConfig
from repro.roofline import analysis
from repro.runtime.steps import (
    build_decode_step,
    build_prefill_step,
    build_train_step,
    step_flops,
)

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "artifacts", "dryrun")


def _parse_set(values: list[str]) -> dict[str, Any]:
    out: dict[str, Any] = {}
    for kv in values or []:
        k, v = kv.split("=", 1)
        try:
            out[k] = json.loads(v)
        except json.JSONDecodeError:
            out[k] = v
    return out


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               overrides: dict[str, Any] | None = None,
               artifact_suffix: str = "", verbose: bool = True,
               roofline: bool = True) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"
    chips = mesh.devices.size

    program = Program.from_arch(arch, kind=shape.kind)
    woven = default_weave(program, shape, dict(mesh.shape), overrides=overrides)
    state = woven.state
    rules = state.rules

    params_sds = abstract_params(program.model, state.policies)
    ps_params = param_shardings(program.model, mesh, rules)
    specs = input_specs(cfg, shape)
    ps_inputs = input_shardings(specs["inputs"], mesh, rules)
    repl = NamedSharding(mesh, P())

    t0 = time.perf_counter()
    if shape.kind == "train":
        opt_cfg = AdamWConfig(
            compression=bool(state.extra.get("grad_compression", False)),
            state_dtype=str(state.extra.get("opt_state_dtype", "float32")),
        )
        opt_sds = adamw.abstract_state(params_sds, opt_cfg)
        ps_opt = {
            "master": ps_params,
            "m": ps_params,
            "v": ps_params,
            "count": repl,
        }
        if opt_cfg.compression:
            ps_opt["ef"] = ps_params
        step_fn = build_train_step(woven, mesh=mesh, opt_cfg=opt_cfg)
        jitted = jax.jit(
            step_fn,
            in_shardings=(ps_params, ps_opt, ps_inputs, repl),
            donate_argnums=(0, 1),
        )
        lowered = jitted.lower(params_sds, opt_sds, specs["inputs"],
                               jax.ShapeDtypeStruct((), jnp.int32))
    elif shape.kind == "prefill":
        step_fn = build_prefill_step(woven, mesh=mesh)
        jitted = jax.jit(step_fn, in_shardings=(ps_params, ps_inputs))
        lowered = jitted.lower(params_sds, specs["inputs"])
    else:  # decode
        cache_sds = specs["cache"]
        ps_cache = input_shardings(cache_sds, mesh, rules)
        step_fn = build_decode_step(woven, mesh=mesh)
        jitted = jax.jit(
            step_fn,
            in_shardings=(ps_params, ps_inputs, ps_cache),
            donate_argnums=(2,),
        )
        lowered = jitted.lower(params_sds, specs["inputs"], cache_sds)
    lower_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0

    mem = compiled.memory_analysis()
    if verbose:
        print(f"[{arch} x {shape_name} x {mesh_name}] lower={lower_s:.1f}s "
              f"compile={compile_s:.1f}s")
        print(mem)
        cost = analysis.cost_properties(compiled)
        print({k: cost[k] for k in ("flops", "bytes accessed")
               if k in cost})

    roof = analysis.from_compiled(
        arch, shape_name, mesh_name, chips, compiled,
        model_flops=step_flops(cfg, shape),
    )
    hbm = analysis.hbm_per_device(roof)
    # Analytic TPU HBM estimate: the CPU backend's temp_size carries a
    # structural multiplier (bwd-loop state copies, double buffering, weak
    # elementwise fusion — measured ~10x the ideal boundary stack on a
    # minimal rematted scan), so the v5e fit verdict uses
    #   state (argument bytes, exact) + remat boundary stack + transients.
    accum_used = int(state.extra.get("accum_steps", 1))
    data_shards = 1
    batch_rule = rules.get("batch") or ()
    if isinstance(batch_rule, str):
        batch_rule = (batch_rule,)
    for a in batch_rule:
        if a in mesh.shape:
            data_shards *= mesh.shape[a]
    model_shards = mesh.shape.get("model", 1) if rules.get("res_seq") else 1
    if shape.kind == "train":
        tokens_micro = shape.global_batch * shape.seq_len / max(accum_used, 1)
        n_layers = cfg.num_layers + (cfg.enc_layers if cfg.family == "encdec" else 0)
        boundary = n_layers * tokens_micro * cfg.d_model * 2 / (
            min(data_shards, shape.global_batch // max(accum_used, 1) or 1)
            * model_shards
        )
    else:
        boundary = 0.0
    analytic_hbm = float(roof.memory_per_device["argument"] + boundary + 3 * 2**30)
    hbm_fits = analytic_hbm <= (16 << 30)
    record = roof.to_json()
    record.update({
        "lower_s": lower_s, "compile_s": compile_s,
        "hbm_per_device": hbm,
        "analytic_hbm_per_device": analytic_hbm,
        "hbm_fits_v5e": hbm_fits,
        "accum_steps": state.extra.get("accum_steps", 1),
        "remat": state.extra.get("remat"),
        "rules": {k: list(v) if isinstance(v, tuple) else v
                  for k, v in rules.items()},
        "overrides": overrides or {},
        "ok": True,
    })
    if verbose:
        print(f"  (raw HLO, loop-bodies-once) compute={roof.compute_s*1e3:.2f}ms "
              f"memory={roof.memory_s*1e3:.2f}ms "
              f"collective={roof.collective_s*1e3:.2f}ms; "
              f"hbm/dev cpu={hbm/2**30:.2f}GiB "
              f"analytic={analytic_hbm/2**30:.2f}GiB fits_v5e={hbm_fits}")

    if roofline:
        from repro.roofline.components import compose_cell

        record["roofline"] = compose_cell(
            arch, shape_name, multi_pod=multi_pod, overrides=overrides,
            verbose=verbose,
        )

    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    fname = f"{arch}__{shape_name}__{mesh_name}{artifact_suffix}.json"
    with open(os.path.join(ARTIFACT_DIR, fname), "w") as f:
        json.dump(record, f, indent=1)
    return record


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--shape", choices=sorted(SHAPES), default=None)
    ap.add_argument("--mesh", choices=("pod", "multipod", "both"), default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--set", dest="sets", action="append", default=[],
                    help="weave override key=value (JSON values)")
    ap.add_argument("--suffix", default="", help="artifact filename suffix")
    args = ap.parse_args()
    use_compile_cache()

    overrides = _parse_set(args.sets)
    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]

    todo: list[tuple[str, str]] = []
    if args.all:
        for arch in ARCHS:
            for shape in cells(arch):
                todo.append((arch, shape))
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        todo = [(args.arch, args.shape)]

    failures = []
    for arch, shape in todo:
        if shape not in cells(arch):
            print(f"SKIP {arch} x {shape}: not supported (see DESIGN.md §5)")
            continue
        for mp in meshes:
            try:
                lower_cell(arch, shape, multi_pod=mp,
                           overrides=dict(overrides),
                           artifact_suffix=args.suffix)
            except Exception as e:
                failures.append((arch, shape, mp, repr(e)))
                traceback.print_exc()
    for arch, shape, reason in (skipped_cells() if args.all else []):
        print(f"NOTED SKIP {arch} x {shape}: {reason}")
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print(" ", f)
        return 1
    print(f"\nDry-run green: {len(todo)} cells x meshes={args.mesh}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
