#!/usr/bin/env python3
"""Chip smoke test: serve yi-6b at its published widths on a TPU.

    python chip_smoke.py             # one chip: phase A (XLA attention) and
                                     # phase B (Pallas kernels), compared
    python chip_smoke.py --chips 4   # four chips: a 4-replica ServingFleet,
                                     # compared with one Server

yi-6b (32 layers, d_model 4096, 32/4 heads, d_ff 11008, vocab 64000) is
built at full width with random weights drawn from `--seed` (about 12.1 GB
of bf16 params) and served through `Server.serve_stream`, the normal
serving path: a paged bf16 KV pool, chunked prefill and prefix sharing.
Everything runs in this one process, because a chip belongs to one process
at a time.

The script exits non-zero, and prints no result line, when JAX finds no
TPU or any check fails.  On success the last line of stdout is exactly
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}`.
Times printed on earlier lines are informational, not metrics.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "yi-6b"
PAGE = 128          # pool page size (tokens)
CHUNK = 256         # chunked-prefill tokens per wave
DECODE = 32         # tokens decoded per request
SHARED = 256        # prefix the sharer takes from its donor
# first-token logits of phase A (XLA attention) and phase B (Pallas
# kernels) must agree to within LOGIT_RTOL of phase A's largest |logit|.
# Both phases read the same bf16 params and round every layer's attention
# output to bf16 (relative step 2^-8), but at different points: XLA's
# softmax and the flash kernels' online softmax sum in different orders.
# Treating the 32 layers' rounding differences as a random walk gives
# about sqrt(32) * 2^-8 = 2.2% of the logit scale; 2^-4 leaves ~3x room
# for that while a wrong mask, a wrong page or a wrong scale (errors of
# the order of the logits themselves) still fails.
LOGIT_RTOL = 2.0 ** -4


def smoke_mix(vocab: int, seed: int):
    """Eight prompts over 64..1024 tokens and their arrival waves.  Prompt
    4 (512 tokens) donates its first SHARED tokens to prompt 7, which
    arrives at wave 4, after the donor's prefill has finished, so its
    admission maps the donor's pages instead of recomputing them."""
    rng = np.random.default_rng(seed)
    lengths = [1024, 64, 768, 256, 512, 128, 512]
    prompts = [rng.integers(0, vocab, n).astype(np.int64) for n in lengths]
    sharer = np.concatenate([prompts[4][:SHARED],
                             rng.integers(0, vocab, 256)]).astype(np.int64)
    return prompts + [sharer], [0] * len(lengths) + [4]


def fleet_mix(vocab: int, seed: int):
    """Four sessions of two requests: a 256-token system prompt each, then
    a 128-token user turn.  Sharers route to their session's replica."""
    rng = np.random.default_rng(seed)
    systems = [rng.integers(0, vocab, 256) for _ in range(4)]
    return [np.concatenate([systems[s], rng.integers(0, vocab, 128)])
            .astype(np.int64) for _ in range(2) for s in range(4)]


def server_config(prompts, seed: int, *, chunk: int | None):
    from repro.runtime.server import ServerConfig

    return ServerConfig(max_cache_len=max(len(p) for p in prompts) + DECODE,
                        decode_tokens=DECODE, seed=seed, page_size=PAGE,
                        prefill_chunk=chunk)


def abstract(tree):
    import jax

    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        tree)


class StepTap:
    """Wraps a server's step dispatch (a LibVC): keeps the last-position
    logits of each prefill call that completes a prompt (the logits
    serve_stream takes that request's first token from) and the abstract
    arguments of one call whose prefix_len satisfies `want`, so its
    compiled program can be inspected after serving."""

    def __init__(self, vc, prompts, want=lambda prefix_len: True):
        self.vc, self.prompts, self.want = vc, prompts, want
        self.first_logits: dict[int, np.ndarray] = {}
        self.args = None
        self.kwargs: dict = {}

    def __call__(self, variant, params, inputs, cache, **kw):
        if self.args is None and self.want(kw.get("prefix_len", 0)):
            self.args, self.kwargs = abstract((params, inputs, cache)), kw
        logits, new_cache = self.vc(variant, params, inputs, cache, **kw)
        end = int(inputs["positions"][0, -1]) + 1
        toks = np.asarray(inputs["tokens"][0])
        for rid, p in enumerate(self.prompts):
            if len(p) == end and np.array_equal(p[end - len(toks):], toks):
                self.first_logits[rid] = np.asarray(logits[0, -1], np.float32)
        return logits, new_cache

    def compiled_text(self) -> str:
        fn = self.vc.get(None)
        return fn.lower(*self.args, **self.kwargs).compile().as_text()


def stream(server, prompts, arrivals=None):
    """Drive serve_stream to the end; returns the per-request tokens."""
    gen = server.serve_stream(prompts, arrival_waves=arrivals)
    while True:
        try:
            next(gen)
        except StopIteration as stop:
            return stop.value


def peak_gb(device) -> float:
    return device.memory_stats()["peak_bytes_in_use"] / 1e9


def check(ok: bool, what: str, failures: list[str]) -> None:
    print(f"  {'pass' if ok else 'FAIL'}: {what}", flush=True)
    if not ok:
        failures.append(what)


def run_phase(name, woven, prompts, arrivals, device, seed, failures,
              *, kernels: bool):
    """Build a Server, serve the mix twice (the first serve compiles every
    step shape, the second reuses them) and check what came out."""
    import jax

    from repro.runtime.server import Server

    t = time.perf_counter()
    server = Server(woven, server_config(prompts, seed, chunk=CHUNK),
                    device=device)
    jax.block_until_ready(server.params)
    print(f"phase {name}: params built in {time.perf_counter() - t:.1f}s, "
          f"peak {peak_gb(device):.3f} GB", flush=True)
    prefill = StepTap(server.paged_prefill_vc, prompts,
                      want=lambda prefix_len: prefix_len > 0)
    decode = StepTap(server.decode_vc, [])
    server.paged_prefill_vc, server.decode_vc = prefill, decode

    t = time.perf_counter()
    first = stream(server, prompts, arrivals)
    t_first = time.perf_counter() - t
    outcomes_first = list(server.last_outcomes)
    t = time.perf_counter()
    second = stream(server, prompts, arrivals)
    t_second = time.perf_counter() - t
    pool = server.last_pool_stats
    print(f"phase {name}: first serve {t_first:.1f}s (compiles + serve), "
          f"second serve {t_second:.1f}s; compile ~{t_first - t_second:.1f}s; "
          f"peak {peak_gb(device):.3f} GB; prefix hits {pool['prefix_hits']}",
          flush=True)

    statuses = [o["status"] for o in outcomes_first + server.last_outcomes]
    check(all(s == "ok" for s in statuses),
          f"phase {name}: every outcome ok ({statuses.count('ok')}/"
          f"{len(statuses)})", failures)
    check(all(len(o) == DECODE for o in first),
          f"phase {name}: {DECODE} tokens per request", failures)
    check(all(np.array_equal(a, b) for a, b in zip(first, second)),
          f"phase {name}: the second serve repeats the first's tokens",
          failures)
    logits = prefill.first_logits
    check(len(logits) == len(prompts),
          f"phase {name}: first-token logits of {len(logits)}/{len(prompts)} "
          f"requests captured", failures)
    check(all(np.isfinite(v).all() for v in logits.values()),
          f"phase {name}: first-token logits finite", failures)
    check(pool["prefix_hits"] > 0,
          f"phase {name}: prefix pages shared ({pool['prefix_hits']})",
          failures)
    if kernels:
        for step, tap in (("decode", decode), ("chunked prefill", prefill)):
            check(tap.args is not None and
                  "tpu_custom_call" in tap.compiled_text(),
                  f"phase {name}: compiled {step} step holds tpu_custom_call",
                  failures)
    return server, first, logits


def one_chip(args, device, failures) -> None:
    import jax

    from repro.configs.base import SHAPES
    from repro.core.program import Program
    from repro.core.strategies.kernels import KernelAspect
    from repro.launch.weave import default_weave

    program = Program.from_arch(ARCH, kind="serve")
    cfg = program.cfg
    print(f"model {ARCH}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
          f"heads {cfg.n_heads}/{cfg.kv_heads}, d_ff {cfg.d_ff}, "
          f"vocab {cfg.vocab}", flush=True)
    prompts, arrivals = smoke_mix(cfg.vocab, args.seed)
    print(f"mix: {len(prompts)} requests, prompt lengths "
          f"{[len(p) for p in prompts]}, arrival waves {arrivals}, "
          f"{DECODE} decode tokens, chunk {CHUNK}, page {PAGE}", flush=True)

    woven_a = default_weave(program, SHAPES["prefill_32k"], {})
    server, tokens_a, logits_a = run_phase("A", woven_a, prompts, arrivals,
                                           device, args.seed, failures,
                                           kernels=False)
    leaves = jax.tree.leaves(server.params)
    n_params = sum(x.size for x in leaves)
    n_bytes = sum(x.size * x.dtype.itemsize for x in leaves)
    print(f"params: {n_params} ({n_bytes} bytes, {n_bytes / 1e9:.3f} GB); "
          f"ModelConfig.param_count {cfg.param_count()} (omits the final "
          f"norm)", flush=True)
    del server, leaves
    gc.collect()  # two 12 GB copies do not fit: free A before building B

    woven_b = default_weave(program, SHAPES["prefill_32k"], {},
                            extra_aspects=[KernelAspect("*", "attention",
                                                        "pallas")])
    server, tokens_b, logits_b = run_phase("B", woven_b, prompts, arrivals,
                                           device, args.seed, failures,
                                           kernels=True)
    del server
    gc.collect()

    worst = 0.0
    for rid in sorted(logits_a):
        if rid not in logits_b:
            continue
        a, b = logits_a[rid], logits_b[rid]
        rel = float(np.max(np.abs(a - b)) / max(np.max(np.abs(a)), 1e-30))
        worst = max(worst, rel)
        print(f"  rid {rid}: first-token logits max|A-B|/max|A| = {rel:.3e}, "
              f"argmax A {int(np.argmax(a))} B {int(np.argmax(b))}")
    check(len(logits_b) == len(logits_a) and worst <= LOGIT_RTOL,
          f"first-token logits agree between A and B "
          f"(worst {worst:.3e} <= {LOGIT_RTOL:.3e})", failures)
    same = sum(int(np.sum(a == b)) for a, b in zip(tokens_a, tokens_b))
    total = sum(len(a) for a in tokens_a)
    print(f"greedy tokens equal between A and B: {same}/{total}", flush=True)


def four_chips(args, devices, failures) -> None:
    import jax

    from repro.configs.base import SHAPES
    from repro.core.program import Program
    from repro.launch.weave import default_weave
    from repro.runtime.fleet import ServingFleet
    from repro.runtime.server import Server

    program = Program.from_arch(ARCH, kind="serve")
    woven = default_weave(program, SHAPES["prefill_32k"], {})
    prompts = fleet_mix(program.cfg.vocab, args.seed)
    cfg = server_config(prompts, args.seed, chunk=None)
    t = time.perf_counter()
    fleet = ServingFleet(lambda **kw: Server(woven, cfg, **kw),
                         replicas=4, devices=devices[:4])
    for rep in fleet.replicas:
        jax.block_until_ready(rep.server.params)
    print(f"fleet: 4 replicas built in {time.perf_counter() - t:.1f}s",
          flush=True)
    placed = []
    for rep in fleet.replicas:
        devs = {d for x in jax.tree.leaves(rep.server.params)
                for d in x.devices()}
        placed.append(devs)
        print(f"  replica {rep.host}: params on {sorted(map(str, devs))}")
    check(all(len(d) == 1 for d in placed)
          and len(set.union(*placed)) == 4,
          "each replica's params on its own device", failures)

    # the one-server reference: replica 0 serves the whole mix alone
    t = time.perf_counter()
    ref = stream(fleet.replicas[0].server, prompts)
    ref_ok = all(o["status"] == "ok"
                 for o in fleet.replicas[0].server.last_outcomes)
    print(f"one server: {time.perf_counter() - t:.1f}s", flush=True)
    t = time.perf_counter()
    outs = fleet.serve(prompts, decode_tokens=DECODE)
    stats = fleet.last_fleet_stats
    print(f"fleet serve: {time.perf_counter() - t:.1f}s, "
          f"{stats['rounds']} round(s), affinity hits "
          f"{stats['affinity_hits']}, outcomes {stats['outcomes']}",
          flush=True)
    check(ref_ok, "one-server reference: every outcome ok", failures)
    check(all(o["status"] == "ok" for o in fleet.last_outcomes),
          "fleet: every outcome ok", failures)
    check(all(np.array_equal(a, b) for a, b in zip(ref, outs)),
          "fleet tokens equal the one-server tokens", failures)
    check(stats["affinity_hits"] > 0, "prefix-affinity routing hit",
          failures)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-replica fleet path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    d0 = devices[0]
    print(f"device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devices)}", flush=True)
    if d0.platform != "tpu":
        print("no TPU: chip_smoke.py runs only on a TPU "
              f"(JAX found {d0.platform!r})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} TPU devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import use_compile_cache

    cache_dir, from_env = use_compile_cache()
    print(f"compile cache: {cache_dir} ("
          f"{'from JAX_COMPILATION_CACHE_DIR' if from_env else 'repo default'}"
          ")", flush=True)

    failures: list[str] = []
    t = time.perf_counter()
    if args.chips == 4:
        four_chips(args, devices, failures)
    else:
        one_chip(args, d0, failures)
    print(f"total {time.perf_counter() - t:.1f}s", flush=True)
    if failures:
        print(f"{len(failures)} check(s) failed: {failures}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
