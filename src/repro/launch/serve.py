"""Serving launcher: the serve path at the architecture's published widths.

    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --stream
    PYTHONPATH=src python -m repro.launch.serve --reduced --stream   # CPU demo

`--reduced` builds the architecture's small smoke config, which runs on
the CPU in seconds; without it the full model is built (yi-6b: ~12 GB of
bf16 params, one TPU v5e).  The exit status is non-zero when any request
of a continuous, stream or fleet serve ends in an outcome other than "ok".

Four modes:
  (default)     legacy solo `serve()` per request;
  --continuous  one `serve_continuous` wave over the whole request set
                (paged pool, prefix sharing), printing each request's
                structured outcome;
  --stream      drive the `serve_stream` event loop directly, printing
                per-token events with TTFT / inter-token latency columns
                (add --slo-ttft-s / --slo-tok-s to run under the QoS
                governor with those SLOs as mARGOt Goals);
  --fleet N     route the same wave across N `ServingFleet` replicas
                (prefix-affinity routing + replica-loss recovery), see
                `repro.launch.fleet` for the full fleet CLI.
"""

from __future__ import annotations

import argparse

import numpy as np

from repro.configs.base import SHAPES
from repro.core.program import Program
from repro.launch.compile_cache import use_compile_cache
from repro.launch.weave import default_weave
from repro.models.registry import ARCHS
from repro.runtime.server import Server, ServerConfig


def _print_outcomes(outcomes, outputs) -> None:
    for o in outcomes:
        rep = f" replica={o['replica']}" if "replica" in o else ""
        print(f"  rid {o['rid']}: {o['status']:<18} tokens={o['tokens']}"
              f"{rep}" + (f"  ({o['reason']})" if o["reason"] else ""))


def _all_ok(outcomes) -> int:
    bad = [o for o in outcomes if o["status"] != "ok"]
    if bad:
        print(f"{len(bad)} of {len(outcomes)} request(s) not ok")
    return 1 if bad else 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default="yi-6b")
    ap.add_argument("--reduced", action="store_true",
                    help="build the small smoke config (CPU demo) instead "
                         "of the published widths")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--decode-tokens", type=int, default=8)
    ap.add_argument("--continuous", action="store_true",
                    help="serve all requests through one continuous-"
                         "batching wave and print structured outcomes")
    ap.add_argument("--stream", action="store_true",
                    help="drive the serve_stream event loop: print "
                         "per-token events with TTFT/inter-token latency")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked-prefill tokens per wave (stream mode)")
    ap.add_argument("--slo-ttft-s", type=float, default=None,
                    help="TTFT SLO — enables the QoS governor (stream)")
    ap.add_argument("--slo-tok-s", type=float, default=None,
                    help="inter-token SLO — enables the QoS governor")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="route the wave across N fleet replicas "
                         "(implies --continuous)")
    args = ap.parse_args()
    use_compile_cache()

    program = Program.from_arch(args.arch, kind="serve",
                                reduced=args.reduced)
    woven = default_weave(program, SHAPES["prefill_32k"], {})
    cfg = ServerConfig(
        max_cache_len=args.prompt_len + args.decode_tokens + 1,
        decode_tokens=args.decode_tokens,
    )
    rng = np.random.default_rng(0)

    if args.fleet > 0:
        from repro.runtime.fleet import ServingFleet

        prompts = [rng.integers(0, program.cfg.vocab, args.prompt_len)
                   .astype(np.int64) for _ in range(args.requests)]
        fleet = ServingFleet(lambda: Server(woven, cfg),
                             replicas=args.fleet)
        outs = fleet.serve(prompts, decode_tokens=args.decode_tokens)
        stats = fleet.last_fleet_stats
        print(f"fleet of {args.fleet}: {stats['outcomes']} in "
              f"{stats['rounds']} round(s); affinity hits "
              f"{stats['affinity_hits']}")
        _print_outcomes(fleet.last_outcomes, outs)
        return _all_ok(fleet.last_outcomes)

    server = Server(woven, cfg)
    if args.stream:
        prompts = [rng.integers(0, program.cfg.vocab, args.prompt_len)
                   .astype(np.int64) for _ in range(args.requests)]
        qos = None
        if args.slo_ttft_s is not None or args.slo_tok_s is not None:
            qos = {}  # governed under DEFAULT_QOS_POLICY + these SLOs
        gen = server.serve_stream(
            prompts, decode_tokens=args.decode_tokens,
            prefill_chunk=args.prefill_chunk, qos=qos,
            slo_ttft_s=args.slo_ttft_s, slo_tok_s=args.slo_tok_s)
        import time as _time

        t_start = _time.perf_counter()
        last_tok: dict[int, float] = {}
        print(f"{'wave':>5} {'event':<14} {'rid':>4} "
              f"{'ttft_ms':>8} {'gap_ms':>7}  detail")
        while True:
            try:
                ev = next(gen)
            except StopIteration as stop:
                outs = stop.value
                break
            kind, rid = ev["event"], ev.get("rid", -1)
            ttft = gap = ""
            if kind == "token":
                if ev["index"] == 0:
                    ttft = f"{1e3 * (ev['t'] - t_start):.1f}"
                elif rid in last_tok:
                    gap = f"{1e3 * (ev['t'] - last_tok[rid]):.1f}"
                last_tok[rid] = ev["t"]
                detail = f"token={ev['token']} index={ev['index']}"
            elif kind == "wave":
                detail = (f"batch={ev['batch']} emitted={ev['emitted']} "
                          f"prefill_tokens={ev['prefill_tokens']} "
                          f"op={ev['op']}")
            else:
                detail = " ".join(f"{k}={v}" for k, v in ev.items()
                                  if k not in ("event", "wave", "t", "rid"))
            print(f"{ev['wave']:>5} {kind:<14} "
                  f"{rid if rid >= 0 else '':>4} {ttft:>8} {gap:>7}  "
                  f"{detail}")
        for o in server.last_outcomes:
            ttft_ms = (f"{1e3 * o['ttft_s']:.1f}ms"
                       if o["ttft_s"] is not None else "-")
            gap_ms = (f"{1e3 * o['tok_gap_max_s']:.1f}ms"
                      if o["tok_gap_max_s"] is not None else "-")
            print(f"  rid {o['rid']}: {o['status']:<18} "
                  f"tokens={o['tokens']} ttft={ttft_ms} max_gap={gap_ms}")
        if server.last_qos_stats is not None:
            q = server.last_qos_stats
            print(f"qos: {q['switches']} OP switch(es), "
                  f"{q['distinct_ops']} distinct OP(s), "
                  f"objective={q['objective']}, "
                  f"energy={q['energy_j']:.1f}J")
        return _all_ok(server.last_outcomes)

    if args.continuous:
        prompts = [rng.integers(0, program.cfg.vocab, args.prompt_len)
                   .astype(np.int64) for _ in range(args.requests)]
        outs = server.serve_continuous(
            prompts, decode_tokens=args.decode_tokens)
        print(f"continuous wave: {len(prompts)} request(s), pool "
              f"{server.last_pool_stats['live_pages']} live pages, "
              f"{server.last_pool_stats['prefix_hits']} prefix hits")
        _print_outcomes(server.last_outcomes, outs)
        return _all_ok(server.last_outcomes)

    for i in range(args.requests):
        prompt = rng.integers(0, program.cfg.vocab,
                              (args.batch, args.prompt_len), dtype=np.int32)
        out = server.serve(prompt)
        print(f"request {i}: generated {out.shape} in {server.latencies[-1]*1e3:.0f}ms")
    print(f"served {server.served}; p50 latency "
          f"{sorted(server.latencies)[len(server.latencies)//2]*1e3:.0f}ms")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
