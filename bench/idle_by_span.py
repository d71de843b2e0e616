#!/usr/bin/env python3
"""Where the device sat idle, by what the host was doing: each idle
nanosecond of a traced run's window put down to the innermost program
span open on the host at that time (bench/spans.py).

    python3 bench/idle_by_span.py bench/out/trace/yi6b.chat [--seconds 51]

Idle time under no program span falls under `bench.serve_stream.next`
(program code outside every span, inside the benchmark's call into the
stream) or else under `harness` (the benchmark between its calls).  The
window opens where the harness's does, at the start of the call into the
stream that ran the first timed wave (just before the `bench.window`
anchor), and lasts `--seconds` (default: BENCHMARK.json's `run_seconds`).
Prints the totals, then the longest gaps with the spans they fell in.  No
metric reads this tool.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent))

from bench import spans  # noqa: E402

WINDOW_SPAN = "bench.window"


def window_start(trace: dict) -> float:
    """Where the harness's window opens on the trace's clock: the start of
    its call into the stream that ran the first timed wave, the last call
    to end before the `bench.window` anchor."""
    anchor = next((a for n, a, _, _ in trace["host"] if n == WINDOW_SPAN),
                  None)
    if anchor is None:
        raise SystemExit(f"the trace holds no {WINDOW_SPAN} span")
    calls = [a for n, a, b, _ in trace["host"]
             if n == spans.NEXT_SPAN and b <= anchor]
    return max(calls, default=anchor)


def report(trace: dict, seconds: float, top: int = 10) -> dict:
    anchor = window_start(trace)
    win = (anchor, anchor + seconds * 1e9)
    r = spans.idle_by_span(trace, win)
    idle = r["idle_ns"]
    return {
        "window_s": seconds,
        "idle_s": idle / 1e9,
        "idle_share": idle / (seconds * 1e9),
        "by_span": sorted(([name, ns / 1e9, ns / idle if idle else 0.0]
                           for name, ns in r["by_span"].items()),
                          key=lambda row: -row[1]),
        "longest_gaps": [
            [(a - anchor) / 1e9, (b - a) / 1e9,
             sorted(([n, ns / 1e9] for n, ns in labels.items()),
                    key=lambda row: -row[1])]
            for a, b, labels in r["gaps"][:top]],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--top", type=int, default=10)
    ap.add_argument("--json", action="store_true",
                    help="print the report as one JSON object")
    args = ap.parse_args(argv)
    files = sorted(Path(args.trace_dir).rglob("*.xplane.pb"))
    if not files:
        print(f"no .xplane.pb under {args.trace_dir}", file=sys.stderr)
        return 1
    seconds = args.seconds or json.loads(
        (BENCH.parent / "BENCHMARK.json").read_text())["run_seconds"]
    r = report(spans.read_xspace(str(files[-1])), seconds, args.top)
    if args.json:
        print(json.dumps(r))
        return 0
    print(f"{files[-1]}: idle {r['idle_s']:.6f} s of {seconds} s "
          f"({100 * r['idle_share']:.2f}%)")
    for name, s, share in r["by_span"]:
        print(f"  {name:28s} {s:10.6f} s  {100 * share:6.2f}% of idle")
    print("longest gaps (window offset, length, spans):")
    for off, length, labels in r["longest_gaps"]:
        where = ", ".join(f"{n} {1e3 * s:.3f} ms" for n, s in labels)
        print(f"  {off:10.6f} s  {1e3 * length:9.3f} ms  {where}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
