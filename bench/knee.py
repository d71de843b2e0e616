#!/usr/bin/env python3
"""Find a cell's knee: the most requests per wave its scheduler sustains.

    JAX_PLATFORMS=cpu python3 bench/knee.py --workload yi6b.chat \
        --rates 0.2 0.3 0.4 --waves 1500

Arrivals run on serve_stream's wave clock and every request decodes the
same number of tokens, so how many requests a wave admits depends only on
the scheduler: prompt lengths, page size, pool pages, max_batch and the
prefill chunk.  This runs the cell's traffic and server settings through
`Server.serve_stream` on the CPU with the model cut to toy widths by its
family's `toy` (which changes no scheduling decision) and prints, per
offered rate, the admissions per wave over the second half of the run and
the queue left at its end.  Where admissions stop following the offered
rate, the queue grows: that rate is the knee.  Times here are of the CPU
and mean nothing.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def sweep(cell, rate: float, waves: int, seed: int = 0) -> dict:
    import jax

    from bench import harness
    from bench import mix as mixlib

    mix = dict(cell.mix, rate_per_wave=rate, preroll_waves=0)
    stream = mixlib.timed_stream(mix, seed, cell.dm["V"], waves)
    server, _ = harness.build_server(cell, seed, jax.devices()[0])
    gen = server.serve_stream(stream.prompts,
                              arrival_waves=stream.arrival_waves,
                              decode_tokens=stream.decode_tokens)
    admit_wave, batches = {}, []
    for ev in gen:
        if ev["wave"] >= waves:
            break
        if ev["event"] == "admit":
            admit_wave[ev["rid"]] = ev["wave"]
        elif ev["event"] == "wave" and ev["wave"] >= waves // 2:
            batches.append(ev["batch"])
    gen.close()
    half = waves // 2
    arrived = sum(1 for w in stream.arrival_waves if w < waves)
    admitted_late = sum(1 for w in admit_wave.values() if w >= half)
    return {"rate": rate,
            "admitted_per_wave": admitted_late / (waves - half),
            "queue_at_end": arrived - len(admit_wave),
            "decode_batch_mean": sum(batches) / max(len(batches), 1)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--waves", type=int, default=1500)
    args = ap.parse_args()

    from bench import harness

    cell = harness.load_cell(args.workload)
    cell.config = cell.family.toy(cell.config)
    for rate in args.rates:
        print(json.dumps(sweep(cell, rate, args.waves)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
