"""The metric arithmetic: percentiles over all requests with misses in
the tail, rates over the whole window, and yi-6b's bytes and operations
(its family's yardstick) against numbers worked out by hand."""

import json
import math
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness, yardstick

BENCH = Path(__file__).resolve().parents[1]
DENSE = harness.load_family("dense_gqa")
YI = DENSE.dims(json.loads((BENCH / "configs" / "yi-6b.json").read_text()))


def test_percentile_nearest_rank():
    vals = list(range(1, 101))
    assert yardstick.percentile(vals, 50) == 50
    assert yardstick.percentile(vals, 95) == 95
    assert yardstick.percentile([3.0], 95) == 3.0
    with pytest.raises(ValueError):
        yardstick.percentile([], 50)


def test_miss_counts_in_the_tail():
    ok = [0.1] * 19
    assert yardstick.percentile(ok + [math.inf], 95) == 0.1
    assert math.isinf(yardstick.percentile(ok[:18] + [math.inf] * 2, 95))


def fake_run(tokens, ttfts, seconds=10.0):
    """A record of a run as the metric readers see it."""
    run = SimpleNamespace(tokens=tokens, seconds=seconds, t_start=100.0,
                          t_end=100.0 + seconds,
                          requests=list(range(len(ttfts))))
    run.in_window = lambda t: run.t_start <= t < run.t_end
    run.ttft = lambda r: ttfts[r]
    return run


def test_readers_use_all_requests_and_the_whole_window():
    ttfts = [0.2] * 18 + [math.inf, 0.3]
    tokens = [(100.0 + 0.5 * i, 0, i) for i in range(30)]  # 15 s of tokens
    run = fake_run(tokens, ttfts)
    assert harness.metric_reader("ttft_p50_s")(run) == 0.2
    assert harness.metric_reader("ttft_p95_s")(run) == 0.3
    # tokens after the window's end do not count, the divisor is the window
    assert harness.metric_reader("output_tok_s")(run) == 20 / 10.0
    # a gap counts where its later token lies inside the window
    assert harness.metric_reader("itl_p95_ms")(run) == pytest.approx(500.0)
    with pytest.raises(ValueError):
        harness.metric_reader("ttft_p95_s")(fake_run(tokens, ttfts[:18]
                                                     + [math.inf] * 2))


def test_yi6b_shapes():
    assert (YI["d"], YI["H"], YI["K"], YI["D"], YI["F"], YI["V"], YI["L"]) \
        == (4096, 32, 4, 128, 11008, 64000, 32)
    assert not YI["bias"] and not YI["tied"]


def test_yi6b_decode_bytes_by_hand():
    # per layer: q 4096*4096 + k,v 2*4096*512 + o 4096*4096 + mlp 3*4096*11008
    layer = 16_777_216 + 4_194_304 + 16_777_216 + 135_266_304
    assert layer == 173_015_040
    matrices = 32 * layer + 4096 * 64000
    assert DENSE.matrix_params(YI) == matrices == 5_798_625_280
    assert DENSE.kv_bytes_per_token(YI) == 65_536  # 64 KiB
    weights_b = 2 * matrices + 65 * 4096 * 4
    assert DENSE.weight_bytes(YI) == weights_b == 11_598_315_520
    assert DENSE.decode_bytes(YI, [100, 200]) == \
        weights_b + 2 * 4096 * 2 + 300 * 65_536 + 2 * 65_536


def test_yi6b_flops_by_hand():
    one = DENSE.forward_flops(YI, 0, 1)
    assert one == 2 * 5_798_625_280 + 4 * 32 * 32 * 128 * 1
    # a 4-token chunk after 2 resident tokens attends 3+4+5+6 keys
    assert DENSE.attention_flops(YI, 2, 6) == 4 * 32 * 32 * 128 * 18
    assert DENSE.forward_flops(YI, 2, 6) == \
        4 * 2 * 5_798_625_280 + 4 * 32 * 32 * 128 * 18


def window(stamp_shift=0.0, ttft_scale=1.0):
    """Two requests served over four waves: each wave's events come back
    together once the wave has run (0.8 s on the harness's clock), each
    stamped by the program on a clock of its own."""
    from bench import mix

    plan = [[("admit", 0, None), ("token", 0, 0)],
            [("admit", 1, None), ("token", 0, 1), ("token", 1, 0)],
            [("token", 0, 2), ("token", 1, 1)],
            [("token", 1, 2)]]
    events, spans = [], []
    for w, evs in enumerate(plan):
        t = 10.0 + w
        for i, (kind, rid, idx) in enumerate(evs + [("wave", None, None)]):
            t0, t1 = (t, t + 0.8) if i == 0 else (t + 0.8 + i * 1e-3,) * 2
            e = {"event": kind, "wave": w, "t": 500.0 + 0.3 * w + stamp_shift}
            if kind == "wave":
                e["batch"] = len(evs)
            else:
                e["rid"] = rid
            if kind == "token":
                e["index"] = idx
            events.append(e)
            spans.append((t0, t1, kind))
    stream = mix.Stream([np.zeros(5, np.int64), np.zeros(7, np.int64)],
                        [0, 1], decode_tokens=3)
    outcomes = [{"rid": r, "status": "ok", "ttft_s": 0.25 * ttft_scale}
                for r in (0, 1)]
    return harness.Window(stream, events, spans, outcomes,
                          [np.zeros(3, np.int64)] * 2, {}, 10.0)


@pytest.mark.parametrize("stamp_shift,ttft_scale", [(0.0, 1.0), (37.5, 1.0),
                                                    (-2.0, 3.0)])
def test_end_to_end_times_are_the_harness_clock(stamp_shift, ttft_scale):
    cell = harness.Cell("small", 1, json.loads(
        (BENCH / "tests" / "data" / "small.json").read_text()), {}, {}, [], [])
    run = harness.Run(cell, window(stamp_shift, ttft_scale), 10.0, [], [], {})
    assert run.t_start == 10.0
    # request 1 arrives as wave 1 starts and gets its first token as the
    # events of wave 1 come back, third of them
    assert run.ttft(0) == pytest.approx(0.801)
    assert run.ttft(1) == pytest.approx(0.802)
    values = {name: harness.metric_reader(name)(run)
              for name in ("ttft_p50_s", "ttft_p95_s", "itl_p95_ms",
                           "output_tok_s")}
    assert values == {"ttft_p50_s": pytest.approx(0.801),
                      "ttft_p95_s": pytest.approx(0.802),
                      "itl_p95_ms": pytest.approx(1000.0),
                      "output_tok_s": pytest.approx(0.6)}


def test_host_report_counts_waves_and_stalls():
    cell = harness.Cell("small", 1, json.loads(
        (BENCH / "tests" / "data" / "small.json").read_text()), {}, {}, [], [])
    run = harness.Run(cell, window(), 10.0, [], [], {})
    report = harness.host_report(run)
    assert report.startswith("window: 4 waves, period p50 999.00 ms")
    assert "0.000 s in periods over twice the median" in report
