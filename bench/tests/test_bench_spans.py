"""The readers of the program's spans and of device time by join point
(bench/spans.py, the `sched.host_ms_per_wave`, `pages.host_ms_per_wave`
and `attn.decode_ms` metrics, bench/idle_by_span.py), each on hand-made
data against a second, independent count."""

import glob
import types

import pytest

from bench import harness, idle_by_span, spans

# name stacks as a TPU trace's `tf_op` stat holds them
ATTN = ("jit(decode_step)/blocks0/while/body/closed_call/block/attn/"
        "dot_general:")
FFN = ("jit(decode_step)/blocks0/while/body/closed_call/block/ffn/"
       "dot_general:")
POOL = "jit(decode_step)/blocks0/while/body/dynamic_update_slice"

# two decode steps in the window (100-200, 400-500), a prefill between
# them, a third decode step past the window's end (window 100-800)
HANDMADE = {
    "devices": [{
        "name": "/device:TPU:0",
        "ops": [["%fusion.1 = attn", 100, 130, ATTN],
                ["%fusion.2 = ffn", 130, 170, FFN],
                ["%copy.3 = pool", 170, 200, POOL],
                ["%while.4 = loop", 100, 200, "jit(decode_step)/blocks0"],
                ["%fusion.5 = prefill attn", 250, 300,
                 "jit(paged_prefill_step)/blocks0/block/attn/dot_general"],
                ["%fusion.1 = attn", 400, 445, ATTN],
                ["%fusion.7 = self attn", 445, 460,
                 "jit(decode_step)/blocks0/block/self_attn/add"],
                ["%fusion.8 = no scope", 460, 500, ""],
                ["%fusion.9 = attn", 900, 1000, ATTN]],
        "modules": [["jit_decode_step(1)", 100, 200],
                    ["jit_paged_prefill_step(2)", 250, 300],
                    ["jit_decode_step(3)", 400, 500],
                    ["jit_decode_step(4)", 900, 1100]],
    }],
    "host": [
        ["bench.serve_stream.next", 40, 360, {}],
        ["bench.window", 100, 101, {}],
        ["serve.wave", 50, 350, {"wave": 7, "batch": 2}],
        ["serve.dispatch", 60, 95, {}],
        ["serve.wait", 95, 205, {}],
        ["pages.absorb", 205, 215, {}],
        ["serve.admit", 215, 330, {"rid": 3}],
        ["pages.admit", 220, 240, {}],
        ["serve.dispatch", 240, 250, {}],
        ["serve.wait", 250, 310, {}],
        ["bench.serve_stream.next", 380, 700, {}],
        ["serve.wave", 390, 690, {"wave": 8, "batch": 2}],
        ["pages.compose", 390, 398, {}],
        ["serve.wait", 398, 505, {}],
        ["serve.emit", 505, 560, {}],
    ],
}
WINDOW = (100, 800)


# -- device time by join point ------------------------------------------------------


def brute_scope_time(trace, win, module, needle):
    w0, w1 = win
    dev = trace["devices"][0]
    calls = [(a, b) for n, a, b in dev["modules"]
             if n.split("(")[0] == module and a >= w0 and b <= w1]
    total = 0
    for name, a, b, scope in dev["ops"]:
        if name.startswith("%while") or needle not in scope.split("/"):
            continue
        if any(c0 <= a < c1 for c0, c1 in calls):
            total += b - a
    return len(calls), total / 1e9


def test_scope_time_by_hand():
    calls, secs = spans.scope_time(HANDMADE, WINDOW, "jit_decode_step",
                                   spans.ATTENTION)
    assert calls == 2
    # attn 30 + 45, self_attn 15; not the prefill's, the loop's, the pool
    # copy's or the step past the window's end
    assert secs == pytest.approx(90e-9)


@pytest.mark.parametrize("needle,pattern", [
    ("attn", r"(^|/)attn(/|$)"), ("ffn", r"(^|/)ffn(/|$)"),
    ("blocks0", r"(^|/)blocks0(/|$)")])
def test_scope_time_matches_an_independent_count(needle, pattern):
    import re

    got = spans.scope_time(HANDMADE, WINDOW, "jit_decode_step",
                           re.compile(pattern))
    want = brute_scope_time(HANDMADE, WINDOW, "jit_decode_step", needle)
    assert got[0] == want[0]
    assert got[1] == pytest.approx(want[1])


def fake_run(trace, win, waves=()):
    """A run whose window is `win` on the trace's clock, half a
    nanosecond early (the host clock's seconds round)."""
    lo, hi = win
    return types.SimpleNamespace(
        trace={"window_s": (hi - lo) / 1e9}, cell=types.SimpleNamespace(
            name="none"),
        t_start=2.0 + (lo - 0.5) / 1e9, seconds=(hi - lo) / 1e9,
        win=types.SimpleNamespace(trace_offset=2.0),
        waves=list(waves), in_window=lambda t: 0.0 <= t < 1.0)


def test_attention_reader(monkeypatch):
    monkeypatch.setattr(spans, "for_run", lambda run: HANDMADE)
    run = fake_run(HANDMADE, WINDOW)
    assert spans.window(run) == pytest.approx((99.5, 799.5), abs=0.01)
    value = harness.metric_reader("attn.decode_ms")(run)
    assert value == pytest.approx(1e3 * 90e-9 / 2)


def test_attention_reader_reads_nothing_without_name_stacks(monkeypatch):
    bare = {"host": HANDMADE["host"], "devices": [dict(
        HANDMADE["devices"][0],
        ops=[op[:3] + [""] for op in HANDMADE["devices"][0]["ops"]])]}
    monkeypatch.setattr(spans, "for_run", lambda run: bare)
    assert harness.metric_reader("attn.decode_ms")(
        fake_run(bare, WINDOW)) is None
    monkeypatch.setattr(spans, "for_run", lambda run: None)
    assert harness.metric_reader("attn.decode_ms")(
        fake_run(bare, WINDOW)) is None


# -- host time per wave --------------------------------------------------------------


WAVES = [
    (0.1, {"host_ms": {"serve.wave": 1.0, "serve.wait": 40.0,
                       "consumer": 3.0, "pages.compose": 2.0,
                       "pages.absorb": 1.5, "serve.emit": 0.5}}),
    (0.2, {"host_ms": {"serve.wave": 2.0, "serve.wait": 30.0,
                       "consumer": 9.0, "pages.compose": 4.0,
                       "serve.dispatch": 6.0}}),
    (0.3, {"host_ms": {"serve.wave": 1.0, "serve.wait": 35.0,
                       "pages.release": 0.25, "serve.admit": 7.0}}),
    (1.5, {"host_ms": {"serve.wave": 100.0, "pages.compose": 100.0}}),
    (0.4, {"batch": 3}),  # a program without spans: no host_ms
]


@pytest.mark.parametrize("metric,keep", [
    ("sched.host_ms_per_wave",
     lambda p: p not in ("serve.wait", "consumer")),
    ("pages.host_ms_per_wave", lambda p: p.startswith("pages."))])
def test_host_ms_readers_match_an_independent_count(metric, keep):
    run = fake_run(HANDMADE, WINDOW, WAVES)
    sums = sorted(sum(v for p, v in w["host_ms"].items() if keep(p))
                  for t, w in WAVES if 0.0 <= t < 1.0 and "host_ms" in w)
    assert len(sums) == 3
    assert harness.metric_reader(metric)(run) == pytest.approx(sums[1])


def test_host_ms_values_by_hand():
    run = fake_run(HANDMADE, WINDOW, WAVES)
    # per wave without wait and consumer: 5.0, 12.0, 8.25
    assert harness.metric_reader("sched.host_ms_per_wave")(run) == 8.25
    # pages.*: 3.5, 4.0, 0.25
    assert harness.metric_reader("pages.host_ms_per_wave")(run) == 3.5


@pytest.mark.parametrize("metric", ["sched.host_ms_per_wave",
                                    "pages.host_ms_per_wave"])
def test_host_ms_readers_read_nothing_from_a_program_without_spans(metric):
    run = fake_run(HANDMADE, WINDOW, [(0.1, {"batch": 2})])
    assert harness.metric_reader(metric)(run) is None


# -- idle time by program span -------------------------------------------------------


def brute_idle(trace, win):
    """Per nanosecond: idle when no op covers it, under the shortest
    program span (else the benchmark's call, else the harness) that holds
    it."""
    w0, w1 = win
    ops = trace["devices"][0]["ops"]
    host = [(a, b, n) for n, a, b, _ in trace["host"]
            if n.startswith(("serve.", "pages.")) or n == spans.NEXT_SPAN]
    out = {}
    for t in range(int(w0), int(w1)):
        if any(a <= t < b for _, a, b, _ in ops):
            continue
        holding = [(b - a, n) for a, b, n in host if a <= t < b]
        label = min(holding)[1] if holding else spans.HARNESS
        out[label] = out.get(label, 0) + 1
    return out


def test_idle_by_span_by_hand():
    r = spans.idle_by_span(HANDMADE, WINDOW)
    # gaps 200-250, 300-400, 500-800
    assert r["idle_ns"] == 450
    assert r["by_span"] == {
        "serve.wait": 5 + 10 + 2 + 5, "pages.absorb": 10,
        "serve.admit": 5 + 20, "pages.admit": 20, "serve.dispatch": 10,
        "serve.wave": 20 + 130, "pages.compose": 8, "serve.emit": 55,
        spans.NEXT_SPAN: 10 + 10 + 10, spans.HARNESS: 20 + 100}
    assert [g[1] - g[0] for g in r["gaps"]] == [300, 100, 50]


def test_idle_by_span_matches_a_count_per_nanosecond():
    r = spans.idle_by_span(HANDMADE, WINDOW)
    assert r["by_span"] == pytest.approx(brute_idle(HANDMADE, WINDOW))


def test_innermost_nests():
    segs = spans.innermost(HANDMADE["host"])
    assert all(a < b for a, b, _ in segs)
    assert all(s[1] <= t[0] for s, t in zip(segs, segs[1:]))
    assert segs[0] == (40, 50, spans.NEXT_SPAN)
    assert (215, 220, "serve.admit") in segs


def test_idle_report_opens_where_the_harness_window_does():
    # the last call into the stream to end before the anchor; no call
    # ends before it here, so the anchor itself
    assert idle_by_span.window_start(HANDMADE) == 100
    early = dict(HANDMADE, host=HANDMADE["host"] + [
        [spans.NEXT_SPAN, 20, 35, {}]])
    assert idle_by_span.window_start(early) == 20
    r = idle_by_span.report(HANDMADE, 700e-9, top=2)
    assert r["idle_s"] == pytest.approx(450e-9)
    assert [g[1] for g in r["longest_gaps"]] == pytest.approx([300e-9,
                                                               100e-9])
    shares = {name: share for name, _, share in r["by_span"]}
    assert sum(shares.values()) == pytest.approx(1.0)
    assert shares["serve.wave"] == pytest.approx(150 / 450)


# -- reading a trace file ------------------------------------------------------------


def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _f(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def xspace_bytes():
    """An XSpace with one TPU plane: two ops (one with a name stack held
    as a string, one as an interned reference) and one module."""
    stat_md = (_f(5, _f(1, 1) + _f(2, _f(1, 1) + _f(2, "tf_op")))
               + _f(5, _f(1, 2) + _f(2, _f(1, 2) + _f(2, "flops")))
               + _f(5, _f(1, 3) + _f(2, _f(1, 3) + _f(2, ATTN))))
    ev_md = (_f(4, _f(1, 10) + _f(2, _f(1, 10) + _f(2, "%fusion.1 = a")
                                  + _f(5, _f(1, 2) + _f(4, 99))
                                  + _f(5, _f(1, 1) + _f(5, FFN))))
             + _f(4, _f(1, 11) + _f(2, _f(1, 11) + _f(2, "%fusion.2 = b")
                                    + _f(5, _f(1, 1) + _f(7, 3))))
             + _f(4, _f(1, 12) + _f(2, _f(1, 12)
                                    + _f(2, "jit_decode_step(7)"))))
    ops = _f(3, _f(2, "XLA Ops") + _f(3, 1000)
             + _f(4, _f(1, 10) + _f(2, 5000) + _f(3, 2000))
             + _f(4, _f(1, 11) + _f(2, 7000) + _f(3, 500)))
    mods = _f(3, _f(2, "XLA Modules") + _f(3, 1000)
              + _f(4, _f(1, 12) + _f(2, 4000) + _f(3, 9000)))
    plane = _f(1, 7) + _f(2, "/device:TPU:0") + ops + mods + ev_md + stat_md
    return _f(1, plane)


def test_device_plane_from_wire_bytes(tmp_path, monkeypatch):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(xspace_bytes())
    buf = memoryview(path.read_bytes())
    (num, plane), = list(spans._fields(buf, 0, len(buf)))
    dev = spans._device_plane(buf, list(spans._fields(buf, *plane)))
    assert dev["ops"] == [["%fusion.1 = a", 1005.0, 1007.0, FFN],
                          ["%fusion.2 = b", 1007.0, 1007.5, ATTN]]
    assert dev["modules"] == [["jit_decode_step(7)", 1004.0, 1013.0]]


def test_host_spans_from_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    x = jnp.ones((8, 8))
    with jax.profiler.trace(str(tmp_path)):
        with TraceAnnotation(spans.NEXT_SPAN):
            with TraceAnnotation("serve.wave", wave=4, batch=2):
                with TraceAnnotation("pages.compose"):
                    (x @ x).block_until_ready()
        with TraceAnnotation("other.span"):
            pass
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    trace = spans.read_xspace(path)
    assert trace["devices"] == []  # the CPU has no TPU plane
    by_name = {h[0]: h for h in trace["host"]}
    assert set(by_name) == {spans.NEXT_SPAN, "serve.wave", "pages.compose"}
    _, a, b, attrs = by_name["serve.wave"]
    assert attrs == {"wave": 4, "batch": 2}
    _, c, d, _ = by_name["pages.compose"]
    assert a <= c < d <= b
    segs = spans.innermost(trace["host"])
    assert [s[2] for s in segs] == [spans.NEXT_SPAN, "serve.wave",
                                    "pages.compose", "serve.wave",
                                    spans.NEXT_SPAN]
