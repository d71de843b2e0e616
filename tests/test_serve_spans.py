"""Host spans of the serving loop and join-point names on device ops.

Covers:
  - `SpanRecorder` arithmetic on a hand-driven clock: self times, time
    inside spans, spans still open at `take`, time outside the program;
  - every `wave` event of `serve_stream` carries `host_ms` by the
    documented phases, whose sum (the caller's time aside) is the event's
    `dt_s`, and waves that decode nothing carry their time and tokens
    into the next event;
  - under the profiler the same spans land on the trace's host plane,
    children inside their `serve.wave`, and the profiler's clock agrees
    with the events' `dt_s`;
  - `prefill_tokens` counts the tokens each prefill computed: a shared
    prefix is not computed, and the stream's total equals what the
    prefill dispatches received, one-shot and chunked;
  - a lowered decode step names the attention join point in `op_name`.
"""

import glob
import re

import numpy as np
import pytest

from repro.monitor import spans as spans_mod
from repro.monitor.spans import SpanRecorder
from repro.runtime.server import WAVE_PHASES


def _server(**cfg_kw):
    from repro.configs.base import SHAPES
    from repro.core.program import Program
    from repro.launch.weave import default_weave
    from repro.runtime.server import Server, ServerConfig

    program = Program.from_arch("yi-6b", kind="serve", reduced=True)
    woven = default_weave(program, SHAPES["prefill_32k"], {})
    cfg_kw.setdefault("max_cache_len", 64)
    cfg_kw.setdefault("decode_tokens", 4)
    return Server(woven, ServerConfig(**cfg_kw))


@pytest.fixture(scope="module")
def srv():
    return _server()


_RNG = np.random.default_rng(11)
# the first request streams in alone over several chunks: waves that
# prefill and decode nothing
PROMPTS = [_RNG.integers(1, 50, (n,)).astype(np.int32)
           for n in (40, 9, 23, 17)]
ARRIVALS = [0, 6, 6, 8]
PREFIX = _RNG.integers(1, 50, (16,)).astype(np.int32)  # two pages of 8
SHARED = [np.concatenate([PREFIX, _RNG.integers(1, 50, (n,))]).astype(
    np.int32) for n in (5, 11, 3)]


def _drain(gen):
    events = []
    while True:
        try:
            events.append(next(gen))
        except StopIteration as stop:
            return events, stop.value


def _waves(events):
    return [e for e in events if e["event"] == "wave"]


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


# ---------------------------------------------------------------------------
# the recorder
# ---------------------------------------------------------------------------


class TestRecorder:
    @pytest.fixture
    def clock(self, monkeypatch):
        c = FakeClock()
        monkeypatch.setattr(spans_mod, "_clock", c)
        return c

    def test_self_time_and_nesting(self, clock):
        rec = SpanRecorder()
        with rec.span("a") as a:
            clock.t += 1.0
            with rec.span("b", rid=3) as b:
                clock.t += 2.0
                with rec.span("c"):
                    clock.t += 4.0
            clock.t += 8.0
            with rec.span("b"):
                clock.t += 16.0
        self_s, total_s = rec.take()
        assert self_s == {"a": 9.0, "b": 18.0, "c": 4.0}
        assert total_s == {"a": 31.0, "b": 22.0, "c": 4.0}
        assert (a.seconds, b.seconds) == (31.0, 6.0)
        assert rec.take() == ({}, {})

    def test_take_counts_open_spans_up_to_now(self, clock):
        rec = SpanRecorder()
        with rec.span("wave"):
            clock.t += 1.0
            with rec.span("dispatch"):
                clock.t += 2.0
                first = rec.take()
                clock.t += 4.0
            clock.t += 8.0
            second = rec.take()
            clock.t += 16.0
        third = rec.take()
        assert first == ({"wave": 1.0, "dispatch": 2.0},
                         {"wave": 3.0, "dispatch": 2.0})
        assert second == ({"wave": 8.0, "dispatch": 4.0},
                          {"wave": 12.0, "dispatch": 4.0})
        assert third == ({"wave": 16.0}, {"wave": 16.0})

    def test_outside_adds_to_the_totals_only(self, clock):
        rec = SpanRecorder()
        with rec.span("wave"):
            clock.t += 1.0
        with rec.outside("consumer"):
            clock.t += 5.0
        self_s, total_s = rec.take()
        assert self_s == {"wave": 1.0, "consumer": 5.0}
        assert total_s == self_s

    def test_spanned_method_runs_inside_its_span(self, clock):
        class Pool:
            spans = SpanRecorder()

            @spans_mod.spanned("pages.compose")
            def batch(self, n):
                clock.t += n
                return n * 2

        pool = Pool()
        assert pool.batch(3.0) == 6.0
        assert pool.spans.take() == ({"pages.compose": 3.0},
                                     {"pages.compose": 3.0})

    def test_an_exception_closes_the_span(self, clock):
        rec = SpanRecorder()
        with pytest.raises(KeyError):
            with rec.span("wave"):
                clock.t += 2.0
                raise KeyError("x")
        assert rec.take() == ({"wave": 2.0}, {"wave": 2.0})
        assert not rec._stack


# ---------------------------------------------------------------------------
# wave events
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def chunked_stream(srv):
    events, out = _drain(srv.serve_stream(
        PROMPTS, page_size=8, prefill_chunk=8, arrival_waves=ARRIVALS))
    return events, out, list(srv.last_outcomes)


def test_every_wave_event_has_host_ms_by_phase(chunked_stream):
    waves = _waves(chunked_stream[0])
    assert waves
    for w in waves:
        assert set(w["host_ms"]) == set(WAVE_PHASES)
        assert all(v >= 0 for v in w["host_ms"].values())
    seen = {p for w in waves for p, v in w["host_ms"].items() if v > 0}
    for phase in ("serve.wave", "serve.admit", "serve.prefill_chunk",
                  "serve.dispatch", "serve.wait", "serve.emit",
                  "pages.compose", "pages.absorb", "pages.prefill_view",
                  "pages.absorb_prefill", "pages.admit"):
        assert phase in seen, phase


def test_phases_sum_to_the_wave_time(chunked_stream):
    for w in _waves(chunked_stream[0]):
        program = sum(v for p, v in w["host_ms"].items() if p != "consumer")
        assert program == pytest.approx(1e3 * w["dt_s"], rel=0.02)


def test_waves_without_a_decode_carry_into_the_next_event(chunked_stream):
    events = chunked_stream[0]
    waves = _waves(events)
    # the first request streams in alone: waves 0.. prefill only
    assert waves[0]["wave"] > 0
    numbered = [e["wave"] for e in waves]
    assert len(numbered) < numbered[-1] + 1
    # every prefilled token and every emitted token lands in some event
    assert sum(w["prefill_tokens"] for w in waves) == sum(
        len(p) for p in PROMPTS)
    assert sum(w["emitted"] for w in waves) == sum(
        1 for e in events if e["event"] == "token")


def test_outcomes_time_tokens_by_their_events(chunked_stream):
    """A request's token times are its token events' stamps: one clock."""
    events, _, outcomes = chunked_stream
    for o in outcomes:
        stamps = [e["t"] for e in events
                  if e["event"] == "token" and e["rid"] == o["rid"]]
        assert o["status"] == "ok" and len(stamps) == o["tokens"]
        assert o["tok_gap_max_s"] == max(
            b - a for a, b in zip(stamps, stamps[1:]))


# ---------------------------------------------------------------------------
# the profiler's view of the same spans
# ---------------------------------------------------------------------------


def test_spans_land_on_the_profiler_trace(srv, tmp_path):
    import jax
    from jax.profiler import ProfileData

    gen = srv.serve_stream(PROMPTS, page_size=8, prefill_chunk=8,
                           arrival_waves=ARRIVALS)
    with jax.profiler.trace(str(tmp_path)):
        events, _ = _drain(gen)
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    host = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                          dict(e.stats)) for e in line.events
                         if e.name.startswith(("serve.", "pages."))]
    names = {h[0] for h in host}
    assert {"serve.wave", "pages.compose", "serve.dispatch",
            "serve.wait"} <= names
    walls = [h for h in host if h[0] == "serve.wave"]
    for name, a, b, _ in host:
        if name != "serve.wave":
            assert any(wa <= a and b <= wb for _, wa, wb, _ in walls), name
    # the profiler's clock: every wave up to the last event's, against the
    # events' dt_s (the trailing wave after the last decode has no event)
    waves = _waves(events)
    last = waves[-1]["wave"]
    traced = sum(b - a for _, a, b, st in walls if int(st["wave"]) <= last)
    assert sum(w["dt_s"] for w in waves) == pytest.approx(traced / 1e9,
                                                          rel=0.02)
    assert len(walls) > len(waves)  # waves that decoded nothing
    assert all("batch" in st for _, _, _, st in walls
               if int(st["wave"]) in {w["wave"] for w in waves})


# ---------------------------------------------------------------------------
# prefill_tokens: the tokens each prefill computed
# ---------------------------------------------------------------------------


class Tap:
    """Counts the prompt tokens each paged-prefill dispatch receives."""

    def __init__(self, vc):
        self.vc, self.tokens = vc, []

    def __call__(self, variant, params, inputs, cache, **kw):
        self.tokens.append(int(inputs["tokens"].shape[1]))
        return self.vc(variant, params, inputs, cache, **kw)

    def __getattr__(self, name):
        return getattr(self.vc, name)


def test_a_shared_prefix_is_not_counted(srv, monkeypatch):
    tap = Tap(srv.paged_prefill_vc)
    monkeypatch.setattr(srv, "paged_prefill_vc", tap)
    events, _ = _drain(srv.serve_stream(SHARED, page_size=8,
                                        prefill_chunk=0))
    assert srv.last_pool_stats["prefix_hits"] >= 4
    computed = sum(w["prefill_tokens"] for w in _waves(events))
    # the donor prefills in full; each sharer only past the two pages
    assert computed == len(SHARED[0]) + sum(len(p) - 16 for p in SHARED[1:])
    assert computed == sum(tap.tokens)


@pytest.mark.parametrize("chunk", [0, 8, 16])
def test_stream_total_matches_the_prefill_dispatches(srv, monkeypatch, chunk):
    tap = Tap(srv.paged_prefill_vc)
    monkeypatch.setattr(srv, "paged_prefill_vc", tap)
    prompts = SHARED + PROMPTS
    events, _ = _drain(srv.serve_stream(
        prompts, page_size=8, prefill_chunk=chunk,
        arrival_waves=[0, 0, 1, 2, 3, 3, 9]))
    assert sum(w["prefill_tokens"] for w in _waves(events)) \
        == sum(tap.tokens) > 0


# ---------------------------------------------------------------------------
# join-point names on device ops
# ---------------------------------------------------------------------------


def test_decode_step_hlo_names_the_attention_join_point(srv):
    import jax
    import jax.numpy as jnp

    from repro.runtime.pages import PagedCacheManager
    from repro.runtime.steps import build_decode_step

    manager = PagedCacheManager(16, 8, max_len=64, window=None)
    srv._paged_admit(manager, 0, PROMPTS[1], 20, None)
    cache = manager.batch([0])
    step = jax.jit(build_decode_step(srv.woven))
    lowered = step.lower(
        srv.params, {"tokens": jnp.zeros((1, 1), jnp.int32),
                     "positions": jnp.full((1, 1), len(PROMPTS[1]),
                                           jnp.int32)}, cache)
    names = set(re.findall(r'op_name="([^"]*)"', lowered.compile().as_text()))
    attn = [n for n in names if "/blocks0/" in n and "/block/attn/" in n]
    ffn = [n for n in names if "/block/ffn/" in n]
    assert attn and ffn
    assert all(n.startswith("jit(decode_step)/") for n in attn)


def test_compile_cache_keeps_the_join_point_names(tmp_path, monkeypatch):
    """The cache key keeps op metadata (a program named differently never
    loads another's executable) and one frame of each op's location (the
    key does not follow the call stack above a jitted step)."""
    import jax

    from repro.launch.compile_cache import use_compile_cache

    keys = ("jax_compilation_cache_include_metadata_in_key",
            "jax_traceback_in_locations_limit")
    before = {k: getattr(jax.config, k) for k in keys}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        assert use_compile_cache() == (str(tmp_path), True)
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        assert jax.config.jax_traceback_in_locations_limit == 1
    finally:
        for k, v in before.items():
            jax.config.update(k, v)


CACHED_STEP = """
import re, sys
import jax, jax.numpy as jnp
from repro.launch.compile_cache import use_compile_cache
use_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
events = []
jax.monitoring.register_event_listener(lambda name, **kw: events.append(name))

def body(c, w):
    with jax.named_scope("attn"):
        return jnp.tanh(c @ w), None

@jax.jit
def step(x, ws):
    with jax.named_scope("blocks0"):
        return jax.lax.scan(body, x, ws)[0].sum()

def here():
    return step.lower(jnp.ones((8, 8)), jnp.ones((2, 8, 8))).compile()

def elsewhere():
    return here()

compiled = (here if sys.argv[1] == "a" else elsewhere)()
names = re.findall(r'op_name="([^"]*attn[^"]*)"', compiled.as_text())
print(events.count("/jax/compilation_cache/cache_hits"),
      events.count("/jax/compilation_cache/cache_misses"), names[:1])
"""


def test_a_cached_step_keeps_its_names_from_any_call_site(tmp_path):
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               PYTHONPATH=os.pathsep.join(p for p in sys.path if p))
    out = []
    for site in ("a", "b"):
        p = subprocess.run([sys.executable, "-c", CACHED_STEP, site],
                           env=env, capture_output=True, text=True,
                           timeout=300)
        assert p.returncode == 0, p.stderr[-2000:]
        out.append(p.stdout.split(maxsplit=2))
    (hits_a, miss_a, names_a), (hits_b, miss_b, names_b) = out
    assert int(miss_a) >= 1 and int(hits_b) >= 1 and int(miss_b) == 0
    assert "jit(step)/blocks0/while/body/closed_call/attn/" in names_b
    assert names_a == names_b
