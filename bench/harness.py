"""One run of one cell: build, warm up, serve the measured window, check.

The system under test is `repro.runtime.server.Server.serve_stream` under
the program's default weave (`repro.launch.weave.default_weave`), as a user
serves it.  The benchmark gives it weights drawn from the seed and a
request stream drawn from the seed (`mix.py`), and reads what it emits:
events stamped on the host clock, its pool counters, and, in a traced run,
the profiler's device trace.

Everything that depends on the model's architecture comes from the family
that the configuration file names (`"family"`): the module
`families/<family>.py`, loaded by path, gives the functions of FAMILY_API.
The harness knows no architecture.

Time line of a run, all in one process:
  set-up   build weights and server, serve the warm-up streams (every step
           shape the window can meet compiles here), then the preroll
           waves of the timed stream, which bring the server to its steady
           state;
  window   from the arrival of the first timed request, `--seconds` long;
  drain    requests that arrived in the window are served to their end,
           later arrivals are turned away (the stream's preemption drain);
  check    the program's state is freed and the family's plain reference
           scores a sample of the served requests (`reference.py`).
"""

from __future__ import annotations

import bisect
import contextlib
import dataclasses
import functools
import gc
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path
from unittest import mock

import numpy as np

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
if str(REPO / "src") not in sys.path:
    sys.path.insert(0, str(REPO / "src"))

import jax  # noqa: E402

from bench import mix as mixlib  # noqa: E402
from bench import reference, tracing, warmmark  # noqa: E402

# arrivals are generated for this many waves per second of window: more
# waves than a step that streams these models' weights can run
WAVES_PER_S_MAX = 200
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
WINDOW_SPAN = "bench.window"
TRACE_LEAD_WAVES = 3
NEXT_SPAN = "bench.serve_stream.next"
FAMILIES = BENCH / "families"
# what a family module gives, by name:
#   dims(conf)             the model's dimensions, a flat dict that carries
#                          the vocabulary size "V" the traffic draws from
#   toy(conf)              the configuration cut to toy widths (knee.py)
#   model_config(conf)     the program's ModelConfig
#   canonical(dm, seed, device)          every weight, drawn from the seed
#   program_params(canon, abstract)      the weights as the program's tree
#   served_logits(canon, dm, seqs, control=False)
#                          the float32 reference (`reference.over_sequences`)
#                          and, with `control`, its float8 control
#   forward_flops(dm, start, stop)       operations for tokens start..stop-1
#   decode_bytes(dm, kv_lens)            least bytes of a decode step, or
#                          None where lengths alone cannot give them
FAMILY_API = ("dims", "toy", "model_config", "canonical", "program_params",
              "served_logits", "forward_flops", "decode_bytes")


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer than the cell asks for."""


class RunError(RuntimeError):
    """The run cannot give a result: say why, print nothing."""


# -- finding things by name ---------------------------------------------------


def load_json(*parts: str) -> dict:
    return json.loads(BENCH.joinpath(*parts).read_text())


@functools.cache
def load_family(name: str, directory: Path = FAMILIES):
    """The family module `<directory>/<name>.py`, loaded once by path."""
    path = directory / f"{name}.py"
    if not path.is_file():
        raise RunError(f"no family module {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_family_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    missing = [f for f in FAMILY_API if not callable(getattr(module, f, None))]
    if missing:
        raise RunError(f"family {name} lacks {missing}")
    return module


@dataclasses.dataclass
class Cell:
    """Everything one workload of BENCHMARK.json names."""

    name: str
    chips: int
    config: dict
    mix: dict
    limits: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    families: Path = FAMILIES  # where the configuration's family is found

    @property
    def family(self):
        if "family" not in self.config:
            raise RunError(f"configuration {self.config.get('name')!r} "
                           "names no family")
        return load_family(self.config["family"], self.families)

    @property
    def dm(self) -> dict:
        return self.family.dims(self.config)

    @property
    def server(self) -> dict:
        return self.config["server"]

    @property
    def max_cache_len(self) -> int:
        return mixlib.max_prompt_len(self.mix) + int(self.mix["decode_tokens"])


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, benchmark: dict | None = None) -> Cell:
    bm = benchmark or json.loads((REPO / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        raise RunError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=load_json("configs", f"{w['config']}.json"),
        mix=load_json("traffic", f"{w['traffic']}.json"),
        limits=load_json("cells", f"{workload}.json"),
        end_to_end=[m for m in bm["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bm["per_layer"] if _applies(m, workload)])


def metric_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def peaks_for(kind: str) -> dict:
    table = load_json("peaks.json")
    if kind not in table:
        raise RunError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


# -- the system under test -------------------------------------------------------


def devices_for(chips: int, require_tpu: bool = True) -> list:
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX found {devs[0].platform!r}, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


def build_server(cell: Cell, seed: int, device):
    """A Server over the cell's configuration, serving the benchmark's
    weights for `seed`.  Server has no way to take weights, so its own
    draw (`init_params`) is replaced while it is built: two sets of
    weights would not fit one chip."""
    from repro.configs.base import SHAPES
    from repro.core.program import Program
    from repro.launch.weave import default_weave
    from repro.models.registry import build_model
    from repro.nn.module import abstract_params
    from repro.runtime import server as server_mod

    fam = cell.family
    mc = fam.model_config(cell.config)
    program = Program(model=build_model(mc), cfg=mc, kind="serve")
    woven = default_weave(program, SHAPES["prefill_32k"], {})
    abstract = abstract_params(program.model, woven.state.policies)
    params = fam.program_params(fam.canonical(cell.dm, seed, device),
                                abstract)
    sc = cell.server
    cfg = server_mod.ServerConfig(
        max_cache_len=cell.max_cache_len,
        decode_tokens=int(cell.mix["decode_tokens"]), seed=0,
        page_size=int(sc["page_size"]), pool_pages=int(sc["pool_pages"]),
        max_batch=int(sc["max_batch"]),
        prefill_chunk=int(sc.get("prefill_chunk") or 0) or None)
    with mock.patch.object(server_mod, "init_params",
                           lambda *a, **k: params):
        server = server_mod.Server(woven, cfg, device=device)
    return server, abstract


def set_weights(server, cell: Cell, seed: int, device, abstract) -> None:
    server.params = None
    gc.collect()
    fam = cell.family
    server.params = fam.program_params(fam.canonical(cell.dm, seed, device),
                                       abstract)


class PrefillTap:
    """Wraps the server's paged-prefill dispatch and records, per call, the
    host time, the resident prefix and the tokens computed (static shapes:
    nothing is read back from the device)."""

    def __init__(self, vc):
        self.vc = vc
        self.calls: list[tuple[float, int, int]] = []

    def __call__(self, variant, params, inputs, cache, **kw):
        self.calls.append((time.perf_counter(), int(kw.get("prefix_len", 0)),
                           int(inputs["tokens"].shape[1])))
        return self.vc(variant, params, inputs, cache, **kw)

    def __getattr__(self, name):
        return getattr(self.vc, name)


class CompileLog:
    """Backend compiles (or persistent-cache loads) seen by JAX: the host
    time each ended, its seconds and the function compiled."""

    def __init__(self):
        self.entries: list[tuple[float, float, str]] = []

    def __call__(self, event: str, secs: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.entries.append((time.perf_counter(), float(secs),
                                 str(kw.get("fun_name", "?"))))

    def between(self, t0: float, t1: float) -> list[float]:
        return [s for t, s, _ in self.entries if t0 <= t <= t1]


class Drain:
    """The `preemption` handle serve_stream polls: once `pending`, it admits
    no one, turns away the queue and serves the admitted to their end."""

    pending = False


# a wave can start decoding several requests at once: up to this many
# first-decode rows are warmed per batch size (4 was the most in 3000 waves
# of the chat mix)
NEW_ROWS_MAX = 6


def warm(server, cell: Cell, device) -> None:
    """Compile, before the window, every program the cell's traffic runs:
    the warm-up streams of `mix.warm_streams`, then the stacks of per-slot
    position rows that `PagedCacheManager._compose` builds each decode
    wave.  `jnp.stack` compiles once per pattern of committed rows (those
    the decode step returned) and uncommitted ones (requests decoding for
    the first time, last in the batch), so every batch size is stacked
    with 0..NEW_ROWS_MAX fresh rows."""
    import jax.numpy as jnp

    sc = cell.server
    for s in mixlib.warm_streams(cell.mix, cell.dm["V"], int(sc["max_batch"]),
                                 int(sc.get("prefill_chunk") or 0)):
        serve_all(server, s)
    width = cell.max_cache_len
    slots = jnp.arange(width, dtype=jnp.int32)
    old = jax.device_put(slots, device)
    new = jnp.where(slots < 1, slots, -1)
    for b in range(1, int(sc["max_batch"]) + 1):
        for k in range(min(b, NEW_ROWS_MAX) + 1):
            jax.block_until_ready(jnp.stack([old] * (b - k) + [new] * k))


def serve_all(server, stream: mixlib.Stream) -> None:
    gen = server.serve_stream(stream.prompts,
                              arrival_waves=stream.arrival_waves,
                              decode_tokens=stream.decode_tokens)
    for _ in gen:
        pass
    bad = [o for o in server.last_outcomes if o["status"] != "ok"]
    if bad:
        raise RunError(f"warm-up request failed: {bad[0]}")


@dataclasses.dataclass
class Window:
    """What the timed stream emitted, on the host clock."""

    stream: mixlib.Stream
    events: list[dict]
    # one per event: the harness's clock when it called next() and when the
    # event came back, and the event's kind
    spans: list[tuple[float, float, str]]
    outcomes: list[dict]
    outputs: list[np.ndarray]
    pool: dict
    t_open: float          # the call that ran the first timed arrival wave
    trace_offset: float = 0.0  # perf_counter = trace_ns / 1e9 + offset


def drive(server, stream: mixlib.Stream, seconds: float,
          trace_dir: Path | None = None) -> Window:
    """Serve `stream` until `seconds` past the first timed arrival, then
    drain; with `trace_dir`, under the profiler."""
    drain = Drain()
    timed = sorted((stream.arrival_waves[r], r)
                   for r in range(len(stream.prompts)) if stream.timed(r))
    if not timed:
        raise RunError("the stream has no timed request")
    first_wave = timed[0][0]
    events: list[dict] = []
    spans: list[tuple[float, float, str]] = []
    seen: set[int] = set()   # admitted or given an outcome
    due: list[int] | None = None
    t_open = t_close = t_anchor = None
    outputs = None
    tracing_on = False
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
    gen = server.serve_stream(stream.prompts,
                              arrival_waves=stream.arrival_waves,
                              decode_tokens=stream.decode_tokens,
                              preemption=drain)
    annotate = (lambda: jax.profiler.TraceAnnotation(NEXT_SPAN)) \
        if trace_dir is not None else contextlib.nullcontext
    try:
        while True:
            t0 = time.perf_counter()
            try:
                with annotate():
                    ev = next(gen)
            except StopIteration as stop:
                outputs = stop.value
                break
            t1 = time.perf_counter()
            spans.append((t0, t1, ev["event"]))
            events.append(ev)
            if ev["event"] in ("admit", "outcome"):
                seen.add(ev["rid"])
            if trace_dir is not None and not tracing_on and t_close is None \
                    and ev["wave"] >= first_wave - TRACE_LEAD_WAVES:
                # the profiler covers the window and a few waves before it
                # (starting it stalls the stream for a moment)
                jax.profiler.start_trace(str(trace_dir))
                tracing_on = True
            if t_open is None and ev["wave"] >= first_wave:
                # this call ran the first timed arrival wave from its start
                t_open = t0
                if trace_dir is not None:
                    # anchors the trace's clock to the host's
                    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                        t_anchor = time.perf_counter()
            if t_open is not None and t_close is None \
                    and t1 >= t_open + seconds:
                t_close = t1
                if tracing_on:
                    jax.profiler.stop_trace()
                    tracing_on = False
                if timed[-1][0] <= ev["wave"] + 1:
                    raise RunError("the stream ran out of arrivals before "
                                   "the window closed")
                # everyone who may have arrived by now is served in full
                due = [r for w, r in timed if w <= ev["wave"] + 1]
            if due is not None and not drain.pending:
                due = [r for r in due if r not in seen]
                drain.pending = not due
    finally:
        if tracing_on:
            jax.profiler.stop_trace()
    if t_close is None:
        raise RunError("the stream ended before the window closed")
    return Window(stream, events, spans, list(server.last_outcomes),
                  [np.asarray(o, np.int64) for o in outputs],
                  dict(server.last_pool_stats), t_open,
                  trace_offset=t_anchor or 0.0)


# -- the numbers of one run ----------------------------------------------------


class Run:
    """The reduced record of a run that every metric reader reads.

    Every time an end-to-end metric reads is the harness's own clock around
    its calls into the stream, never a stamp the program puts on an event.
    serve_stream queues a wave's events and hands them over once the wave
    has run, so a call that returns the first event of wave w (or of a
    later one) ran wave w from its start: that call's start is the arrival
    time of every request of wave w, and each event's time is the return of
    the call that handed it over."""

    def __init__(self, cell: Cell, win: Window, seconds: float,
                 prefills, compile_times, device: dict):
        self.cell, self.win, self.seconds = cell, win, seconds
        self.device = device
        self.setup: dict = {}
        self.dm = cell.dm
        self.decode_tokens = win.stream.decode_tokens
        self.lengths = [len(p) for p in win.stream.prompts]
        arrival_waves = win.stream.arrival_waves
        n = len(self.lengths)
        self.first_t: dict[int, float] = {}
        self.tokens: list[tuple[float, int, int]] = []  # (t, rid, index)
        self.waves: list[tuple[float, dict]] = []
        # the program's own stamps, read only by per-layer spans
        self.admit_stamp: dict[int, float] = {}
        self.first_stamp: dict[int, float] = {}
        wave_start: dict[int, float] = {}
        pending = sorted(set(arrival_waves))
        k = 0
        for (t0, t1, _), e in zip(win.spans, win.events):
            while k < len(pending) and pending[k] <= e["wave"]:
                wave_start[pending[k]] = t0
                k += 1
            if e["event"] == "token":
                self.tokens.append((t1, e["rid"], e["index"]))
                if e["index"] == 0:
                    self.first_t[e["rid"]] = t1
                    self.first_stamp[e["rid"]] = e["t"]
            elif e["event"] == "admit":
                self.admit_stamp[e["rid"]] = e["t"]
            elif e["event"] == "wave":
                self.waves.append((t1, e))
        self.outcome = {o["rid"]: o for o in win.outcomes}
        self.arrive_t = {r: wave_start[arrival_waves[r]] for r in range(n)
                         if arrival_waves[r] in wave_start}
        timed = [r for r in range(n) if win.stream.timed(r)
                 and r in self.arrive_t]
        self.t_start = min(self.arrive_t[r] for r in timed)
        self.t_end = self.t_start + seconds
        self.requests = [r for r in timed if self.arrive_t[r] < self.t_end]
        self.prefills = [c for c in prefills
                         if self.t_start <= c[0] < self.t_end]
        self.compiles = [t for t in compile_times if self.in_window(t)]
        self.trace: dict | None = None  # device numbers of a traced run

    @property
    def peaks(self) -> dict:
        return peaks_for(self.device["kind"])

    def in_window(self, t: float) -> bool:
        return self.t_start <= t < self.t_end

    def ttft(self, rid: int) -> float:
        if self.outcome[rid]["status"] != "ok" or rid not in self.first_t:
            return float("inf")
        return self.first_t[rid] - self.arrive_t[rid]

    def decode_waves(self):
        """Per decode wave in the window: the live lengths its step read."""
        by_wave: dict[int, list[int]] = {}
        for (_, t1, _), e in zip(self.win.spans, self.win.events):
            if e["event"] == "token" and e["index"] >= 1 \
                    and self.in_window(t1):
                by_wave.setdefault(e["wave"], []).append(
                    self.lengths[e["rid"]] + e["index"])
        return [by_wave[w] for w in sorted(by_wave)]

    def window_flops(self) -> float:
        dm, flops = self.dm, self.cell.family.forward_flops
        total = 0.0
        for t, rid, idx in self.tokens:
            if idx >= 1 and self.in_window(t):
                p = self.lengths[rid] + idx - 1
                total += flops(dm, p, p + 1)
        for _, prefix, n in self.prefills:
            total += flops(dm, prefix, prefix + n)
        return total


def host_report(run: Run) -> str:
    """How steadily the host ran the window's waves: the period between the
    ends of consecutive waves, and the time spent in periods over twice the
    median (stalls, or waves that hold a one-shot prefill)."""
    ends = [t for t, _ in run.waves if run.in_window(t)]
    periods = np.diff(ends) * 1e3 if len(ends) > 1 else np.zeros(1)
    med = float(np.median(periods))
    stall = float(np.sum(np.maximum(periods - 2 * med, 0))) / 1e3
    return (f"window: {len(ends)} waves, period p50 {med:.2f} ms p95 "
            f"{float(np.percentile(periods, 95)):.2f} ms max "
            f"{float(periods.max()):.2f} ms; {stall:.3f} s in periods over "
            f"twice the median")


def label_gap(win: Window, t: float) -> str:
    """What the host was doing at host time `t`."""
    i = bisect.bisect_right([s[0] for s in win.spans], t) - 1
    if i >= 0 and win.spans[i][0] <= t <= win.spans[i][1]:
        return f"in serve_stream, returning '{win.spans[i][2]}'"
    return "in the harness, between events"


def breakdown(run: Run) -> dict:
    tr = run.trace or {}
    gaps = []
    for a, b in tr.get("gaps_ns", [])[:10]:
        mid = (a + b) / 2e9 + run.win.trace_offset
        gaps.append([label_gap(run.win, mid), (b - a) / 1e9])
    return {"device_ops": tr.get("top_ops", []), "idle_gaps": gaps}


# -- correctness -------------------------------------------------------------------


def sample(run: Run, seed: int, k: int) -> list[int]:
    """`k` finished requests of the window, drawn from the seed, with the
    longest prompt among them."""
    done = [r for r in run.requests if run.outcome[r]["status"] == "ok"
            and len(run.win.outputs[r]) == run.decode_tokens]
    if not done:
        return []
    longest = max(done, key=lambda r: (run.lengths[r], -r))
    rest = [r for r in done if r != longest]
    rng = mixlib.rng_for(seed, 4)
    pick = list(rng.choice(len(rest), size=min(k - 1, len(rest)),
                           replace=False)) if rest and k > 1 else []
    return [longest] + [rest[i] for i in sorted(pick)]


def score(cell: Cell, seed: int, prompts, served, device, *,
          control: bool = False) -> dict:
    """The reference's verdict on served tokens: the widest gap below its
    best logit; with `control`, also the gap of the control's own choices."""
    fam = cell.family
    canon = fam.canonical(cell.dm, seed, device)
    seqs = [(np.concatenate([p, s[:-1]]), len(p) - 1)
            for p, s in zip(prompts, served)]
    ref = fam.served_logits(canon, cell.dm, seqs)
    out = {"served_gap_max": reference.served_gap(ref, served),
           "tokens_scored": int(sum(len(s) for s in served))}
    if control:
        ctl = fam.served_logits(canon, cell.dm, seqs, control=True)
        out["control_gap_max"] = reference.served_gap(
            ref, [c.argmax(-1) for c in ctl])
    del canon
    gc.collect()
    return out


def checks(cell: Cell, run: Run, readings: dict) -> dict:
    wrong_len = sum(1 for r in run.requests
                    if run.outcome[r]["status"] == "ok"
                    and len(run.win.outputs[r]) != run.decode_tokens)
    return {
        "served_gap_max": {"value": readings.get("served_gap_max"),
                           "limit": cell.limits["served_gap_max"]},
        "tokens_scored": {"value": readings.get("tokens_scored", 0),
                          "limit": cell.limits["tokens_scored_min"]},
        "wrong_length": {"value": wrong_len, "limit": 0},
    }


def passed(c: dict) -> bool:
    gap, scored, wrong = (c["served_gap_max"], c["tokens_scored"],
                          c["wrong_length"])
    return (gap["value"] is not None and gap["value"] <= gap["limit"]
            and scored["value"] >= scored["limit"]
            and wrong["value"] <= wrong["limit"])


# -- one run -------------------------------------------------------------------------


def open_cache() -> str:
    """JAX's persistent compilation cache where the program keeps it, with
    every program stored."""
    from repro.launch.compile_cache import use_compile_cache

    cache_dir, _ = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


def warm_only(cell: Cell, seed: int) -> None:
    """Compile the cell's programs into the persistent cache and stop: the
    work of the child that `run.py` starts where no earlier run has, so
    that the measured process finds every program there, as a later run
    does, and starts its window on a host not just out of compiling."""
    device = devices_for(cell.chips)[0]
    cache_dir = open_cache()
    server, _ = build_server(cell, seed, device)
    warm(server, cell, device)
    warmmark.mark(cell.name, cache_dir)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_process: float, require_tpu: bool = True,
             log=print) -> dict:
    devs = devices_for(cell.chips, require_tpu)
    device = devs[0]
    cache_dir = open_cache()
    compiles = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    try:
        return _run(cell, seed, seconds, trace, device, devs, compiles,
                    t_process, cache_dir, log)
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)


def _run(cell, seed, seconds, trace, device, devs, compiles, t_process,
         cache_dir, log):
    t = time.perf_counter()
    server, _ = build_server(cell, seed, device)
    jax.block_until_ready(server.params)
    t_params = time.perf_counter() - t
    tap = PrefillTap(server.paged_prefill_vc)
    server.paged_prefill_vc = tap

    t = time.perf_counter()
    warm(server, cell, device)
    t_warm = time.perf_counter() - t
    warm_compiles = compiles.between(t, t + t_warm)
    if cache_dir:
        warmmark.mark(cell.name, cache_dir)

    stream = mixlib.timed_stream(cell.mix, seed, cell.dm["V"],
                                 waves=int(seconds * WAVES_PER_S_MAX))
    trace_dir = BENCH / "out" / "trace" / cell.name if trace else None
    t_stream = time.perf_counter()
    win = drive(server, stream, seconds, trace_dir)
    peak = int((device.memory_stats() or {}).get("peak_bytes_in_use", 0))
    device_info = {"platform": device.platform, "kind": device.device_kind,
                   "count": len(devs), "memory_peak_bytes": peak}
    run = Run(cell, win, seconds, tap.calls,
              [tc for tc, _, _ in compiles.entries if tc >= t_stream],
              device_info)
    run.setup = {"setup_s": run.t_start - t_process, "params_s": t_params,
                 "warm_s": t_warm, "warm_programs": len(warm_compiles),
                 "warm_compile_s": sum(warm_compiles),
                 "preroll_s": run.t_start - t_stream}
    su = run.setup
    log(f"setup: {su['setup_s']:.3f} s = params {t_params:.3f} s + warm-up "
        f"{t_warm:.3f} s ({len(warm_compiles)} programs, "
        f"{sum(warm_compiles):.3f} s of them compiling or loading from "
        f"{cache_dir}) + preroll {su['preroll_s']:.3f} s + the rest "
        f"(imports, JAX start, model build)")
    log(host_report(run))
    late = [name for tc, _, name in compiles.entries if run.in_window(tc)]
    if late:
        log(f"compiled inside the window: {late}")

    if trace:
        files = sorted(trace_dir.rglob("*.xplane.pb"))
        if not files:
            raise RunError("the profiler wrote no trace")
        raw = tracing.read_xspace(str(files[-1]))
        anchor = tracing.host_window(raw, WINDOW_SPAN)
        if anchor is None:
            raise RunError("the trace holds no window anchor span")
        win.trace_offset -= anchor[0] / 1e9  # host time = trace ns/1e9 + offset
        lo = (run.t_start - win.trace_offset) * 1e9
        run.trace = tracing.reduce(raw, (lo, lo + seconds * 1e9))
        if not run.trace:
            raise RunError("no device operation in the traced window")
        device_info["busy_s"] = run.trace["busy_s"]
        device_info["window_s"] = run.trace["window_s"]

    # the program's state goes before the reference runs
    served_ids = sample(run, seed, int(cell.mix["sample_requests"]))
    prompts = [win.stream.prompts[r] for r in served_ids]
    served = [win.outputs[r] for r in served_ids]
    del server, tap
    gc.collect()
    readings = score(cell, seed, prompts, served, device) if served_ids \
        else {}
    verdict = checks(cell, run, readings)

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = metric_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {
        "correct": passed(verdict),
        "attempted": len(run.requests),
        "failed": sum(1 for r in run.requests
                      if run.outcome[r]["status"] != "ok"),
        "metrics": metrics,
        "device": device_info,
    }
    if trace:
        result["breakdown"] = breakdown(run)
    result["checks"] = verdict
    return result
