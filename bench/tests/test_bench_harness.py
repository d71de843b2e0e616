"""The harness end to end on the CPU at a small size: a sound run is
correct; the faults a serving cell can have, planted in the timed path,
and the float8 control are not."""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path
from unittest import mock

import jax
import jax.numpy as jnp
import pytest

from bench import calibrate, harness

DATA = Path(__file__).resolve().parent / "data"
# between the program's largest reading at this size (0.019 over seeds
# 1-6) and the float8 control's smallest (0.120): the limit for these tests
LIMIT = 0.06


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """The harness turns JAX's persistent compilation cache on for the
    checkout; these CPU tests share a process with other test files, so
    they leave it off."""
    with mock.patch("repro.launch.compile_cache.use_compile_cache",
                    lambda: ("", False)):
        yield


@pytest.fixture(scope="module")
def cell():
    bm = json.loads((harness.REPO / "BENCHMARK.json").read_text())
    return harness.Cell(
        "small", 1, json.loads((DATA / "small.json").read_text()),
        json.loads((DATA / "smallmix.json").read_text()),
        {"served_gap_max": LIMIT, "tokens_scored_min": 48},
        [m for m in bm["end_to_end"] if "workloads" not in m], [])


def run(cell, seed=1):
    return harness.run_cell(cell, seed, 1.0, False,
                            t_process=time.perf_counter(), require_tpu=False,
                            log=lambda msg: None)


def test_sound_run_is_correct(cell):
    r = run(cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"ttft_p50_s", "itl_p95_ms", "output_tok_s",
                                 "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())


def broken_decode(fault):
    from repro.runtime import steps

    real = steps.build_decode_step

    def build(woven, **kw):
        fn = real(woven, **kw)

        def step(params, inputs, cache):
            logits, new_cache = fn(params, inputs, cache)
            if fault == "state_unchanged":
                return logits, cache
            return jnp.roll(logits, 1, axis=-1), new_cache

        return step

    return build


@pytest.mark.parametrize("fault", ["state_unchanged", "token_altered"])
def test_fault_in_timed_path_is_not_correct(cell, fault):
    with mock.patch("repro.runtime.server.build_decode_step",
                    broken_decode(fault)):
        r = run(cell)
    assert not r["correct"]
    assert r["checks"]["served_gap_max"]["value"] > LIMIT


def test_float8_control_is_not_correct(cell):
    found = list(calibrate.readings(cell, [1, 2, 3], 1.0, control=True,
                                    device=jax.devices()[0]))
    assert len(found) == 3
    for f in found:
        assert f["correct"] and not f["control_correct"], f
        assert f["served_gap_max"] <= LIMIT < f["control_gap_max"], f


@pytest.mark.parametrize("where", ["checkout", "bench_only"])
def test_run_refuses_without_a_chip_or_the_program(where, tmp_path):
    root = harness.REPO
    if where == "bench_only":
        shutil.copy(root / "BENCHMARK.json", tmp_path)
        shutil.copytree(root / "bench", tmp_path / "bench",
                        ignore=shutil.ignore_patterns("out", "__pycache__"))
        root = tmp_path
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "yi6b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_benchmark_names_resolve():
    bm = json.loads((harness.REPO / "BENCHMARK.json").read_text())
    for w in bm["workloads"]:
        cell = harness.load_cell(w["name"], bm)
        assert cell.config["name"] == w["config"]
        assert {"served_gap_max", "tokens_scored_min"} <= set(cell.limits)
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) > 1
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in reported
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))
    for c in bm["configs"]:
        conf = json.loads((harness.REPO / c["file"]).read_text())
        assert conf["source"] == c["source"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        family = harness.load_family(conf["family"])
        for fn in harness.FAMILY_API:
            assert callable(getattr(family, fn)), (conf["family"], fn)
        assert family.dims(conf)["V"] == conf["vocab_size"]


def test_warm_mark_follows_the_cache(tmp_path, monkeypatch):
    from bench import warmmark

    monkeypatch.setattr(warmmark, "MARKS", tmp_path / "marks")
    monkeypatch.delenv(warmmark.ENV, raising=False)
    cache = tmp_path / "cache"
    cache.mkdir()
    assert not warmmark.is_warm("cell")
    warmmark.mark("cell", str(cache))
    assert not warmmark.is_warm("cell")  # the cache is empty
    (cache / "entry").write_bytes(b"x")
    assert warmmark.is_warm("cell") and not warmmark.is_warm("other")
    monkeypatch.setenv(warmmark.ENV, str(tmp_path / "elsewhere"))
    assert not warmmark.is_warm("cell")


def test_cold_run_warms_up_in_a_child_first():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2s5.longdoc",
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=harness.REPO, env=env, capture_output=True, text=True,
        timeout=300)
    # the child finds no chip, and the run ends there with its exit code
    assert "warm-up in a child process" in p.stderr
    assert "no chip" in p.stderr
    assert p.returncode == 2 and p.stdout.strip() == ""
