"""Architecture families: the dense decoder's weights and reference are
those the harness drew and computed before families existed; a family of
another architecture runs through the harness as files of its own, and a
fault planted in its reference is caught; no harness file names a
family."""

import hashlib
import json
import re
import time
from pathlib import Path
from unittest import mock

import numpy as np
import pytest

from bench import harness

BENCH = harness.BENCH
DATA = Path(__file__).resolve().parent / "data"
SMALL = json.loads((DATA / "small.json").read_text())
VARIANTS = {"llama": {}, "qwen2": {"model_type": "qwen2"}}  # qwen2: biases

# sha256 (first 32 hex digits) of every tensor and of the reference's
# logits, recorded from bench/weights.py and bench/reference.py as they
# were before the dense decoder moved to families/dense_gqa.py.  The
# logits were recorded on a CPU with several cores: on one core XLA's CPU
# backend sums in another order, and they differ in the last bits.
WEIGHTS = {
    ("llama", 1): "c24e37015611491028e1d7744f8b468e",
    ("llama", 2147483655): "0cf0e1c46984b66b7cdce3e9dc0a81db",
    ("qwen2", 1): "6aaf06fbf4da663becc365d8cd1c04b2",
    ("qwen2", 2147483655): "368e15edf0aee0d1624933ed627fe669",
}
LOGITS = {
    ("llama", 1, False): "5971ac70458baa8feb810b4eefc6eb19",
    ("llama", 1, True): "26d198cfd749bcf1ff9262208e49d0b3",
    ("llama", 2147483655, False): "ac3d7ac3f46e6048b73d9793120e5f9d",
    ("llama", 2147483655, True): "f17d682efa0e0eaf7ddc4210d4c14bf7",
    ("qwen2", 1, False): "6c852d390f591ecf9f6464b8d498245a",
    ("qwen2", 1, True): "ffa4a1f03f758461a54090a51225dc19",
    ("qwen2", 2147483655, False): "d2e62c099a9cfe98bf98df783e731e8d",
    ("qwen2", 2147483655, True): "6c0da475a9948fcbdfa846177272beed",
}


def digest(arrays: dict) -> str:
    h = hashlib.sha256()
    for name in sorted(arrays):
        a = np.asarray(arrays[name])
        h.update(f"{name} {a.dtype} {a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()[:32]


def dense(variant):
    fam = harness.load_family("dense_gqa")
    return fam, fam.dims(dict(SMALL, **VARIANTS[variant]))


@pytest.mark.parametrize("variant,seed", sorted(WEIGHTS))
def test_dense_weights_are_bitwise_the_parents(variant, seed):
    fam, dm = dense(variant)
    assert digest(fam.canonical(dm, seed)) == WEIGHTS[variant, seed]


@pytest.mark.parametrize("variant,seed,control", sorted(LOGITS))
def test_dense_reference_logits_are_the_parents(variant, seed, control):
    fam, dm = dense(variant)
    rng = np.random.default_rng(7)
    seqs = [(rng.integers(0, dm["V"], 40), 29),
            (rng.integers(0, dm["V"], 17), 0)]
    out = fam.served_logits(fam.canonical(dm, seed), dm, seqs,
                            control=control)
    assert [o.shape for o in out] == [(11, dm["V"]), (17, dm["V"])]
    assert digest({str(i): o for i, o in enumerate(out)}) \
        == LOGITS[variant, seed, control]


# -- a family outside bench/families: the program's capacity-routed MoE -------

# Readings of `served_gap_max` on this cell (CPU, one-second windows): the
# program 0-0.067 over seeds 1-12; the reference with expert 0 of layer 0
# given expert 1's down projection 0.53-1.70 over the same seeds.
MOE_LIMIT = 0.2


@pytest.fixture
def moe_cell():
    """The fixture family `capacity_moe` (tests/data/capacity_moe.py) is
    served where the program drops no token: see its docstring."""
    bm = json.loads((harness.REPO / "BENCHMARK.json").read_text())
    with mock.patch("repro.launch.compile_cache.use_compile_cache",
                    lambda: ("", False)):
        yield harness.Cell(
            "small_moe", 1, json.loads((DATA / "small_moe.json").read_text()),
            json.loads((DATA / "smallmix.json").read_text()),
            {"served_gap_max": MOE_LIMIT, "tokens_scored_min": 48},
            [m for m in bm["end_to_end"] if "workloads" not in m], [],
            families=DATA)


def run(cell):
    return harness.run_cell(cell, 1, 1.0, False,
                            t_process=time.perf_counter(), require_tpu=False,
                            log=lambda msg: None)


def test_family_of_another_architecture_is_correct(moe_cell):
    assert moe_cell.family.model_config(moe_cell.config).family == "moe"
    r = run(moe_cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert all(m["value"] > 0 for m in r["metrics"].values())


def test_wrong_expert_weight_in_the_reference_is_not_correct(moe_cell,
                                                              monkeypatch):
    fam = moe_cell.family
    served_logits = fam.served_logits

    def planted(canon, dm, seqs, control=False):
        down = canon["w_down"]  # (layers, experts, F, d)
        canon = dict(canon, w_down=down.at[0, 0].set(down[0, 1]))
        return served_logits(canon, dm, seqs, control=control)

    monkeypatch.setattr(fam, "served_logits", planted)
    r = run(moe_cell)
    assert not r["correct"]
    assert r["checks"]["served_gap_max"]["value"] > MOE_LIMIT


def test_harness_names_no_family():
    names = {p.stem for p in (BENCH / "families").glob("*.py")}
    names |= {p.stem for p in DATA.glob("*.py")}
    assert "dense_gqa" in names and "capacity_moe" in names
    files = [BENCH / f for f in ("harness.py", "calibrate.py", "knee.py",
                                 "run.py")]
    files += sorted((BENCH / "metrics").glob("*.py"))
    for path in files:
        words = set(re.findall(r"[A-Za-z_][A-Za-z0-9_]*", path.read_text()))
        assert not words & names, (path.name, words & names)
        assert "bench.families" not in path.read_text(), path.name
