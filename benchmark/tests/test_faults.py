"""A whole run of the harness, without the look for a chip, on a small
graph on the CPU: sound, it is correct; with the timed path broken
underneath, `correct` comes out false."""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from benchmark import run

CONFIG = {
    "name": "faults-small", "projections": "spo",
    "flags": ["--use-fis", "--clean-implied"],
    "generator": {"kind": "lubm", "universities": 1, "departments": 2,
                  "structure_seed": 11},
}
TRAFFIC = {"aao": {"support": 20, "flags": ["--traversal-strategy", "0"]},
           "s2l": {"support": 20, "flags": ["--traversal-strategy", "1"]}}


def bench(traffic):
    return {"workloads": [{"name": f"small.{traffic}", "config": "faults",
                           "traffic": traffic, "chips": 1}],
            "end_to_end": [{"name": n, "unit": u} for n, u in (
                ("triples_per_s", "triples/s"), ("peak_hbm_gib", "GiB"),
                ("setup_s", "s"))],
            "per_layer": []}


def run_small(traffic, seed=2 ** 31 + 3, trace=0, b=None):
    args = run.parse_args(["--workload", f"small.{traffic}", "--seed",
                           str(seed), "--seconds", "0.5", "--trace",
                           str(trace)])
    return run.run_cell(args, require_tpu=False, bench=b or bench(traffic),
                        config=CONFIG, traffic=TRAFFIC[traffic])


@pytest.fixture
def driver():
    return pytest.importorskip("rdfind_tpu.runtime.driver")


@pytest.mark.parametrize("traffic", ["aao", "s2l"])
def test_sound_run_is_correct(traffic):
    out = run_small(traffic)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 2 and out["failed"] == 0
    assert list(out)[-1] == "checks"


def _altered(fn):
    """A strategy whose first CIND's support is off by one."""
    def wrapped(*a, **k):
        table = fn(*a, **k)
        support = np.array(table.support, copy=True)
        support[0] += 1
        return dataclasses.replace(table, support=support)
    return wrapped


def _empty(fn):
    """A strategy that skips the work and returns no CINDs."""
    def wrapped(*a, **k):
        table = fn(*a, **k)
        return type(table).empty()
    return wrapped


@pytest.mark.parametrize("strategy,traffic", [(0, "aao"), (1, "s2l")])
@pytest.mark.parametrize("fault", [_altered, _empty])
def test_broken_answer_is_not_correct(driver, monkeypatch, strategy,
                                      traffic, fault):
    monkeypatch.setitem(driver.STRATEGIES, strategy,
                        fault(driver.STRATEGIES[strategy]))
    out = run_small(traffic)
    assert not out["correct"], out["checks"]
    assert out["failed"] == out["attempted"]


def test_half_the_input_left_out_is_not_correct(driver, monkeypatch):
    ingest = driver.native.ingest_files

    def half(*a, **k):
        ids, dictionary = ingest(*a, **k)
        return ids[: len(ids) // 2], dictionary

    monkeypatch.setattr(driver.native, "ingest_files", half)
    out = run_small("aao")
    assert not out["correct"], out["checks"]


def test_traced_run_reports_the_per_layer_metrics(monkeypatch):
    """--trace 1 on the CPU, with the hand-built trace of test_trace.py in
    place of the profiler's: every per-layer metric of the cell comes out
    with its unit, and the breakdown and the device's times are there."""
    from jax.profiler import ProfileData

    from benchmark import peaks, trace
    from benchmark.tests.test_trace import HAND

    def fake_traced(jax, argv, out_path, trace_dir):
        return (run.record(argv, out_path),
                trace.reduce(ProfileData.from_text_proto(HAND)))

    monkeypatch.setattr(run, "traced_discovery", fake_traced)
    monkeypatch.setitem(peaks.TPU_PEAKS, "cpu",
                        peaks.TPU_PEAKS["TPU v5 lite"])
    b = bench("aao")
    b["per_layer"] = [{"name": n, "unit": u} for n, u in (
        ("ingest_s", "s"), ("discover_s", "s"), ("device_idle", "fraction"),
        ("pair_kernel_s", "s"), ("pair_roofline", "%"))]
    out = run_small("aao", seed=9, trace=1, b=b)
    assert out["correct"]
    assert {k: v["unit"] for k, v in out["metrics"].items()} == {
        "ingest_s": "s", "discover_s": "s", "device_idle": "fraction",
        "pair_kernel_s": "s", "pair_roofline": "%"}
    assert out["metrics"]["device_idle"]["value"] == pytest.approx(0.58)
    assert out["device"]["busy_s"] == pytest.approx(42e-6)
    assert out["breakdown"]["idle_gaps"][0][0] == "discover"
