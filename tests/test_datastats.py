"""The data plane (PR 11 tentpole): per-pass distribution telemetry
(obs/datastats.py), cap-exhaustion forecasting (obs/forecast.py), and their
wiring through the sharded pipeline and the single-device strategies.

Acceptance pins: all four sharded strategies bit-identical with the data
plane on vs off, the disabled path inside the <2% arithmetic overhead bound,
and the forecast advisory landing at least one pass BEFORE the injected
overflow's grow rung.
"""

import time

import numpy as np
import pytest

import jax

from rdfind_tpu.models import allatonce, sharded, small_to_large
from rdfind_tpu.obs import datastats, forecast, metrics, report, tracer
from rdfind_tpu.parallel.mesh import make_mesh
from rdfind_tpu.runtime import faults
from rdfind_tpu.utils.synth import generate_triples


@pytest.fixture(scope="module")
def mesh8():
    assert len(jax.devices()) >= 8, "conftest should provide 8 CPU devices"
    return make_mesh(8)


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    """Every test starts with the data plane disarmed and fault-free."""
    for k in ("RDFIND_DATASTATS", "RDFIND_FORECAST", "RDFIND_FORECAST_WARN",
              "RDFIND_FAULTS", "RDFIND_PAIR_ROW_BUDGET"):
        monkeypatch.delenv(k, raising=False)
    monkeypatch.setenv("RDFIND_BACKOFF_BASE_MS", "1")
    faults.reset()
    tracer.stop()
    metrics.reset()
    yield
    faults.reset()
    tracer.stop()
    metrics.reset()


STRATEGIES = {
    0: sharded.discover_sharded,
    1: sharded.discover_sharded_s2l,
    2: sharded.discover_sharded_approx,
    3: sharded.discover_sharded_late_bb,
}


def _workload():
    return generate_triples(300, seed=5, n_predicates=8, n_entities=32)


# ---------------------------------------------------------------------------
# Bucketing units.
# ---------------------------------------------------------------------------


def test_log2_bucket_counts():
    # 1,1 -> b0; 2,3 -> b1; 4..7 -> b2; zero/negative dropped.
    assert datastats.log2_bucket_counts([1, 1, 2, 3, 4, 7, 0, -2]) == {
        0: 2, 1: 2, 2: 2}
    assert datastats.log2_bucket_counts([]) == {}
    assert datastats.log2_bucket_counts([0, 0]) == {}
    # Values past 2^31 clamp into the last bucket instead of overflowing.
    big = datastats.log2_bucket_counts(np.asarray([2 ** 40], np.int64))
    assert big == {datastats.N_BUCKETS - 1: 1}


def test_hist_from_bins_and_struct_keys():
    assert datastats.hist_from_bins([0, 2, 0, 5]) == {1: 2, 3: 5}
    stats = {}
    datastats.publish_line_stats(stats, hist={1: 2, 3: 5}, n_lines=7,
                                 max_line=9, giant_lines=1, source="t")
    dl = stats["datastats_lines"]
    assert dl["hist_log2"] == {"b1": 2, "b3": 5}
    assert dl["giant_share"] == round(1 / 7, 6)
    assert dl["source"] == "t"


def test_publish_cap_utilization_skips_unplanned():
    stats = {}
    datastats.publish_cap_utilization(
        stats, {"pairs": 100, "freq": 0}, {"pairs": 80, "freq": 5,
                                           "unknown": 3})
    cu = stats["cap_utilization"]
    assert cu == {"pairs": {"planned": 100, "used": 80, "frac": 0.8}}


def test_enabled_gating(monkeypatch):
    assert not datastats.enabled()  # no consumer, no knob
    monkeypatch.setenv("RDFIND_DATASTATS", "1")
    assert datastats.enabled()
    monkeypatch.setenv("RDFIND_DATASTATS", "0")
    assert not datastats.enabled()
    # forecast follows datastats by default, with its own override.
    monkeypatch.setenv("RDFIND_DATASTATS", "1")
    assert forecast.enabled()
    monkeypatch.setenv("RDFIND_FORECAST", "0")
    assert not forecast.enabled()
    monkeypatch.delenv("RDFIND_DATASTATS")
    monkeypatch.setenv("RDFIND_FORECAST", "1")
    assert forecast.enabled()


def test_enabled_follows_tracer(monkeypatch, tmp_path):
    assert not datastats.enabled()
    tracer.start(str(tmp_path))
    try:
        assert datastats.enabled()
    finally:
        tracer.stop()
    assert not datastats.enabled()


# ---------------------------------------------------------------------------
# Forecast units.
# ---------------------------------------------------------------------------


def test_predict_exhaustion():
    assert forecast.predict_exhaustion([(0, 0.2)]) is None  # too short
    assert forecast.predict_exhaustion([(0, 0.5), (1, 0.5)]) is None  # flat
    assert forecast.predict_exhaustion([(0, 0.6), (1, 0.4)]) is None  # falling
    # slope 0.2/pass from 0.1: crosses 1.0 at pass ceil(0.9/0.2)+... = 5.
    assert forecast.predict_exhaustion([(0, 0.1), (1, 0.3), (2, 0.5)]) == 5
    # A fit that crosses in the past still predicts a FUTURE pass.
    p = forecast.predict_exhaustion([(0, 0.9), (1, 0.99)])
    assert p is not None and p >= 2


def test_forecaster_trend_trigger_once_per_cap():
    stats = {}
    fc = forecast.Forecaster(stats, n_pass=8, phase="pair-phase", warn=0.99)
    assert fc.step(0, {"pairs": 0.1}) == []
    raised = fc.step(1, {"pairs": 0.3})
    raised += fc.step(2, {"pairs": 0.5})
    assert [a["cap"] for a in raised] == ["pairs"]
    adv = stats["cap_forecast"]["pairs"]
    assert adv["reason"] == "trend" and adv["predicted_pass"] < 8
    assert stats["cap_forecast_active"] == 1
    # Later passes never re-raise for the same cap.
    assert fc.step(3, {"pairs": 0.9}) == []


def test_forecaster_warn_trigger_and_no_advisory_when_healthy():
    stats = {}
    fc = forecast.Forecaster(stats, n_pass=4, warn=0.85)
    assert fc.step(0, {"pairs": 0.9}) != []  # already past the warn frac
    assert stats["cap_forecast"]["pairs"]["reason"] == "warn"
    healthy = {}
    fc2 = forecast.Forecaster(healthy, n_pass=4, warn=0.85)
    for p in range(4):
        fc2.step(p, {"pairs": 0.5})
    assert "cap_forecast" not in healthy


def test_advisory_line_shared_formatter():
    adv = {"cap": "pairs", "phase": "pair-phase", "pass": 1,
           "predicted_pass": 3, "frac": 0.91, "n_pass": 4, "reason": "warn"}
    line = forecast.advisory_line(adv)
    assert "cap pairs" in line and "pass 3/4" in line and "warn" in line
    # format_lines and format_debug_lines both route through advisory_line.
    stats = {"cap_forecast": {"pairs": adv}}
    assert forecast.format_lines(stats) == [line]
    assert line in report.format_debug_lines(stats)


def test_format_debug_lines_render_datastats():
    stats = {}
    datastats.publish_line_stats(stats, hist={2: 4}, n_lines=4, max_line=6,
                                 source="single")
    datastats.publish_block_skip(stats, n_blocks=10, n_blocks_skipped=4)
    text = "\n".join(report.format_debug_lines(stats))
    assert "datastats[lines]" in text and "datastats[block_skip]" in text
    assert "frac=0.4" in text


# ---------------------------------------------------------------------------
# Wiring: single-device strategies.
# ---------------------------------------------------------------------------


def test_single_device_publishes(monkeypatch):
    monkeypatch.setenv("RDFIND_DATASTATS", "1")
    triples = _workload()
    for discover in (allatonce.discover, small_to_large.discover):
        stats = {}
        discover(triples, 2, stats=stats)
        assert stats["datastats_lines"]["source"] == "single", discover
        assert stats["datastats_lines"]["n_lines"] > 0
        assert stats["datastats_captures"]["max_support"] > 0
        # The histogram buckets positive sizes only, so its mass is bounded
        # by (and usually equal to) the line count.
        mass = sum(stats["datastats_lines"]["hist_log2"].values())
        assert 0 < mass <= stats["datastats_lines"]["n_lines"]


def test_single_device_silent_when_disabled():
    stats = {}
    allatonce.discover(_workload(), 2, stats=stats)
    assert "datastats_lines" not in stats
    assert "cap_utilization" not in stats


# ---------------------------------------------------------------------------
# Wiring: the sharded pipeline (all four strategies, on vs off).
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("strategy", sorted(STRATEGIES))
def test_sharded_bit_identical_with_data_plane(mesh8, strategy, monkeypatch,
                                              s2l_pair_phase):
    """The acceptance matrix: rows identical with datastats+forecast on vs
    off, and the on-run actually published the data-plane keys."""
    triples = _workload()
    discover = STRATEGIES[strategy]
    stats_off = {}
    off = discover(triples, 2, mesh=mesh8, stats=stats_off).to_rows()
    monkeypatch.setenv("RDFIND_DATASTATS", "1")
    monkeypatch.setenv("RDFIND_FORECAST", "1")
    stats_on = {}
    on = discover(triples, 2, mesh=mesh8, stats=stats_on).to_rows()
    assert on == off
    assert stats_on["datastats_lines"]["source"] == "sharded"
    assert stats_on["datastats_lines"]["n_lines"] > 0
    assert stats_on["cap_utilization"]
    for row in stats_on["cap_utilization"].values():
        assert 0.0 <= row["frac"] == round(row["used"] / row["planned"], 6)
    assert stats_on["cap_utilization_passes"], "no per-pass trajectory"
    for entry in stats_on["cap_utilization_passes"]:
        assert "pass" in entry and "pairs" in entry
    # And the off-run stayed clean of every data-plane key.
    for key in ("datastats_lines", "datastats_captures", "cap_utilization",
                "cap_utilization_passes", "cap_forecast"):
        assert key not in stats_off, key


def test_sharded_disabled_path_overhead_under_2pct(mesh8):
    """The data plane's disabled path is one env read + flag checks at
    pipeline init plus one attribute check per pass: bound (measured per-call
    cost) x (calls per run) under 2% of the measured pipeline wall —
    deterministic on a noisy box, same scheme as the tracer's bound."""
    assert not datastats.enabled()
    triples = _workload()
    stats: dict = {}
    sharded.discover_sharded(triples, 2, mesh=mesh8, stats=stats)  # warm
    stats = {}
    t0 = time.perf_counter()
    sharded.discover_sharded(triples, 2, mesh=mesh8, stats=stats)
    wall_s = time.perf_counter() - t0

    n = 5_000
    t0 = time.perf_counter()
    for _ in range(n):
        datastats.enabled()
        forecast.enabled()
    per_call_s = (time.perf_counter() - t0) / (2 * n)
    # enabled() resolves once at init (datastats) + once per attempt
    # (forecast); per pass the gate is a python attribute check, far cheaper
    # than enabled() — charge it at full price anyway for headroom.
    calls = 2 + 2 * max(stats.get("n_pair_passes", 1), 1)
    overhead = calls * per_call_s
    assert overhead / wall_s < 0.02, (
        f"disabled data plane costs {overhead * 1e3:.3f}ms over "
        f"{wall_s * 1e3:.0f}ms wall ({overhead / wall_s:.2%})")


# ---------------------------------------------------------------------------
# Forecast vs the degradation ladder (differential, injected overflow).
# ---------------------------------------------------------------------------


def test_forecast_advisory_precedes_injected_grow_rung(mesh8, monkeypatch):
    """With an overflow injected at pass 2, the forecaster must name an
    exhausted cap at least one pass earlier than the grow rung it predicts
    (warn frac forced to 0 so the advisory fires on the first trajectory
    point — the test pins ordering, not threshold calibration)."""
    monkeypatch.setenv("RDFIND_FORECAST", "1")
    monkeypatch.setenv("RDFIND_FORECAST_WARN", "0")
    monkeypatch.setattr(sharded, "PAIR_ROW_BUDGET", 1 << 13)  # several passes
    want = sharded.discover_sharded(_workload(), 2, mesh=mesh8).to_rows()
    monkeypatch.setenv("RDFIND_FAULTS", "overflow@cind:pass=2")
    faults.reset()
    stats: dict = {}
    got = sharded.discover_sharded(_workload(), 2, mesh=mesh8,
                                   stats=stats).to_rows()
    assert stats["n_pair_passes"] > 2  # the injected pass actually ran
    assert got == want  # the grow rung recovered bit-identically
    grow_passes = [d["pass"] for d in stats.get("degradations", [])
                   if d["action"] == "grow" and "pass" in d]
    assert 2 in grow_passes, stats.get("degradations")
    assert stats.get("cap_forecast"), "no advisory raised"
    first_advisory = min(a["pass"] for a in stats["cap_forecast"].values())
    assert first_advisory <= min(grow_passes) - 1, (
        f"advisory at pass {first_advisory} did not precede the grow rung "
        f"at pass {min(grow_passes)}")


def test_pass_utilization_trajectory_feeds_forecaster(monkeypatch):
    """publish_pass_utilization's entries are exactly the Forecaster's
    input shape and land in the registry list."""
    stats = {}
    entry = datastats.publish_pass_utilization(
        stats, 3, {"pairs": 0.25, "giant_pairs": 0.1})
    assert entry == {"pass": 3, "giant_pairs": 0.1, "pairs": 0.25}
    assert stats["cap_utilization_passes"] == [entry]
    fc = forecast.Forecaster(stats, n_pass=8, warn=0.2)
    raised = fc.step(entry["pass"],
                     {k: v for k, v in entry.items() if k != "pass"})
    assert {a["cap"] for a in raised} == {"pairs"}


# ---------------------------------------------------------------------------
# report --summary (satellite a): rebuilt from the trace counter lanes.
# ---------------------------------------------------------------------------


def _traced_pass(tr, p, fracs):
    tr.counter("host_skew", skew=1.0 + p / 10, slowest=0)
    tr.counter("pass_phase_ms", exchange=1.0, compute=2.0, pull=0.5,
               commit=0.1)
    tr.counter("cap_utilization", **{"pass": p, **fracs})


def test_report_summary_from_trace(tmp_path):
    d = str(tmp_path)
    tracer.start(d)
    try:
        _traced_pass(tracer, 0, {"pairs": 0.2})
        _traced_pass(tracer, 1, {"pairs": 0.6})
        tracer.instant("cap_forecast", cat=tracer.CAT_PASS, cap="pairs",
                       phase="pair-phase", predicted_pass=3, n_pass=4,
                       frac=0.6, reason="trend", **{"pass": 1})
    finally:
        tracer.stop()
    summary = report.summarize_passes(d)
    rows = summary[0]["passes"]
    assert [r["pass"] for r in rows] == [0, 1]
    assert rows[0]["skew"] == 1.0 and rows[1]["skew"] == 1.1
    assert rows[1]["cap_util"] == {"pairs": 0.6}
    assert summary[0]["advisories"][0]["cap"] == "pairs"
    text = "\n".join(report.format_summary_lines(summary))
    assert "host 0 pass 1" in text and "util pairs=0.6" in text
    assert "forecast [pair-phase]: cap pairs" in text


def test_report_summary_cli(tmp_path):
    import os
    import subprocess
    import sys

    d = str(tmp_path)
    tracer.start(d)
    try:
        _traced_pass(tracer, 0, {"pairs": 0.4})
    finally:
        tracer.stop()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-m", "rdfind_tpu.obs.report", d, "--summary"],
        capture_output=True, text=True, timeout=60, cwd=repo)
    assert r.returncode == 0, r.stderr
    assert "host 0 pass 0" in r.stdout and "util pairs=0.4" in r.stdout
