"""Device seconds by named scope (benchmark/scopes.py), on a hand-built
trace and on one recorded on a TPU v5 lite (`data/scopes_fixture.pbtxt.gz`,
made by make_scopes_fixture.py)."""

from __future__ import annotations

import importlib
import os

import pytest

from benchmark import scopes, trace
from benchmark.tests import make_scopes_fixture

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "data", "scopes_fixture.pbtxt.gz")
METRICS = {"freq_cond_s": "frequent_conditions",
           "candidate_emit_s": "emit_candidates",
           "capture_intern_s": "intern_captures",
           "line_ids_s": "line_ids"}

# Times in microseconds.  Window 0-100 on the host line.  On the device:
# jit__stage_prepare 10-60 runs ops A 10-20 (frequent_conditions, path on
# the metadata), B 20-40 (emit_candidates, path on the event), C 40-60
# (intern_captures, path by reference) and D 45-50 inside C (line_ids);
# another program runs E 70-90 (membership, no scope of the four) and F
# 95-110 (line_ids, cut by the window's end).
HAND = """
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "benchmark.discovery" } }
}
planes {
  id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 20000000 duration_ps: 20000000
             stats { metadata_id: 1
                     str_value: "jit(_stage_prepare)/emit_candidates/add" } }
    events { metadata_id: 3 offset_ps: 40000000 duration_ps: 20000000 }
    events { metadata_id: 4 offset_ps: 45000000 duration_ps: 5000000 }
    events { metadata_id: 5 offset_ps: 70000000 duration_ps: 20000000 }
    events { metadata_id: 4 offset_ps: 95000000 duration_ps: 15000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000000
    events { metadata_id: 6 offset_ps: 10000000 duration_ps: 50000000 }
    events { metadata_id: 7 offset_ps: 70000000 duration_ps: 40000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "sort.1" stats {
    metadata_id: 1
    str_value: "jit(_stage_prepare)/frequent_conditions/jit(lexsort)/sort" }
  } }
  event_metadata { key: 2 value { id: 2 name: "fusion.2" } }
  event_metadata { key: 3 value { id: 3 name: "sort.3" stats {
    metadata_id: 1 ref_value: 3 } } }
  event_metadata { key: 4 value { id: 4 name: "scatter.4" stats {
    metadata_id: 1 str_value: "jit(_stage_prepare)/line_ids/scatter" } } }
  event_metadata { key: 5 value { id: 5 name: "fusion.5" stats {
    metadata_id: 1 str_value: "jit(_stage_membership)/membership/dot" } } }
  event_metadata { key: 6 value { id: 6 name: "jit__stage_prepare(1)" } }
  event_metadata { key: 7 value { id: 7 name: "jit_other(2)" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 2 value { id: 2 name: "program_id" } }
  stat_metadata { key: 3 value { id: 3
    name: "jit(_stage_prepare)/intern_captures/jit(lexsort)/sort" } }
}
"""
HAND_SECONDS = {"frequent_conditions": 10e-6, "emit_candidates": 20e-6,
                "intern_captures": 15e-6, "line_ids": 10e-6}


class _Run:
    def __init__(self, cell, reduction):
        self.cell = {"name": cell}
        self.trace = reduction
        self.window = []


@pytest.fixture
def hand_trace(tmp_path, monkeypatch):
    """The hand-built trace where run.py leaves a cell's traced discovery."""
    from jax.profiler import ProfileData

    raw = ProfileData.text_proto_to_serialized_xspace(HAND)
    out = tmp_path / "hand.cell" / "xplane" / "plugins" / "profile" / "t"
    out.mkdir(parents=True)
    (out / "host.xplane.pb").write_bytes(raw)
    monkeypatch.setattr(scopes, "TRACE_ROOT", str(tmp_path))
    path = scopes.trace_file("hand.cell")
    return path, trace.reduce(trace.load(path))


def test_hand_built_trace_by_scope(hand_trace):
    path, _ = hand_trace
    got = scopes.by_scope(path, scopes.scope_names())
    assert got == pytest.approx(HAND_SECONDS)


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_hand_built_trace_metrics(hand_trace, metric):
    _, reduction = hand_trace
    reader = importlib.import_module(f"benchmark.metrics.{metric}")
    run = _Run("hand.cell", reduction)
    assert reader.read(run) == pytest.approx(HAND_SECONDS[METRICS[metric]])
    # Nothing to read: an untraced run, or a program without the scopes.
    assert reader.read(_Run("hand.cell", None)) is None
    assert reader.read(_Run("no.such.cell", reduction)) is None


def test_program_without_scopes_reads_nothing(hand_trace, monkeypatch):
    _, reduction = hand_trace
    monkeypatch.setattr(scopes, "scope_names", lambda: None)
    for metric in METRICS:
        reader = importlib.import_module(f"benchmark.metrics.{metric}")
        assert reader.read(_Run("hand.cell", reduction)) is None


def test_trimmed_trace_reads_the_same(hand_trace, tmp_path):
    """make_scopes_fixture.trim keeps what the reductions read."""
    import gzip

    path, reduction = hand_trace
    profile = trace.load(path)
    raw = memoryview(scopes._raw(path))
    out = tmp_path / "trimmed.pbtxt.gz"
    with gzip.open(out, "wt") as f:
        f.write(make_scopes_fixture.trim(raw, profile))
    assert scopes.by_scope(str(out), scopes.scope_names()) == pytest.approx(
        HAND_SECONDS)
    again = trace.reduce(trace.load(str(out)))
    assert again.module_s == pytest.approx(reduction.module_s)
    assert again.busy_s == pytest.approx(reduction.busy_s)


def _prepare_s(reduction) -> float:
    return sum(v for k, v in reduction.module_s.items()
               if k.startswith("jit__stage_prepare"))


def test_recorded_trace_scopes():
    """On the chip's trace the four scopes each read time, together cover
    the join-line build's program, and never more than it."""
    got = scopes.by_scope(FIXTURE, scopes.scope_names())
    prepare = _prepare_s(trace.reduce(trace.load(FIXTURE)))
    assert prepare > 0
    for scope in scopes.scope_names():
        assert got.get(scope, 0.0) > 0, scope
    total = sum(got[s] for s in scopes.scope_names())
    assert total >= 0.95 * prepare
    assert total <= prepare
    assert prepare == pytest.approx(0.98926068, rel=1e-7)
    assert got == pytest.approx(PINNED, rel=1e-7)


# The recording's reduction (LUBM(2), 2^18 triple rows): the join-line
# build's program ran 0.989 s, 99.8% of it under the four scopes.
PINNED = {"frequent_conditions": 0.151600149,
          "emit_candidates": 1.6183516e-05,
          "intern_captures": 0.598118416,
          "line_ids": 0.237551242}
