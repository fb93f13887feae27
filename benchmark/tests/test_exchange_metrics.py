"""exchange_s and exchange_roofline (the four-chip cell's join-line
exchange): a hand-built two-chip trace, the least time, and the recorded
one-chip trace, which has no exchange scope."""

from __future__ import annotations

import gzip
import os

import pytest

from benchmark import scopes, trace
from benchmark.metrics import exchange_roofline, exchange_s

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "data", "scopes_fixture.pbtxt.gz")

# Times in microseconds.  Window 0-100.  Chip 0: an all_to_all 10-30 under
# exchange and a sort 30-40 under line_ids; chip 1: an all_to_all 10-50
# under exchange, and a psum 90-110 under exchange cut by the window's end.
TWO_CHIPS = """
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
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 30000000 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "all-to-all.1" stats {
    metadata_id: 1 str_value: "jit(_cooc_step)/shard_map/exchange/all_to_all" }
  } }
  event_metadata { key: 2 value { id: 2 name: "sort.2" stats {
    metadata_id: 1 str_value: "jit(_emit_step)/shard_map/line_ids/sort" } } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
}
planes {
  id: 3 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 40000000 }
    events { metadata_id: 2 offset_ps: 90000000 duration_ps: 20000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "all-to-all.1" stats {
    metadata_id: 1 str_value: "jit(_cooc_step)/shard_map/exchange/all_to_all" }
  } }
  event_metadata { key: 2 value { id: 2 name: "all-reduce.2" stats {
    metadata_id: 1 str_value: "jit(_cooc_step)/shard_map/exchange/psum" } } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
}
"""
WORK = {"captures": 88, "lines": 15_679_733, "incidences": 100_124_060,
        "pair_instances": 0}


class _Run:
    def __init__(self, cell, reduction, chips=4):
        self.cell = {"name": cell, "chips": chips}
        self.trace = reduction
        self.work = WORK


def _place(tmp_path, monkeypatch, cell, raw):
    out = tmp_path / cell / "xplane" / "plugins" / "profile" / "t"
    out.mkdir(parents=True)
    (out / "host.xplane.pb").write_bytes(raw)
    monkeypatch.setattr(scopes, "TRACE_ROOT", str(tmp_path))
    path = scopes.trace_file(cell)
    return trace.reduce(trace.load(path))


def test_two_chip_trace(tmp_path, monkeypatch):
    from jax.profiler import ProfileData

    raw = ProfileData.text_proto_to_serialized_xspace(TWO_CHIPS)
    run = _Run("two.chips", _place(tmp_path, monkeypatch, "two.chips", raw))
    assert exchange_s.per_chip(run) == pytest.approx(
        {"/device:TPU:0": 20e-6, "/device:TPU:1": 50e-6})
    assert exchange_s.read(run) == pytest.approx(35e-6)
    assert exchange_s.read(_Run("two.chips", None)) is None
    assert exchange_s.read(_Run("no.such.cell", run.trace)) is None
    monkeypatch.setattr(exchange_s, "scope_name", lambda: None)
    assert exchange_s.read(run) is None


def test_roofline_at_the_least_time_reads_100(monkeypatch):
    """An exchange that takes exactly the least time reads 100%, never
    more; a slower one reads less."""
    monkeypatch.setattr(exchange_roofline, "device_kind",
                        lambda: "TPU v5 lite")
    bw = exchange_roofline.ICI_BYTES_PER_S["TPU v5 lite"]
    least = exchange_roofline.least_time(WORK, 4, bw)
    # The bitmap is the smaller encoding here: 3/4 of 15.7M lines x 88
    # captures / 8 bytes, sent by four chips at 200 GB/s each.
    assert least == pytest.approx(0.75 * WORK["lines"] * 88 / 8 / (4 * bw))
    run = _Run("any", object())
    monkeypatch.setattr(exchange_s, "read", lambda r: least)
    assert 0 < exchange_roofline.read(run) <= 100.0
    assert exchange_roofline.read(run) == pytest.approx(100.0)
    monkeypatch.setattr(exchange_s, "read", lambda r: 2 * least)
    assert exchange_roofline.read(run) == pytest.approx(50.0)
    monkeypatch.setattr(exchange_s, "read", lambda r: None)
    assert exchange_roofline.read(run) is None


def test_unknown_device_kind_is_an_error(monkeypatch):
    monkeypatch.setattr(exchange_roofline, "device_kind", lambda: "cpu")
    monkeypatch.setattr(exchange_s, "read", lambda r: 1.0)
    with pytest.raises(KeyError, match="ICI bandwidth"):
        exchange_roofline.read(_Run("any", object()))


def test_recorded_one_chip_trace_reads_nothing(tmp_path, monkeypatch):
    """The recorded one-chip trace has no exchange scope: both metrics
    read None, and nothing raises."""
    from jax.profiler import ProfileData

    with gzip.open(FIXTURE, "rt") as f:
        raw = ProfileData.text_proto_to_serialized_xspace(f.read())
    run = _Run("one.chip", _place(tmp_path, monkeypatch, "one.chip", raw),
               chips=1)
    assert set(exchange_s.per_chip(run).values()) == {0.0}
    assert exchange_s.read(run) is None
    assert exchange_roofline.read(run) is None
