"""The trace reduction, on a hand-built trace and on a small trace
recorded on a TPU v5 lite (`data/fixture.pbtxt.gz`, made by
make_fixture.py and trimmed to the lines the reduction reads)."""

from __future__ import annotations

import importlib
import os

import pytest

from benchmark import trace

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "data", "fixture.pbtxt.gz")

# Times in microseconds.  Window 0-100 on the host line, with the stage
# spans read+parse 0-50 and discover 50-100 under it; device operations
# 10-30, 20-40 (overlapping) and 58-70.
HAND = """
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 50000000 }
    events { metadata_id: 3 offset_ps: 50000000 duration_ps: 50000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "benchmark.discovery" } }
  event_metadata { key: 2 value { id: 2 name: "read+parse" } }
  event_metadata { key: 3 value { id: 3 name: "discover" } }
}
planes {
  id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 20000000 duration_ps: 20000000 }
    events { metadata_id: 1 offset_ps: 58000000 duration_ps: 12000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000000
    events { metadata_id: 3 offset_ps: 10000000 duration_ps: 30000000 }
    events { metadata_id: 4 offset_ps: 58000000 duration_ps: 12000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "sort.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit__stage_membership(7)" } }
  event_metadata { key: 4 value { id: 4 name: "jit_other(8)" } }
}
"""


def test_hand_built_trace():
    from jax.profiler import ProfileData

    r = trace.reduce(ProfileData.from_text_proto(HAND))
    assert r.window_s == pytest.approx(100e-6)
    assert r.busy_s == pytest.approx(42e-6)  # 10-40 and 58-70
    assert r.idle_share == pytest.approx(0.58)
    assert r.op_s == pytest.approx({"fusion.1": 32e-6, "sort.2": 20e-6})
    assert r.module_s == pytest.approx({"jit__stage_membership(7)": 30e-6,
                                        "jit_other(8)": 12e-6})
    labels = [g[0] for g in r.gaps]
    assert labels == ["discover", "read+parse", "read+parse"]
    assert [g[1] for g in r.gaps] == pytest.approx([30e-6, 18e-6, 10e-6])


def _metric(name):
    return importlib.import_module(f"benchmark.metrics.{name}")


class _Run:
    def __init__(self, red):
        self.trace = red
        self.window = []
        self.work = {"captures": 2257, "lines": 83026,
                     "incidences": 1210725, "pair_instances": 352086123}
        self.peaks = {"int8_ops": 394e12, "hbm_bytes_per_s": 819e9}


def test_hand_built_trace_metrics():
    from jax.profiler import ProfileData

    run = _Run(trace.reduce(ProfileData.from_text_proto(HAND)))
    assert _metric("device_idle").read(run) == pytest.approx(0.58)
    assert _metric("pair_kernel_s").read(run) == pytest.approx(30e-6)
    least = 4 * 1210725 / 819e9  # bytes-bound
    assert _metric("pair_roofline").read(run) == pytest.approx(
        100 * least / 30e-6)


def test_recorded_trace():
    red = trace.reduce(trace.load(FIXTURE))
    run = _Run(red)
    assert red.n_devices == 1
    assert _metric("device_idle").read(run) == pytest.approx(
        PINNED["device_idle"], rel=1e-9)
    assert _metric("pair_kernel_s").read(run) == pytest.approx(
        PINNED["pair_kernel_s"], rel=1e-9)


# Checked by hand from the fixture's text: the window is 118,330,091 ns,
# the union of its "XLA Ops" intervals 42,565,793 ns; the three pair-phase
# programs ran 2,012,707 + 146,828 + 12,486 ns.
PINNED = {"device_idle": 1 - 42565793 / 118330091,
          "pair_kernel_s": (2012707 + 146828 + 12486) * 1e-9}
