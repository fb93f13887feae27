"""Rung-3 satellite tests: the plane-bits x emit_pipeline x fused parity
matrix, the probe-and-fallback contract behind each knob, the PR-6
reproduction pin, the kernel-feed stall fraction, and the kernel-resolution
report surfaces.

Everything here runs on the CPU proxy: emit_pipeline cannot trace off TPU
(even interpreted) and XLA CPU rejects int2/int4 custom element types, so
the sub-byte and emit rows exercise exactly the paths production would
take on this backend — which is the contract under test.  The native rows
run on the chip in chip_smoke.py and are compiled for v5e in
tests/test_tpu_compile.py.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from rdfind_tpu.obs import report as obs_report
from rdfind_tpu.ops import cooc, pallas_kernels

N_LINES, NUM_CAPS = 1200, 300


def _planted(rng):
    """Membership with planted j < j+120 containments (random IID admits
    almost none at this density, and a parity gate over empty pair sets
    proves nothing)."""
    plan = cooc.dense_plan(N_LINES, NUM_CAPS)
    member = np.zeros((plan.l_pad, plan.c_pad), bool)
    member[:N_LINES, :NUM_CAPS] = \
        rng.random((N_LINES, NUM_CAPS)) < 0.02
    for j in range(30):
        member[:, j] = 0
        rows = rng.choice(N_LINES, 5, replace=False)
        member[rows, j] = 1
        member[rows, j + 120] = 1
    dt = jnp.int8 if plan.dtype == "int8" else jnp.bfloat16
    m = jnp.asarray(member, dt)
    dep_count = member.sum(axis=0).astype(np.int64)
    cap_id = np.arange(plan.c_pad, dtype=np.int64)
    return m, dep_count, cap_id


def _sweep_pairs(m, dep_count, cap_id, stats=None):
    # The plan is re-resolved inside so each knob combination plans its own
    # sweep — exactly what the model layer does per run.
    plan = cooc.dense_plan(N_LINES, NUM_CAPS)
    d, r, _ = cooc.discover_pairs_dense(
        m, dep_count, cap_id, cap_id, cap_id, 2, NUM_CAPS, plan.tile,
        starts=plan.dep_tile_starts, plan=plan, stats=stats)
    return set(zip(d.tolist(), r.tolist()))


@pytest.mark.parametrize("plane_bits", ["2", "4", "8"])
@pytest.mark.parametrize("emit", ["0", "1"])
@pytest.mark.parametrize("fuse", ["0", "1"])
def test_dense_sweep_parity_matrix(monkeypatch, plane_bits, emit, fuse):
    """The full rung-3 knob grid is bit-identical on the dense CIND sweep:
    knobs select kernels and schedules, never results."""
    rng = np.random.default_rng(17)
    m, dep_count, cap_id = _planted(rng)

    baseline = _sweep_pairs(m, dep_count, cap_id)
    assert baseline, "planted workload must produce CINDs"

    monkeypatch.setattr(cooc, "PLANE_BITS", plane_bits)
    monkeypatch.setattr(cooc, "EMIT_PIPELINE", emit)
    monkeypatch.setattr(cooc, "FUSE_VERDICT", fuse)
    assert _sweep_pairs(m, dep_count, cap_id) == baseline
    assert cooc.dense_plan(N_LINES, NUM_CAPS).plane_bits == int(plane_bits)


def test_pr6_pin_reproduces_defaults(monkeypatch):
    """RDFIND_PLANE_BITS=4 + RDFIND_EMIT_PIPELINE=0 is the PR-6
    configuration: identical pair sets, and a dense plan that differs from
    the resolved default only in the pinned plane width."""
    rng = np.random.default_rng(19)
    m, dep_count, cap_id = _planted(rng)

    baseline = _sweep_pairs(m, dep_count, cap_id)
    base_plan = cooc.dense_plan(N_LINES, NUM_CAPS).describe()

    monkeypatch.setattr(cooc, "PLANE_BITS", "4")
    monkeypatch.setattr(cooc, "EMIT_PIPELINE", "0")
    assert _sweep_pairs(m, dep_count, cap_id) == baseline
    pin_plan = cooc.dense_plan(N_LINES, NUM_CAPS).describe()
    assert pin_plan["plane_bits"] == 4
    assert {k: v for k, v in base_plan.items() if k != "plane_bits"} == \
        {k: v for k, v in pin_plan.items() if k != "plane_bits"}


def test_emit_pipeline_knob_resolution(monkeypatch):
    """The resolver composes knob and probe: "0" always wins, "1" and
    "auto" both defer to the availability probe (force can only select
    paths that exist), and "auto" additionally requires the TPU backend."""
    import jax

    monkeypatch.setattr(cooc, "EMIT_PIPELINE", "0")
    assert not cooc.emit_pipeline_enabled()

    # Probe says no (the real verdict on CPU): even the force falls back.
    monkeypatch.setattr(pallas_kernels, "emit_pipeline_supported",
                        lambda: False)
    monkeypatch.setattr(cooc, "EMIT_PIPELINE", "1")
    assert not cooc.emit_pipeline_enabled()
    monkeypatch.setattr(cooc, "EMIT_PIPELINE", "auto")
    assert not cooc.emit_pipeline_enabled()

    # Probe says yes (monkeypatched — it can never pass off-TPU for real).
    monkeypatch.setattr(pallas_kernels, "emit_pipeline_supported",
                        lambda: True)
    monkeypatch.setattr(cooc, "EMIT_PIPELINE", "1")
    assert cooc.emit_pipeline_enabled()
    monkeypatch.setattr(cooc, "EMIT_PIPELINE", "auto")
    assert cooc.emit_pipeline_enabled() == (jax.default_backend() == "tpu")


def test_emit_probe_is_false_off_tpu():
    """Off TPU emit_pipeline cannot trace, so the verdict is False without
    running a probe (never an exception)."""
    assert pallas_kernels.emit_pipeline_supported() is False


def test_emit_probe_failure_raises_on_tpu(monkeypatch):
    """On TPU a failing emit_pipeline probe names itself instead of quietly
    selecting the grid kernel.  Steered here by reporting the backend as
    TPU: the probe kernel then really runs, and fails, on the CPU."""
    monkeypatch.setattr(pallas_kernels.jax, "default_backend",
                        lambda: "tpu")
    pallas_kernels.emit_pipeline_supported.cache_clear()
    try:
        with pytest.raises(pallas_kernels.KernelProbeError,
                           match="emit_pipeline_supported"):
            pallas_kernels.emit_pipeline_supported()
    finally:
        pallas_kernels.emit_pipeline_supported.cache_clear()


def test_int2_planes_never_native():
    """Mosaic refuses native int2 matmuls on v5e and the XLA int2 dot
    crashes its compiler: the crumb mode always stores int8 elements."""
    assert pallas_kernels._plane_elem("int2") == "int8"
    assert "int2" not in pallas_kernels._PLANE_JNP


def test_unknown_tpu_kind_has_no_plane_width():
    """A device kind missing from SUBBYTE_PLANE_BITS is an error, not a
    default (the CPU's kind is never in the table)."""
    with pytest.raises(RuntimeError, match="SUBBYTE_PLANE_BITS"):
        cooc.tpu_plane_bits()


def test_probe_flip_retraces_via_static_keys(monkeypatch):
    """A probe flip mid-process must change the resolved call, not serve a
    stale cached trace: the emit resolution is a static jit key computed at
    call time, so two calls around a flip may not share a signature."""
    calls = []
    real = pallas_kernels._packed_contains_matrix

    def spy(s, r, p, *, interpret, unpack_dtype, plane_elem, emit=False):
        calls.append(emit)
        return real(s, r, p, interpret=interpret, unpack_dtype=unpack_dtype,
                    plane_elem=plane_elem, emit=emit)

    monkeypatch.setattr(pallas_kernels, "_packed_contains_matrix", spy)
    rng = np.random.default_rng(23)
    sketches = jnp.asarray(
        rng.integers(0, 1 << 32, size=(128, 128), dtype=np.uint32))
    from rdfind_tpu.ops import sketch
    ref_packed, popc = sketch.pack_ref_bits(
        jnp.asarray(rng.integers(0, 100, 128, dtype=np.int32)), bits=4096,
        num_hashes=4)

    monkeypatch.setattr(pallas_kernels, "emit_pipeline_supported",
                        lambda: False)
    a = pallas_kernels.packed_contains_matrix(
        sketches, ref_packed, popc, interpret=True, emit_pipeline=True)
    # Probe "recovers": the same arguments must now resolve to the emit
    # kernel.  Off-TPU that kernel cannot trace — seeing emit=True reach
    # the jitted inner fn (which then raises) proves no stale emit=False
    # program was served.
    monkeypatch.setattr(pallas_kernels, "emit_pipeline_supported",
                        lambda: True)
    try:
        b = pallas_kernels.packed_contains_matrix(
            sketches, ref_packed, popc, interpret=True, emit_pipeline=True)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    except Exception:
        pass  # expected off-TPU: the emit trace itself refuses the backend
    assert calls == [False, True]


def test_kernel_feed_stall_fraction_math():
    """Hand-timed phase vectors: summed across hosts (skew must not hide in
    a mean), None when unmeasured (never a fake 0), >= 1 when
    exchange-bound."""
    hs = {"phase_ms": {"exchange": [10.0, 30.0], "compute": [100.0, 100.0],
                       "pull": [1.0, 1.0], "commit": [0.5, 0.5]}}
    assert obs_report.kernel_feed_stall_fraction(hs) == \
        pytest.approx(40.0 / 200.0)
    # Exchange-bound pod: the fraction crosses 1.
    hs2 = {"phase_ms": {"exchange": [300.0, 340.0],
                        "compute": [150.0, 170.0]}}
    assert obs_report.kernel_feed_stall_fraction(hs2) > 1.0
    # Unmeasured shapes -> None, not 0.
    assert obs_report.kernel_feed_stall_fraction(None) is None
    assert obs_report.kernel_feed_stall_fraction({}) is None
    assert obs_report.kernel_feed_stall_fraction(
        {"phase_ms": {"exchange": [1.0]}}) is None
    assert obs_report.kernel_feed_stall_fraction(
        {"phase_ms": {"exchange": [1.0], "compute": [0.0]}}) is None


def test_resolution_report_struct_and_debug_line(monkeypatch):
    """One describe() surface for every kernel-mode decision: raw knobs
    next to resolved values, published into run stats and rendered on the
    shared --debug dense-plan line."""
    monkeypatch.setattr(cooc, "COOC_DTYPE", "bf16")
    monkeypatch.setattr(cooc, "PLANE_BITS", "2")
    monkeypatch.setattr(cooc, "EMIT_PIPELINE", "0")
    rep = cooc.resolution_report()
    assert rep["plane_bits"] == 2
    assert rep["kernel_dtype"] == "bf16"
    assert rep["emit_pipeline"] is False
    assert rep["knobs"]["RDFIND_PLANE_BITS"] == "2"
    assert rep["knobs"]["RDFIND_EMIT_PIPELINE"] == "0"

    # The models publish it as stats["kernel_resolution"]; the debug
    # renderer folds kernel dtype + emit into the dense-plan line.
    from rdfind_tpu.models import allatonce
    from rdfind_tpu.utils.synth import generate_triples
    stats: dict = {}
    allatonce.discover(generate_triples(300, seed=31, n_predicates=4,
                                        n_entities=40), 2, stats=stats)
    assert stats["kernel_resolution"]["plane_bits"] == 2
    assert stats["kernel_resolution"]["kernel_dtype"] == "bf16"
    text = "\n".join(obs_report.format_debug_lines(stats))
    assert "kernel=bf16/bf16" in text
    assert "emit=0" in text


def test_emit_needs_lane_aligned_blocks(monkeypatch):
    """The emit variant DMAs (128, wk)-word ref blocks itself; Mosaic
    refuses wk < 128, so 2048-bit sketches (64 words) take the grid
    variant even where emit_pipeline is selected, and 4096-bit ones take
    the emit variant."""
    from rdfind_tpu.ops import sketch

    calls = []
    monkeypatch.setattr(pallas_kernels, "emit_pipeline_supported",
                        lambda: True)
    monkeypatch.setattr(pallas_kernels, "_packed_contains_matrix",
                        lambda *a, emit, **k: calls.append(emit))
    rng = np.random.default_rng(2)
    for bits in (2048, 4096):
        ref_packed, popc = sketch.pack_ref_bits(
            jnp.asarray(rng.integers(0, 100, 128, dtype=np.int32)),
            bits=bits, num_hashes=4)
        pallas_kernels.packed_contains_matrix(
            jnp.zeros((128, bits // 32), jnp.uint32), ref_packed, popc,
            interpret=True, unpack_dtype="int8", emit_pipeline=True)
    assert calls == [False, True]


@pytest.mark.parametrize("dtype", ["int8", "bf16"])
def test_block_counts_match_reshape_sum(dtype):
    """The MXU block-count reduction equals the plain reshape-and-sum."""
    rng = np.random.default_rng(3)
    kl, tile = 256, 128
    member = rng.random((4 * kl, 3 * tile)) < 0.05
    member[:kl, tile:2 * tile] = False  # one empty block
    m = jnp.asarray(member, jnp.int8 if dtype == "int8" else jnp.bfloat16)
    got = np.asarray(cooc._stage_block_counts(m, kl=kl, tile=tile))
    want = member.reshape(4, kl, 3, tile).sum(axis=(1, 3))
    np.testing.assert_array_equal(got, want)
