"""Dense-plan tile scheduling, occupancy accounting, and cooc dtype.

The acceptance contract of the occupancy rework: all four traversal
strategies produce bit-identical CIND output with int8 vs bf16 membership
and with tile-skip scheduling on vs off, while the scheduled plan's issued
FLOPs drop (occupancy > 0.9 on headline-shaped workloads where the pow2
plan measured ~0.56 row occupancy).
"""

import numpy as np
import pytest

from rdfind_tpu.ops import cooc


def test_dense_plan_headline_occupancy(monkeypatch):
    # The round-5 headline workload shape (BASELINE.md): 18491 real lines
    # padded by the pow2 plan to a 32768 x 8192 product (~56% row occupancy).
    plan = cooc.dense_plan(18491, 5000)
    assert plan.occupancy > 0.9
    assert plan.l_pad % cooc.LINE_MULT == 0 and plan.l_pad >= 18491
    assert plan.c_pad % cooc.CAP_MULT == 0 and plan.c_pad >= 5000

    monkeypatch.setattr(cooc, "TILE_SCHEDULE", False)
    legacy = cooc.dense_plan(18491, 5000)
    assert legacy.l_pad == 32768 and legacy.c_pad == 8192
    assert 18491 / legacy.l_pad == pytest.approx(0.56, abs=0.01)
    # The scheduled plan issues measurably fewer FLOPs for the same work.
    assert plan.issued_flops < legacy.issued_flops
    assert plan.real_flops == legacy.real_flops


@pytest.mark.parametrize("n_lines,num_caps", [
    (1, 1), (7, 3), (300, 200), (18491, 5000), (100_000, 8193),
    (12_345, 4736), (50_000, 4097)])
def test_dense_plan_properties(n_lines, num_caps):
    plan = cooc.dense_plan(n_lines, num_caps)
    # Tile starts must be exact under dynamic_slice clamping: the tile
    # divides c_pad, so no start can clamp onto (and recount) earlier rows.
    assert plan.c_pad % plan.tile == 0
    assert plan.tile % cooc.CAP_MULT == 0
    starts = plan.dep_tile_starts
    # The schedule covers [0, num_caps) exactly once and skips all-padding
    # tiles.
    assert starts[0] == 0
    assert all(b - a == plan.tile for a, b in zip(starts, starts[1:]))
    assert starts[-1] < num_caps <= starts[-1] + plan.tile
    assert plan.n_tiles_skipped == plan.n_tiles - len(starts)
    assert 0 < plan.occupancy <= 1
    d = plan.describe()
    assert d["occupancy"] == round(plan.occupancy, 4)
    assert d["dtype"] == cooc.resolved_cooc_dtype()


def test_pow2_plan_skips_padding_tiles(monkeypatch):
    # Under the legacy pow2 buckets, whole dep tiles can be pure padding;
    # the schedule never dispatches them (the "row/column tile skip").
    monkeypatch.setattr(cooc, "TILE_SCHEDULE", False)
    plan = cooc.dense_plan(100_000, 8193)
    assert plan.c_pad == 16384 and plan.tile == 4096
    assert plan.n_tiles == 4
    assert plan.dep_tile_starts == (0, 4096, 8192)
    assert plan.n_tiles_skipped == 1


def test_dense_plan_legacy_unpack():
    l_pad, c_pad, tile = cooc.dense_plan(1000, 500)
    assert (l_pad, c_pad, tile) == (cooc.dense_plan(1000, 500).l_pad,
                                    cooc.dense_plan(1000, 500).c_pad,
                                    cooc.dense_plan(1000, 500).tile)


def test_tile_for_divides():
    for c_pad in (128, 256, 4736, 5120, 8192, 128 * 37, 128 * 96):
        t = cooc.tile_for(c_pad)
        assert c_pad % t == 0 and t % 128 == 0 and t <= cooc.DEFAULT_TILE


def test_resolved_dtype_policy(monkeypatch):
    monkeypatch.setattr(cooc, "COOC_DTYPE", "bf16")
    assert cooc.resolved_cooc_dtype() == "bf16"
    monkeypatch.setattr(cooc, "COOC_DTYPE", "int8")
    assert cooc.resolved_cooc_dtype() == "int8"
    monkeypatch.setattr(cooc, "COOC_DTYPE", "auto")
    # auto = int8 on the TPU MXU; XLA CPU's generic int8 loops are slower
    # than bf16, so the CPU resolves bf16 and its wall clock cannot regress.
    assert cooc.resolved_cooc_dtype() == (
        "int8" if cooc._on_tpu() else "bf16")
    import jax
    if jax.default_backend() != "tpu":
        assert cooc.resolved_cooc_dtype() == "bf16"


@pytest.mark.parametrize("dtype,schedule,fuse", [
    ("bf16", True, False), ("int8", True, False), ("int8", False, False),
    ("bf16", False, False),
    # Fused-verdict rows: the Pallas fused kernel (interpreted off-TPU)
    # replaces the materialized cooc_cind_tile; outputs must stay
    # bit-identical across the full plane-bits x fusion x schedule matrix.
    ("int8", True, True), ("bf16", True, True), ("int8", False, True)])
def test_strategies_invariant_to_dtype_and_schedule(monkeypatch, dtype,
                                                    schedule, fuse):
    """All four traversal strategies: bit-identical CIND output across
    int8/bf16 membership, tile-skip scheduling on/off, and fused-verdict
    on/off (the acceptance differential).  The baseline is the resolved
    default configuration."""
    from rdfind_tpu.models import allatonce, approximate, late_bb, \
        small_to_large
    from rdfind_tpu.utils.synth import generate_triples

    triples = generate_triples(500, seed=23, n_predicates=5, n_entities=48)
    strategies = {
        "allatonce": allatonce.discover,
        "small_to_large": small_to_large.discover,
        "approximate": approximate.discover,
        "late_bb": late_bb.discover,
    }
    base = {name: fn(triples, 2).to_rows() for name, fn in strategies.items()}
    monkeypatch.setattr(cooc, "COOC_DTYPE", dtype)
    monkeypatch.setattr(cooc, "TILE_SCHEDULE", schedule)
    monkeypatch.setattr(cooc, "FUSE_VERDICT", "1" if fuse else "0")
    for name, fn in strategies.items():
        stats = {}
        got = fn(triples, 2, stats=stats).to_rows()
        assert got == base[name], (name, dtype, schedule, fuse)
        if "dense_plan" in stats:
            assert stats["cooc_dtype"] == dtype
            assert stats["dense_plan"]["policy"] == (
                "tile" if schedule else "pow2")
            assert stats["dense_plan"]["fuse_verdict"] is fuse


def test_plane_bits_resolution_policy(monkeypatch):
    # Explicit pins are honored; "auto" takes the chip kind's entry of
    # SUBBYTE_PLANE_BITS on TPU and 8-bit planes everywhere else.
    monkeypatch.setattr(cooc, "PLANE_BITS", "8")
    assert cooc.resolved_plane_bits() == 8
    monkeypatch.setattr(cooc, "PLANE_BITS", "4")
    assert cooc.resolved_plane_bits() == 4
    monkeypatch.setattr(cooc, "PLANE_BITS", "2")
    assert cooc.resolved_plane_bits() == 2
    monkeypatch.setattr(cooc, "PLANE_BITS", "auto")
    assert cooc.resolved_plane_bits() == (
        cooc.tpu_plane_bits() if cooc._on_tpu() else 8)
    # The kernel dtype narrows to int4/int2 only on int8 membership: the
    # bf16 fallback keeps its own planes.
    monkeypatch.setattr(cooc, "COOC_DTYPE", "int8")
    monkeypatch.setattr(cooc, "PLANE_BITS", "4")
    assert cooc.resolved_kernel_dtype() == "int4"
    monkeypatch.setattr(cooc, "PLANE_BITS", "2")
    assert cooc.resolved_kernel_dtype() == "int2"
    monkeypatch.setattr(cooc, "PLANE_BITS", "8")
    assert cooc.resolved_kernel_dtype() == "int8"
    monkeypatch.setattr(cooc, "COOC_DTYPE", "bf16")
    monkeypatch.setattr(cooc, "PLANE_BITS", "4")
    assert cooc.resolved_kernel_dtype() == "bf16"
    monkeypatch.setattr(cooc, "PLANE_BITS", "2")
    assert cooc.resolved_kernel_dtype() == "bf16"


def test_fuse_and_block_skip_knobs(monkeypatch):
    import jax

    monkeypatch.setattr(cooc, "FUSE_VERDICT", "0")
    assert not cooc.fuse_verdict_enabled()
    monkeypatch.setattr(cooc, "FUSE_VERDICT", "1")
    assert cooc.fuse_verdict_enabled()
    monkeypatch.setattr(cooc, "FUSE_VERDICT", "auto")
    assert cooc.fuse_verdict_enabled() == (jax.default_backend() == "tpu")
    monkeypatch.setattr(cooc, "BLOCK_SKIP", "0")
    assert not cooc.block_skip_enabled()
    monkeypatch.setattr(cooc, "BLOCK_SKIP", "auto")
    assert cooc.block_skip_enabled()
    # The plan records the resolved policy (what describe()/--debug show).
    plan = cooc.dense_plan(1000, 500)
    assert plan.plane_bits == cooc.resolved_plane_bits()
    assert plan.fuse_verdict == cooc.fuse_verdict_enabled()
    assert plan.line_block and plan.l_pad % plan.line_block == 0
    d = plan.describe()
    assert d["n_blocks"] == plan.n_blocks and d["n_blocks_skipped"] == 0


def _planted_dense_inputs(rng, n_lines=2400, num_caps=300, zero_tile=True):
    """Membership with real containments, one dep tile confined to the
    first line block (all-zero later blocks), and one all-zero dep tile."""
    plan = cooc.dense_plan(n_lines, num_caps)
    l_pad, c_pad = plan.l_pad, plan.c_pad
    member = np.zeros((l_pad, c_pad), np.float32)
    member[:n_lines, :num_caps] = rng.random((n_lines, num_caps)) < 0.02
    for j in range(40):  # plant j < j+120 containments
        member[:, j] = 0
        rows = rng.choice(n_lines, 6, replace=False)
        member[rows, j] = 1
        member[rows, j + 120] = 1
    if zero_tile:
        # Dep tile [0, tile): confine EVERY capture of the tile to the first
        # line block, leaving later (dep-tile x line-block) pairs all-zero.
        kl = plan.line_block
        member[kl:, :plan.tile] = 0
    dep_count = member.sum(axis=0).astype(np.int64)
    cap_code = np.full(c_pad, 12, np.int64)
    cap_v1 = np.arange(c_pad, dtype=np.int64)
    cap_v2 = np.full(c_pad, -1, np.int64)
    return plan, member, dep_count, cap_code, cap_v1, cap_v2


def test_fused_sweep_matches_materialized_with_block_skip(monkeypatch):
    """Fused kernel + sub-tile skip schedule vs the materialized path, on a
    workload with an all-zero (dep-tile x line-block) pair: identical pairs,
    and the skip accounting shows up in the dense-plan record."""
    import jax.numpy as jnp

    rng = np.random.default_rng(11)
    (plan, member, dep_count, cap_code, cap_v1,
     cap_v2) = _planted_dense_inputs(rng)
    assert plan.n_line_blocks > 1, "workload must span several line blocks"
    m = jnp.asarray(member, jnp.bfloat16)

    monkeypatch.setattr(cooc, "FUSE_VERDICT", "0")
    d_a, r_a, s_a = cooc.discover_pairs_dense(
        m, dep_count, cap_code, cap_v1, cap_v2, 2, plan.num_caps,
        tile=plan.tile, starts=plan.dep_tile_starts)
    want = set(zip(d_a.tolist(), r_a.tolist()))
    assert want, "planted workload must produce CINDs"

    monkeypatch.setattr(cooc, "FUSE_VERDICT", "1")
    stats = {}
    d_b, r_b, s_b = cooc.discover_pairs_dense(
        m, dep_count, cap_code, cap_v1, cap_v2, 2, plan.num_caps,
        tile=plan.tile, starts=plan.dep_tile_starts,
        plan=cooc.dense_plan(plan.n_lines, plan.num_caps), stats=stats)
    assert set(zip(d_b.tolist(), r_b.tolist())) == want
    assert (s_b == np.asarray(dep_count)[d_b]).all()
    assert stats["n_blocks_skipped"] > 0
    assert stats["dense_plan"]["n_blocks_skipped"] > 0

    # Skip off: dense full-range schedule, still identical.
    monkeypatch.setattr(cooc, "BLOCK_SKIP", "0")
    stats = {}
    d_c, r_c, _ = cooc.discover_pairs_dense(
        m, dep_count, cap_code, cap_v1, cap_v2, 2, plan.num_caps,
        tile=plan.tile, starts=plan.dep_tile_starts,
        plan=cooc.dense_plan(plan.n_lines, plan.num_caps), stats=stats)
    assert set(zip(d_c.tolist(), r_c.tolist())) == want
    assert stats["n_blocks_skipped"] == 0


def test_all_zero_dep_tile_dropped_from_schedule(monkeypatch):
    """A dep tile whose captures occur in no line is dropped from the
    schedule on BOTH backends (its verdict block is provably empty)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(5)
    n_lines, num_caps = 600, 300
    plan = cooc.dense_plan(n_lines, num_caps)
    if len(plan.dep_tile_starts) < 2:
        pytest.skip("needs a multi-tile plan")
    member = np.zeros((plan.l_pad, plan.c_pad), np.float32)
    member[:n_lines, :num_caps] = rng.random((n_lines, num_caps)) < 0.05
    member[:, :plan.tile] = 0  # first dep tile: captures in no line
    dep_count = member.sum(axis=0).astype(np.int64)
    cap_code = np.full(plan.c_pad, 12, np.int64)
    cap_v1 = np.arange(plan.c_pad, dtype=np.int64)
    cap_v2 = np.full(plan.c_pad, -1, np.int64)
    m = jnp.asarray(member, jnp.bfloat16)

    monkeypatch.setattr(cooc, "BLOCK_SKIP", "0")
    d_a, r_a, _ = cooc.discover_pairs_dense(
        m, dep_count, cap_code, cap_v1, cap_v2, 2, num_caps,
        tile=plan.tile, starts=plan.dep_tile_starts)
    monkeypatch.setattr(cooc, "BLOCK_SKIP", "1")
    stats = {}
    d_b, r_b, _ = cooc.discover_pairs_dense(
        m, dep_count, cap_code, cap_v1, cap_v2, 2, num_caps,
        tile=plan.tile, starts=plan.dep_tile_starts, plan=plan, stats=stats)
    assert set(zip(d_a.tolist(), r_a.tolist())) == \
        set(zip(d_b.tolist(), r_b.tolist()))
    assert stats["dense_plan"]["n_tiles_data_skipped"] == 1
    assert stats["n_blocks_skipped"] >= plan.n_line_blocks


def test_strategies_invariant_on_planted_cinds(monkeypatch):
    """The fused kernel on the planted-CIND generator: every strategy's
    output is invariant to fusion, and the minimal sets agree across all
    four strategies under clean_implied (the minimality pre-filter must
    not change what the join would have produced)."""
    from rdfind_tpu.models import allatonce, approximate, late_bb, \
        small_to_large
    from rdfind_tpu.utils.synth import generate_planted_cinds

    triples, expected = generate_planted_cinds(2, 8, seed=3)
    strategies = {
        "allatonce": allatonce.discover,
        "small_to_large": small_to_large.discover,
        "approximate": approximate.discover,
        "late_bb": late_bb.discover,
    }
    base = {name: fn(triples, 8, clean_implied=True).to_rows()
            for name, fn in strategies.items()}
    minimal = set(base["allatonce"])
    assert len(minimal) >= 8  # one minimal CIND per rule x family
    assert all(set(rows) == minimal for rows in base.values())
    monkeypatch.setattr(cooc, "FUSE_VERDICT", "1")
    for name, fn in strategies.items():
        assert fn(triples, 8, clean_implied=True).to_rows() == base[name], \
            name


def test_discover_pairs_dense_schedule_matches_full(monkeypatch):
    """The scheduled tile sweep equals the full-range sweep bit for bit on a
    plan whose c_pad rounds past num_caps (schedule skips the padding tile)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(11)
    monkeypatch.setattr(cooc, "TILE_SCHEDULE", False)
    plan = cooc.dense_plan(200, 130)  # pow2: c_pad=256, tile=256
    monkeypatch.setattr(cooc, "TILE_SCHEDULE", True)
    tplan = cooc.dense_plan(200, 130)  # tile: c_pad=256, tile<=256
    member = np.zeros((plan.l_pad, plan.c_pad), np.float32)
    member[:200, :130] = rng.random((200, 130)) < 0.1
    dep_count = member.sum(axis=0).astype(np.int64)
    cap_code = np.full(plan.c_pad, 12, np.int64)
    cap_v1 = np.arange(plan.c_pad, dtype=np.int64)
    cap_v2 = np.full(plan.c_pad, -1, np.int64)
    m = jnp.asarray(member, jnp.bfloat16)

    d_a, r_a, _ = cooc.discover_pairs_dense(
        m, dep_count, cap_code, cap_v1, cap_v2, 2, 130, tile=plan.tile)
    mt = jnp.asarray(member[:tplan.l_pad, :tplan.c_pad], jnp.bfloat16)
    d_b, r_b, _ = cooc.discover_pairs_dense(
        mt, dep_count[:tplan.c_pad], cap_code[:tplan.c_pad],
        cap_v1[:tplan.c_pad], cap_v2[:tplan.c_pad], 2, 130,
        tile=tplan.tile, starts=tplan.dep_tile_starts)
    assert set(zip(d_a.tolist(), r_a.tolist())) == \
        set(zip(d_b.tolist(), r_b.tolist()))
