"""Multi-device golden tests on the 8-device CPU mesh (the minicluster analog)."""

import random

import numpy as np
import pytest

import jax

from rdfind_tpu.dictionary import intern_triples
from rdfind_tpu.models import allatonce, sharded
from rdfind_tpu.parallel.mesh import make_mesh
from rdfind_tpu.utils.synth import generate_triples


@pytest.fixture(scope="module")
def mesh8():
    assert len(jax.devices()) >= 8, "conftest should provide 8 CPU devices"
    return make_mesh(8)


def random_triples(rng, n, n_subj, n_pred, n_obj):
    return [
        (f"s{rng.randrange(n_subj)}", f"p{rng.randrange(n_pred)}",
         f"o{rng.randrange(n_obj)}")
        for _ in range(n)
    ]


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("min_support", [1, 3])
def test_sharded_matches_single_chip(mesh8, seed, min_support):
    rng = random.Random(seed)
    ids, _ = intern_triples(np.asarray(random_triples(rng, 90, 6, 3, 5), dtype=object))
    a = sharded.discover_sharded(ids, min_support, mesh=mesh8)
    b = allatonce.discover(ids, min_support)
    assert a.to_rows() == b.to_rows()


def test_sharded_synthetic_workload(mesh8):
    triples = generate_triples(300, seed=5, n_predicates=8, n_entities=32)
    a = sharded.discover_sharded(triples, 2, mesh=mesh8)
    b = allatonce.discover(triples, 2)
    assert a.to_rows() == b.to_rows()


@pytest.mark.slow
def test_sharded_device_counts(min_support=2):
    # The result must not depend on the mesh size.
    triples = generate_triples(150, seed=6, n_predicates=6, n_entities=24)
    want = allatonce.discover(triples, min_support).to_rows()
    for d in (1, 2, 4, 8):
        mesh = make_mesh(d)
        got = sharded.discover_sharded(triples, min_support, mesh=mesh).to_rows()
        assert got == want, f"mismatch on {d}-device mesh"


def test_sharded_projections(mesh8):
    triples = generate_triples(150, seed=8, n_predicates=6, n_entities=24)
    for proj in ("s", "so"):
        a = sharded.discover_sharded(triples, 2, mesh=mesh8, projections=proj)
        b = allatonce.discover(triples, 2, projections=proj)
        assert a.to_rows() == b.to_rows()


def test_sharded_empty(mesh8):
    out = sharded.discover_sharded(np.zeros((0, 3), np.int32), 2, mesh=mesh8)
    assert len(out) == 0


def skewed_triples(rng, n_hot, n_cold):
    """One scorching join value (o0 shared by n_hot distinct (s,p) combos) plus a
    cold tail — the power-law shape the skew engine exists for."""
    rows = [(f"s{i}", f"p{i % 5}", "o0") for i in range(n_hot)]
    rows += [(f"s{rng.randrange(40)}", f"p{rng.randrange(5)}",
              f"o{1 + rng.randrange(30)}") for _ in range(n_cold)]
    rng.shuffle(rows)
    return rows


@pytest.mark.parametrize("min_support", [1, 3])
def test_skew_split_matches_single_chip(mesh8, min_support):
    rng = random.Random(11)
    ids, _ = intern_triples(
        np.asarray(skewed_triples(rng, 120, 200), dtype=object))
    stats = {}
    a = sharded.discover_sharded(ids, min_support, mesh=mesh8, stats=stats)
    b = allatonce.discover(ids, min_support)
    assert a.to_rows() == b.to_rows()
    # The hot line must actually have been routed through the split path.
    assert stats["n_giant_lines"] >= 1
    assert stats["n_giant_pairs"] > 0


def test_tiny_input_small_mesh():
    # Regression: cap_giant larger than the whole row buffer must not break the
    # gather slicing (4 triples on 1-/2-device meshes).
    ids, _ = intern_triples(np.asarray(
        [("s1", "p1", "o1"), ("s2", "p1", "o1"), ("s1", "p2", "o2"),
         ("s2", "p2", "o2")], dtype=object))
    want = allatonce.discover(ids, 1).to_rows()
    for d in (1, 2):
        got = sharded.discover_sharded(ids, 1, mesh=make_mesh(d)).to_rows()
        assert got == want


@pytest.mark.slow
def test_skew_split_device_invariance(mesh8):
    rng = random.Random(12)
    ids, _ = intern_triples(
        np.asarray(skewed_triples(rng, 80, 120), dtype=object))
    want = allatonce.discover(ids, 2).to_rows()
    for d in (1, 4, 8):
        got = sharded.discover_sharded(ids, 2, mesh=make_mesh(d)).to_rows()
        assert got == want, f"mismatch on {d}-device mesh"


# ---------------------------------------------------------------------------
# Distributed frequency filter + sharded SmallToLarge (round 2).
# ---------------------------------------------------------------------------

from rdfind_tpu.models import small_to_large  # noqa: E402


@pytest.mark.parametrize("use_fis,use_ars",
                         [(False, False), (True, False), (True, True)])
def test_sharded_fis_ars_matches_single_chip(mesh8, use_fis, use_ars):
    """The distributed frequency filter + AR suppression must be output-
    identical to the single-device AllAtOnce with the same flags."""
    triples = generate_triples(300, seed=9, n_predicates=6, n_entities=24)
    a = sharded.discover_sharded(triples, 2, mesh=mesh8, use_fis=use_fis,
                                 use_ars=use_ars)
    b = allatonce.discover(triples, 2, use_frequent_condition_filter=use_fis,
                           use_association_rules=use_ars)
    assert a.to_rows() == b.to_rows()


@pytest.mark.parametrize("min_support", [1, 3])
@pytest.mark.parametrize("seed", range(2))
def test_sharded_s2l_matches_single_chip(mesh8, seed, min_support):
    """Sharded S2L (default strategy distributed) == single-device S2L."""
    rng = random.Random(seed)
    ids, _ = intern_triples(
        np.asarray(random_triples(rng, 90, 6, 3, 5), dtype=object))
    a = sharded.discover_sharded_s2l(ids, min_support, mesh=mesh8)
    b = small_to_large.discover(ids, min_support)
    assert a.to_rows() == b.to_rows()


@pytest.mark.parametrize("use_fis,use_ars",
                         [(False, False), (True, False), (True, True)])
def test_sharded_s2l_flags(mesh8, use_fis, use_ars):
    triples = generate_triples(250, seed=11, n_predicates=6, n_entities=20)
    a = sharded.discover_sharded_s2l(triples, 2, mesh=mesh8, use_fis=use_fis,
                                     use_ars=use_ars)
    b = small_to_large.discover(triples, 2,
                                use_frequent_condition_filter=use_fis,
                                use_association_rules=use_ars)
    assert a.to_rows() == b.to_rows()


def test_sharded_s2l_skew_split(mesh8, s2l_pair_phase):
    """A hot join value must drive the S2L giant-line path and stay correct."""
    triples = generate_triples(150, seed=13, n_predicates=5, n_entities=16)
    hot = np.stack([np.arange(100, 160, dtype=np.int32),
                    np.arange(60, dtype=np.int32) % 3 + 500,
                    np.full(60, 999, dtype=np.int32)], axis=1)
    triples = np.concatenate([np.asarray(triples, np.int32), hot])
    stats = {}
    a = sharded.discover_sharded_s2l(triples, 2, mesh=mesh8, stats=stats)
    b = small_to_large.discover(triples, 2)
    assert a.to_rows() == b.to_rows()
    assert stats["n_giant_lines"] >= 1  # the split path actually fired


@pytest.mark.slow
def test_sharded_s2l_device_invariance():
    triples = generate_triples(120, seed=17, n_predicates=4, n_entities=12)
    want = small_to_large.discover(triples, 2).to_rows()
    for d in (1, 2, 4, 8):
        got = sharded.discover_sharded_s2l(triples, 2, mesh=make_mesh(d)).to_rows()
        assert got == want, f"mismatch at {d} devices"


def test_global_row_counts_roundtrip(mesh8):
    """exchange.global_row_counts must equal a host group-count, per row."""
    import functools

    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from rdfind_tpu.parallel import exchange
    from rdfind_tpu.parallel.mesh import AXIS, shard_map

    rng = np.random.default_rng(0)
    n = 256  # 32 rows/device
    keys = rng.integers(0, 37, n).astype(np.int32)
    valid = rng.random(n) < 0.9

    def step(k, v):
        c, ovf = exchange.global_row_counts([k], v, AXIS, 64, seed=3)
        return c, jnp.full(1, ovf, jnp.int32)

    fn = jax.jit(shard_map(
        step, mesh=mesh8, in_specs=(P(AXIS), P(AXIS)),
        out_specs=(P(AXIS), P(AXIS)), check_vma=False))
    counts, ovf = fn(jnp.asarray(keys), jnp.asarray(valid))
    assert int(np.asarray(ovf).sum()) == 0
    want = np.zeros(n, np.int64)
    uniq, inv = np.unique(keys[valid], return_inverse=True)
    cnt = np.bincount(inv)
    lut = dict(zip(uniq.tolist(), cnt.tolist()))
    for i in range(n):
        want[i] = lut.get(int(keys[i]), 0) if valid[i] else 0
    assert np.array_equal(np.asarray(counts), want)


def test_capacity_plan_scales_with_load(mesh8):
    """Planned per-device buffers must track measured loads (~N/D + skew), not
    the old 'everything lands on one device' worst cases (VERDICT r1 weak #3).
    """
    # Sized to share the floored per-device block (t_loc = T_LOC_FLOOR) with
    # the rest of the suite, so the pipeline compiles are reused.
    triples = generate_triples(800, seed=21, n_predicates=8, n_entities=64)
    # One hot join value so the plan includes real skew.
    hot = np.stack([np.arange(100, 160, dtype=np.int32),
                    np.arange(60, dtype=np.int32) % 4 + 900,
                    np.full(60, 7777, dtype=np.int32)], axis=1)
    triples = np.concatenate([np.asarray(triples, np.int32), hot])
    stats = {}
    a = sharded.discover_sharded(triples, 2, mesh=mesh8, stats=stats)
    b = allatonce.discover(triples, 2)
    assert a.to_rows() == b.to_rows()

    caps = stats["planned_caps"]
    num_dev = 8
    n = triples.shape[0]
    t_loc = max(sharded.T_LOC_FLOOR,
                1 << (-(-n // num_dev) - 1).bit_length())
    # The old worst-case formulas (sharded.py r1: cap_b = pow2(D*cap_a),
    # cap_p = pow2(4*D*cap_a)) for this workload:
    def pow2(x):
        return 1 << max(0, int(x) - 1).bit_length() if x > 1 else 1
    old_cap_a = pow2(9 * t_loc)
    old_cap_b = pow2(num_dev * old_cap_a)
    old_cap_p = pow2(4 * num_dev * old_cap_a)
    # Planned row exchanges must be far below the worst cases...
    assert caps["exchange_a"] <= old_cap_a // 2
    assert caps["exchange_b"] <= old_cap_b // 8
    assert caps["pairs"] <= old_cap_p // 2
    # ...and within a constant factor of the per-device share of the real load
    # (pow2 bucketing + 12.5% margin => <= 4x the measured maximum, which is
    # itself >= share/D of the global row count).
    assert caps["exchange_b"] <= 4 * (9 * n // num_dev + 80)


# ---------------------------------------------------------------------------
# Sharded approximate strategies (2: ApproximateAllAtOnce, 3: LateBB).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(2))
@pytest.mark.parametrize("min_support", [1, 3])
def test_sharded_approx_matches_single_chip(mesh8, seed, min_support):
    from rdfind_tpu.models import approximate
    rng = random.Random(seed + 500)
    ids, _ = intern_triples(
        np.asarray(random_triples(rng, 120, 6, 3, 5), dtype=object))
    stats = {}
    a = sharded.discover_sharded_approx(ids, min_support, mesh=mesh8,
                                        stats=stats)
    b = approximate.discover(ids, min_support)
    assert a.to_rows() == b.to_rows()
    assert stats["n_sketch_candidates"] > 0


def test_sharded_late_bb_matches_single_chip(mesh8):
    from rdfind_tpu.models import late_bb
    rng = random.Random(77)
    ids, _ = intern_triples(
        np.asarray(random_triples(rng, 120, 6, 3, 5), dtype=object))
    a = sharded.discover_sharded_late_bb(ids, 2, mesh=mesh8)
    b = late_bb.discover(ids, 2)
    assert a.to_rows() == b.to_rows()


def test_sharded_approx_flags(mesh8):
    from rdfind_tpu.models import approximate
    rng = random.Random(78)
    ids, _ = intern_triples(
        np.asarray(random_triples(rng, 100, 5, 3, 4), dtype=object))
    a = sharded.discover_sharded_approx(ids, 2, mesh=mesh8, use_fis=True,
                                        use_ars=True, clean_implied=True)
    b = approximate.discover(ids, 2, use_frequent_condition_filter=True,
                             use_association_rules=True, clean_implied=True)
    assert a.to_rows() == b.to_rows()


def midskew_triples(n_groups=32, group_len=7):
    """Many mid-sized hot join lines (above-average load, below every giant
    threshold) + a cold tail of 3-capture lines: the shape where pure hash
    placement can pile hot lines onto one device while the split engine —
    which only fires on giant lines — never helps."""
    rows = []
    s = 0
    for g in range(n_groups):
        for _ in range(group_len):
            rows.append((s, 5000 + g, 10000 + g))
            s += 1
    return np.asarray(rows, np.int32)


def test_load_aware_placement(mesh8):
    triples = midskew_triples()
    stats = {}
    a = sharded.discover_sharded(triples, 2, mesh=mesh8, stats=stats)
    b = allatonce.discover(triples, 2)
    assert a.to_rows() == b.to_rows()
    # These lines are hot but NOT giant: the greedy placement, not the split
    # engine, is what handles them.
    assert stats["n_giant_lines"] == 0
    reb = stats["rebalance"]
    assert reb["hot_lines"] >= 2 * 32  # one o-line and one p-line per group
    assert reb["moved_lines"] > 0
    assert reb["load_max_over_mean_planned"] <= 2.0
    assert (reb["load_max_over_mean_planned"]
            < reb["load_max_over_mean_before"])


def test_load_aware_placement_s2l(mesh8):
    """The default strategy shares the pipeline, so placement must not change
    its output either."""
    from rdfind_tpu.models import small_to_large
    triples = midskew_triples(n_groups=16)
    stats = {}
    a = sharded.discover_sharded_s2l(triples, 2, mesh=mesh8, stats=stats)
    b = small_to_large.discover(triples, 2)
    assert a.to_rows() == b.to_rows()


def test_route_scattered_valid(mesh8):
    """route() must deliver rows whose valid mask is NOT a compacted prefix
    (regression: the validity lane was permuted twice, which only worked by
    accident for prefix masks)."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from rdfind_tpu.parallel import exchange
    from rdfind_tpu.parallel.mesh import AXIS, shard_map

    n, cap = 64, 16

    def step(col, valid):
        bucket = col % 8
        out, out_valid, ovf = exchange.bucket_exchange(
            [col], valid, bucket, AXIS, cap)
        got = jnp.where(out_valid, out[0], 0).sum()
        return jnp.full(1, got, jnp.int32), jnp.full(1, ovf, jnp.int32)

    rng = np.random.default_rng(3)
    col = rng.integers(0, 1000, size=8 * n).astype(np.int32)
    valid = rng.random(8 * n) < 0.3  # scattered, sparse
    got, ovf = shard_map(
        step, mesh=mesh8, in_specs=(P(AXIS), P(AXIS)), out_specs=P(AXIS),
        check_vma=False)(jnp.asarray(col), jnp.asarray(valid))
    assert int(np.asarray(ovf)[0]) == 0
    assert int(np.asarray(got).sum()) == int(col[valid].sum())


def test_skew_policy_range_slice(mesh8):
    """Split strategy 2 (contiguous range-slice ownership) must produce the
    same output as the default hash-slice on a workload where the split
    engine provably fires."""
    rng = random.Random(11)
    ids, _ = intern_triples(
        np.asarray(skewed_triples(rng, 120, 200), dtype=object))
    want = allatonce.discover(ids, 2).to_rows()
    stats = {}
    a = sharded.discover_sharded(ids, 2, mesh=mesh8, stats=stats,
                                 skew=sharded.SkewPolicy(strategy=2))
    assert a.to_rows() == want
    assert stats["n_giant_lines"] >= 1  # the split path really ran


def test_skew_policy_max_load(mesh8):
    """--rebalance-max-load forces mid-sized lines through the split path."""
    triples = midskew_triples()
    base_stats = {}
    want = sharded.discover_sharded(triples, 2, mesh=mesh8,
                                    stats=base_stats).to_rows()
    assert base_stats["n_giant_lines"] == 0  # not giant under defaults
    stats = {}
    a = sharded.discover_sharded(
        triples, 2, mesh=mesh8, stats=stats,
        skew=sharded.SkewPolicy(max_load=100.0))
    assert a.to_rows() == want
    assert stats["n_giant_lines"] > 0  # max_load made them split


def test_no_combinable_join(mesh8):
    """The --no-combinable-join ablation (raw candidate rows into exchange A)
    must not change the output."""
    triples = generate_triples(150, seed=8, n_predicates=6, n_entities=24)
    want = sharded.discover_sharded(triples, 2, mesh=mesh8).to_rows()
    got = sharded.discover_sharded(triples, 2, mesh=mesh8,
                                   combine=False).to_rows()
    assert got == want


def test_skew_policy_validation():
    with pytest.raises(ValueError, match="rebalance strategy"):
        sharded.SkewPolicy(strategy=3)


def test_host_capture_budget_guard(mesh8, monkeypatch, s2l_pair_phase):
    """The host-side lattice pull fails loudly past its stated budget."""
    import os
    monkeypatch.setitem(os.environ, "RDFIND_HOST_CAPTURES_BUDGET", "4")
    triples = generate_triples(100, seed=2, n_predicates=4, n_entities=16)
    with pytest.raises(ValueError, match="lattice budget"):
        sharded.discover_sharded_s2l(triples, 2, mesh=mesh8)


def _make_preshard(ids, mesh):
    """Single-process preshard via the production layout helper (the same
    contiguous split + per-device valid prefixes sharded_ingest assembles)."""
    from rdfind_tpu.parallel.mesh import make_global

    padded, n_valid, _ = sharded._shard_triples(np.asarray(ids, np.int32),
                                                mesh.devices.size)
    return make_global(padded, mesh), make_global(n_valid, mesh)


def test_preshard_use_ars_matches_host_mining(mesh8):
    """Distributed AR mining over a preshard == host mining: same rule table,
    same AR-filtered CINDs (the lifted --sharded-ingest --use-ars path)."""
    from rdfind_tpu.ops import frequency

    rng = random.Random(31)
    rows = random_triples(rng, 80, 5, 3, 4)
    rows += [("s_ar", "p_ar", f"o{i}") for i in range(4)] * 3  # a real rule
    ids, _ = intern_triples(np.asarray(rows, dtype=object))
    g_triples, g_valid = _make_preshard(ids, mesh8)

    want_rules = frequency.mine_association_rules(ids, 2)
    got_rules = sharded.mine_ars_sharded(g_triples, g_valid, 2, mesh8)
    to_set = lambda cols: {tuple(int(c[i]) for c in cols)
                           for i in range(len(cols[0]))}
    assert to_set(got_rules) == to_set(want_rules)
    assert len(want_rules[0]) > 0  # the fixture really mines rules

    for fn in (sharded.discover_sharded, sharded.discover_sharded_s2l,
               sharded.discover_sharded_approx,
               sharded.discover_sharded_late_bb):
        want = fn(ids, 2, mesh=mesh8, use_fis=True, use_ars=True).to_rows()
        got = fn(None, 2, mesh=mesh8, use_fis=True, use_ars=True,
                 preshard=(g_triples, g_valid)).to_rows()
        assert got == want, fn.__name__


def test_join_histogram_sharded_matches_host(mesh8):
    from rdfind_tpu.runtime.driver import _join_histogram

    triples = generate_triples(200, seed=12, n_predicates=6, n_entities=24)
    ids = np.asarray(triples, np.int32)
    g_triples, g_valid = _make_preshard(ids, mesh8)
    got = sharded.join_histogram_sharded(g_triples, g_valid, "spo", mesh8)
    want = _join_histogram(ids, "spo")
    assert got == want


def test_sharded_multipass_pair_phase(mesh8, monkeypatch, s2l_pair_phase):
    """A tiny pair-row budget must force dep-slice streaming passes (the
    bounded-memory pair phase) on BOTH strategies, with identical output."""
    triples = generate_triples(300, seed=21, n_predicates=8, n_entities=32)
    # 2^13 rows => 2-3 passes (enough to exercise slicing without tens of
    # per-pass dispatches dominating the fast tier).
    monkeypatch.setattr(sharded, "PAIR_ROW_BUDGET", 1 << 13)
    s0, s1 = {}, {}
    a = sharded.discover_sharded(triples, 2, mesh=mesh8, stats=s0)
    b = sharded.discover_sharded_s2l(triples, 2, mesh=mesh8, stats=s1)
    assert s0["n_pair_passes"] > 1
    assert s1["n_pair_passes"] > 1
    want = allatonce.discover(triples, 2)
    assert a.to_rows() == want.to_rows()
    assert b.to_rows() == small_to_large.discover(triples, 2).to_rows()


@pytest.mark.slow
@pytest.mark.parametrize("seed", [31, 37, 41])
def test_sharded_multipass_fuzz(mesh8, monkeypatch, seed):
    """Streaming passes stay exact across random workloads (slow tier):
    every strategy-0 run with a tiny budget must equal the single-chip
    oracle regardless of how the dep slices cut the capture space."""
    rng = random.Random(seed)
    ids, _ = intern_triples(np.asarray(
        random_triples(rng, 250, 10, 4, 8), dtype=object))
    monkeypatch.setattr(sharded, "PAIR_ROW_BUDGET", 1 << 12)
    s: dict = {}
    a = sharded.discover_sharded(ids, 2, mesh=mesh8, stats=s)
    assert s["n_pair_passes"] > 1
    assert a.to_rows() == allatonce.discover(ids, 2).to_rows()
