"""The join-line build keyed on the triple table: capture ids from the
frequent-condition sorts and line ids from one sort per projection column
equal what sorting the candidate rows gives (segments.masked_unique over
(code, v1, v2), segments.masked_dense_ids over the join values)."""

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rdfind_tpu.models import allatonce
from rdfind_tpu.ops import emission, frequency, segments
from rdfind_tpu.utils import synth

CAP, N_VALID = 256, 203  # padded rows, valid rows
HUGE = 1 << 20  # a support above every count: no capture forms


def _skewed_triples(seed):
    """Zipf-skewed ids over ranges that overlap across the three fields (so
    join lines mix projections), padded as upload_triples pads."""
    rng = np.random.default_rng(seed)
    t = np.stack([rng.zipf(1.4, N_VALID) % 37, rng.zipf(2.0, N_VALID) % 7 + 30,
                  rng.zipf(1.3, N_VALID) % 53], axis=1).astype(np.int32)
    return jnp.asarray(np.pad(t, ((0, CAP - N_VALID), (0, 0)),
                              constant_values=np.iinfo(np.int32).max))


@functools.partial(jax.jit,
                   static_argnames=("projections", "use_fc_filter", "use_ars"))
def _sorted_candidates(triples, n_valid, min_support, *, projections,
                       use_fc_filter, use_ars):
    """The build that sorts the candidate rows, as the stages made it before:
    emission under the filter's verdicts, then one sort to intern captures
    and one to number lines."""
    valid_t = jnp.arange(triples.shape[0], dtype=jnp.int32) < n_valid
    freq = (frequency.triple_frequencies(triples, valid_t, min_support,
                                         find_ar_implied=use_ars)
            if use_fc_filter else frequency.no_filter(valid_t))
    cands = emission.emit_join_candidates(triples, freq, projections)
    cap_cols, _, cap_id, num_caps = segments.masked_unique(
        [cands.code, cands.v1, cands.v2], cands.valid)
    line_gid, n_lines = segments.masked_dense_ids(cands.join_val, cands.valid)
    return (line_gid, cap_id, cands.valid, n_lines, *cap_cols, num_caps)


def _both(seed, projections, use_fc_filter, use_ars, min_support):
    """(parent-algorithm outputs, _stage_prepare outputs) as numpy."""
    args = (_skewed_triples(seed), jnp.int32(N_VALID), jnp.int32(min_support))
    kw = dict(projections=projections, use_fc_filter=use_fc_filter,
              use_ars=use_ars)
    want = _sorted_candidates(*args, **kw)
    got = list(allatonce._stage_prepare(*args, **kw))
    got[0] = emission.candidate_row_ids(got[0])
    return ([np.asarray(x) for x in want], [np.asarray(x) for x in got])


CASES = list(itertools.product([True, False], [True, False],
                               ["spo", "s", "po"], [1, 3, HUGE]))
CASE_IDS = [f"fc{int(f)}-ar{int(a)}-{p}-s{m}" for f, a, p, m in CASES]


@pytest.mark.parametrize("use_fc_filter,use_ars,projections,min_support",
                         CASES, ids=CASE_IDS)
def test_capture_ids_match_candidate_sort(use_fc_filter, use_ars, projections,
                                          min_support):
    want, got = _both(1, projections, use_fc_filter, use_ars, min_support)
    valid = want[2]
    np.testing.assert_array_equal(got[2], valid)
    assert int(got[7]) == int(want[7])  # num_caps
    np.testing.assert_array_equal(got[1][valid], want[1][valid])  # cap_id
    for g, w in zip(got[4:7], want[4:7]):  # cap_code, cap_v1, cap_v2
        np.testing.assert_array_equal(g, w)  # SENTINEL tails included
    if use_fc_filter and min_support == HUGE:
        assert int(got[7]) == 0
    else:
        assert int(got[7]) > 0


@pytest.mark.parametrize("use_fc_filter,use_ars,projections,min_support",
                         CASES, ids=CASE_IDS)
def test_line_ids_match_candidate_sort(use_fc_filter, use_ars, projections,
                                       min_support):
    want, got = _both(2, projections, use_fc_filter, use_ars, min_support)
    valid = want[2]
    assert int(got[3]) == int(want[3])  # n_lines
    np.testing.assert_array_equal(got[0][valid], want[0][valid])  # line_gid


def test_stage_prepare_outputs_match_on_a_graph():
    """All eight outputs on a planted graph at support 2, with association
    rules; line ids as each candidate row takes them; ids count only on
    valid rows, where the membership reads them."""
    t = synth.inject_cind_structure(
        synth.generate_triples(300, seed=5, n_predicates=6, n_entities=30),
        n_rules=3, ref_size=10, dep_size=6)
    n = t.shape[0]
    padded = allatonce.upload_triples(t)
    args = (padded, jnp.int32(n), jnp.int32(2))
    kw = dict(projections="spo", use_fc_filter=True, use_ars=True)
    want = [np.asarray(x) for x in _sorted_candidates(*args, **kw)]
    got = list(allatonce._stage_prepare(*args, **kw))
    assert got[0].shape == (3, padded.shape[0])
    got[0] = emission.candidate_row_ids(got[0])
    got = [np.asarray(x) for x in got]
    valid = want[2]
    assert valid.sum() > 0 and int(want[7]) > 0 and int(want[3]) > 0
    for i, (g, w) in enumerate(zip(got, want)):
        if i in (0, 1):  # line_gid, cap_id
            g, w = g[valid], w[valid]
        np.testing.assert_array_equal(g, w, err_msg=str(i))


@pytest.mark.parametrize("projections,rows", [("spo", 9), ("s", 4), ("po", 7)])
def test_join_sort_rows(projections, rows):
    """Six condition sorts and three join-value columns for spo, where
    sorting the candidate rows twice took 18 rows per triple."""
    assert emission.join_sort_rows(CAP, projections) == rows * CAP


def test_dense_discovery_records_join_sort_rows():
    t = synth.generate_triples(200, seed=9, n_predicates=5, n_entities=25)
    stats = {}
    allatonce.discover(t, 2, pair_backend="matmul", stats=stats)
    assert stats["join_sort_rows"] == 9 * segments.pow2_capacity(t.shape[0])


def _masked_keys(seed, n=300, n_cols=2):
    rng = np.random.default_rng(seed)
    cols = [rng.zipf(1.5, n).astype(np.int32) % 23 for _ in range(n_cols)]
    valid = rng.random(n) < 0.8
    return cols, valid


@pytest.mark.parametrize("n_cols", [1, 2])
@pytest.mark.parametrize("seed", range(3))
def test_masked_sorted_runs_match_numpy(seed, n_cols):
    """Row counts, sorted keys, run starts, counts in key order and a
    carried payload, against a plain numpy group-by."""
    cols, valid = _masked_keys(seed, n_cols=n_cols)
    payload = np.arange(300, dtype=np.int32) * 7
    runs = segments.masked_sorted_runs([jnp.asarray(c) for c in cols],
                                       jnp.asarray(valid),
                                       payload=(jnp.asarray(payload),))
    rows = np.stack(cols, axis=1)
    keys, inv, counts = np.unique(rows[valid], axis=0, return_inverse=True,
                                  return_counts=True)
    want = np.zeros(300, np.int64)
    want[valid] = counts[inv.reshape(-1)]
    np.testing.assert_array_equal(np.asarray(segments.masked_row_counts(
        [jnp.asarray(c) for c in cols], jnp.asarray(valid))), want)
    perm = np.asarray(runs.perm)
    assert sorted(perm.tolist()) == list(range(300))
    n_valid = int(valid.sum())
    assert valid[perm[:n_valid]].all() and not valid[perm[n_valid:]].any()
    sorted_rows = np.stack([np.asarray(k) for k in runs.keys], axis=1)
    np.testing.assert_array_equal(sorted_rows[:n_valid], rows[perm[:n_valid]])
    np.testing.assert_array_equal(sorted_rows[n_valid:], segments.SENTINEL)
    np.testing.assert_array_equal(np.asarray(runs.payload[0]), payload[perm])
    np.testing.assert_array_equal(np.asarray(runs.counts), want[perm])
    starts = np.asarray(runs.starts)
    np.testing.assert_array_equal(sorted_rows[starts], keys)


@pytest.mark.parametrize("seed", range(3))
def test_masked_dense_ids_match_numpy(seed):
    (col,), valid = _masked_keys(seed + 10, n_cols=1)
    ids, n_ids = segments.masked_dense_ids(jnp.asarray(col),
                                           jnp.asarray(valid))
    uniq, inv = np.unique(col[valid], return_inverse=True)
    assert int(n_ids) == uniq.size
    np.testing.assert_array_equal(np.asarray(ids)[valid], inv)


@pytest.mark.parametrize("seed", range(3))
def test_run_sums_unsort_and_compact(seed):
    rng = np.random.default_rng(seed)
    starts = rng.random(200) < 0.2
    starts[0] = True
    x = rng.integers(0, 5, 200).astype(np.int32)
    run = np.cumsum(starts) - 1
    want = np.bincount(run, weights=x).astype(np.int64)[run]
    got = segments.run_sums(jnp.asarray(starts), jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(got), want)
    perm = rng.permutation(200).astype(np.int32)
    (back,) = segments.unsort(jnp.asarray(perm), [jnp.asarray(x)])
    want_back = np.empty_like(x)
    want_back[perm] = x
    np.testing.assert_array_equal(np.asarray(back), want_back)
    keep = rng.random(200) < 0.3
    cols, n_kept = segments.compact([jnp.asarray(x), jnp.asarray(keep)],
                                    jnp.asarray(keep))
    assert int(n_kept) == keep.sum()
    order = np.argsort(~keep, kind="stable")
    np.testing.assert_array_equal(np.asarray(cols[0]), x[order])
    np.testing.assert_array_equal(np.asarray(cols[1]), keep[order])
