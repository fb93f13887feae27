"""SmallToLarge traversal strategy (the reference's default, id 1).

Walks the CIND lattice level by level — 1/1 overlaps -> 1/1 CINDs -> 1/2 -> 2/1 ->
2/2 — generating candidates for each level from the previous one and verifying only
those, instead of materializing every co-occurrence pair at once (AllAtOnce).
Mirrors plan/SmallToLargeTraversalStrategy.scala:38-171 with these mappings:

  * overlap/evidence extraction + MultiunionOverlapCandidates  ->  masked, chunked
    co-occurrence pair counting on device (ops/pairs.py rotations), restricted per
    level to (dep-family x ref-family) captures;
  * candidate Bloom filters between levels (:381-401 etc.)     ->  exact sorted-
    array candidate sets, semi-joined on the host after per-chunk dedup (prunes a
    superset of what the BF prunes; no false positives to re-verify);
  * Generate{UnaryBinary,BinaryUnary,BinaryBinary}CindCandidates and
    InferDoubleSingleCinds group-reduces                        ->  vectorized
    within-group pair emission over numpy arrays (same rotation layout);
  * the inferred-2/1 frequency join against triple-count-based frequent binary
    conditions (:534-548, an over-approximation of capture support)  ->  exact
    capture-support test via the always-on capture filter — output-neutral, prunes
    strictly more.

Output semantics are reference-faithful: the RAW result keeps only 2/1 CINDs whose
unary dep subcaptures are both proper overlaps of the ref (minimal 2/1s,
GenerateBinaryUnaryCindCandidates.scala:23-57) and 2/2 CINDs not implied by a 1/2
CIND, so raw S2L output is a subset of raw AllAtOnce output; with clean_implied
both strategies produce the identical minimal CIND set.  Exception, inherited from
the reference: with use_association_rules the AR filter runs on the 1/1 CINDs
BEFORE they seed the 1/2 / 2/1-inference / 2/2 generation
(SmallToLargeTraversalStrategy.scala:79-86), so higher-family CINDs whose only
generation path went through an AR-implied 1/1 CIND are missing versus AllAtOnce
even under clean_implied.  One deliberate divergence:
the reference's PruneNonMinimalDoubleDoubleCindCandidates.scala:42-66 only ever
tests the FIRST 1/2 CIND of each group (a tail-recursion bound bug), making its raw
2/2 output depend on Flink's nondeterministic group order; we implement the
documented intent (prune against ALL 1/2 CINDs), which is deterministic and
converges to the same clean_implied result.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import conditions as cc
from ..data import NO_VALUE, CindTable
from ..obs import datastats, integrity, metrics, tracer
from ..ops import cooc as cooc_ops
from ..ops import emission, frequency, minimality, pairs, segments, sketch
from ..runtime import dispatch, faults
from . import allatonce

SENTINEL = segments.SENTINEL


# ---------------------------------------------------------------------------
# Device stage: masked pair counting (the per-level evidence extraction).
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("capacity", "balanced"))
def _stage_pair_counts_masked(line_cap, dep_f, ref_f, pos, length, start_idx, *,
                              capacity, balanced=False):
    """One chunk of (dep-flagged x ref-flagged) co-occurrence pairs, deduped+counted.

    Like allatonce._stage_pair_counts but pairs survive only when the dependent row
    is dep-flagged and the partner row is ref-flagged — the per-level restriction
    that replaces the reference's family-specific Create*/Extract* operators.
    """
    row, partner, pair_valid = pairs.emit_pair_indices(pos, length, start_idx,
                                                       capacity,
                                                       balanced=balanced)
    pair_valid = pair_valid & dep_f[row] & ref_f[partner]
    dep = jnp.where(pair_valid, line_cap[row], SENTINEL)
    ref = jnp.where(pair_valid, line_cap[partner], SENTINEL)
    perm = segments.lexsort([dep, ref])
    ds, rs, vs = dep[perm], ref[perm], pair_valid[perm]
    starts = segments.run_starts([ds, rs]) & vs
    gid = jnp.cumsum(starts).astype(jnp.int32) - 1
    cnt = jax.ops.segment_sum(vs.astype(jnp.int32), gid, num_segments=capacity)[gid]
    (d_out, r_out, c_out), n_out = segments.compact([ds, rs, cnt], starts)
    return d_out, r_out, c_out, n_out


def _iter_chunk_pairs(line_val_h, line_cap_h, dep_ok, ref_ok, budget,
                      stats, stat_key, balanced=False):
    """Yield per-chunk partial (dep, ref, cnt) host arrays for flagged pairs.

    The shared chunk loop under both the exact merge (_chunked_cooc) and the
    two-round half-approximate 1/1 evaluation.  Rows flagged for neither side
    are dropped before the quadratic emission; the stat accounting (pair slots
    materialized per line) accumulates into stats[stat_key].

    Pipelined: chunk k+1's jitted pair program is dispatched BEFORE chunk k's
    outputs are pulled (one batched device_get per chunk, staged async), so
    the host-side merge of chunk k overlaps chunk k+1's device compute — the
    same dispatch discipline as the sharded pass executor, at the cost of one
    extra chunk's buffers in flight.  RDFIND_SYNC_PASSES=1 restores the
    serial pull-then-dispatch schedule (bit-identical output).
    """
    row_keep = dep_ok[line_cap_h] | ref_ok[line_cap_h]
    lv, lc = line_val_h[row_keep], line_cap_h[row_keep]
    n = lv.shape[0]
    if n == 0:
        return
    dep_f_h = dep_ok[lc]
    ref_f_h = ref_ok[lc]

    starts = np.empty(n, bool)
    starts[0] = True
    starts[1:] = lv[1:] != lv[:-1]
    line_start_rows = np.flatnonzero(starts)
    line_lens = np.diff(np.append(line_start_rows, n)).astype(np.int64)
    pairs_per_line = line_lens * (line_lens - 1)
    if balanced:
        pairs_per_line //= 2  # each unordered pair materializes once
    if stats is not None:
        metrics.counter_add(stats, stat_key, int(pairs_per_line.sum()))
        metrics.counter_add(stats, "total_pairs",
                            int(pairs_per_line.sum()))
    if int(pairs_per_line.sum()) == 0:
        return
    pos_h = (np.arange(n, dtype=np.int64)
             - np.repeat(line_start_rows, line_lens)).astype(np.int32)
    len_h = np.repeat(line_lens, line_lens).astype(np.int32)

    bounds = allatonce._chunk_boundaries(pairs_per_line, budget)
    pad = allatonce._pad_np
    pipelined = not dispatch.sync_passes_forced()

    def pull(chunk):
        # ONE batched round trip, through the host_pull fault gate + bounded
        # backoff retry (pure read: re-pulling a chunk is always safe).
        d, r, c, n_out = faults.guarded_pull(lambda: jax.device_get(chunk))
        m = int(n_out)
        return (d[:m].astype(np.int64), r[:m].astype(np.int64),
                c[:m].astype(np.int64))

    pend = None
    for bi in range(len(bounds) - 1):
        lo_line, hi_line = bounds[bi], bounds[bi + 1]
        if lo_line == hi_line:
            continue
        rs = int(line_start_rows[lo_line])
        re = int(line_start_rows[hi_line]) if hi_line < len(line_start_rows) else n
        chunk_pairs = int(pairs_per_line[lo_line:hi_line].sum())
        if chunk_pairs == 0:
            continue
        row_cap = segments.pow2_capacity(re - rs)
        pair_cap = segments.pow2_capacity(chunk_pairs)
        chunk = _stage_pair_counts_masked(
            jnp.asarray(pad(lc[rs:re], row_cap, SENTINEL)),
            jnp.asarray(pad(dep_f_h[rs:re], row_cap, False)),
            jnp.asarray(pad(ref_f_h[rs:re], row_cap, False)),
            jnp.asarray(pad(pos_h[rs:re], row_cap, 0)),
            jnp.asarray(pad(len_h[rs:re], row_cap, 1)),
            jnp.asarray(pad(
                (np.arange(rs, re, dtype=np.int32) - pos_h[rs:re]) - rs, row_cap, 0)),
            capacity=pair_cap, balanced=balanced)
        dispatch.stage_to_host(chunk)
        if not pipelined:
            yield pull(chunk)
            continue
        if pend is not None:
            yield pull(pend)
        pend = chunk
    if pend is not None:
        yield pull(pend)


def _merge_pair_parts(parts):
    """Exact cross-chunk merge (the reduceGroup side of IntersectCindCandidates)."""
    if not parts:
        z = np.zeros(0, np.int64)
        return z, z, z
    d = np.concatenate([p[0] for p in parts])
    r = np.concatenate([p[1] for p in parts])
    c = np.concatenate([p[2] for p in parts])
    key = (d << 32) | r
    uniq, inv = np.unique(key, return_inverse=True)
    cnt = np.bincount(inv, weights=c, minlength=len(uniq)).astype(np.int64)
    return (uniq >> 32), (uniq & 0xFFFFFFFF), cnt


def _chunked_cooc(line_val_h, line_cap_h, dep_ok, ref_ok, budget, stats, stat_key,
                  balanced=False):
    """Global (dep, ref) -> co-occurrence counts for flagged capture pairs.

    line_val_h/line_cap_h: host arrays of valid join-line rows sorted by (value,
    capture id).  dep_ok/ref_ok: per-capture-id participation flags.  Rows flagged
    for neither side are dropped before the quadratic emission — THE saving of this
    strategy over AllAtOnce.  Returns merged host arrays (dep, ref, cnt).

    balanced=True halves the materialized 1/1 emission (each unordered pair
    once, ops/pairs.py rotation ownership) and symmetrizes the merged counts;
    only valid when dep_ok == ref_ok (the 1/1 level).
    """
    d, r, c = _merge_pair_parts(list(_iter_chunk_pairs(
        line_val_h, line_cap_h, dep_ok, ref_ok, budget, stats, stat_key,
        balanced=balanced)))
    if not balanced or d.size == 0:
        return d, r, c
    # Fold by unordered key (ownership is positional, so a capture pair can be
    # owned in either direction across lines), then emit both directions.
    lo = np.minimum(d, r)
    hi = np.maximum(d, r)
    ukey = (lo << 32) | hi
    uniq, inv = np.unique(ukey, return_inverse=True)
    cnt = np.bincount(inv, weights=c, minlength=len(uniq)).astype(np.int64)
    ld, lr = uniq >> 32, uniq & 0xFFFFFFFF
    return (np.concatenate([ld, lr]), np.concatenate([lr, ld]),
            np.concatenate([cnt, cnt]))


def _sbf_cap(sbf_bits: int) -> int:
    """Saturation value of an `sbf_bits`-wide spectral counter (clamped to the
    count-min implementation maximum) — shared by the upfront guard and the
    sketch build."""
    return min((1 << max(1, sbf_bits)) - 1, sketch.MAX_COUNT_MIN_CAP)


def _pair_hash32(key64: np.ndarray) -> np.ndarray:
    """int64 pair keys -> well-mixed non-negative int32 count-min keys."""
    h = (key64.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)) >> np.uint64(32)
    return (h & np.uint64(0x7FFFFFFF)).astype(np.int32)


def _half_approx_cooc_11(line_val_h, line_cap_h, dep_ok, ref_ok, budget, stats,
                         min_support, explicit_threshold, sbf_bits, sbf_width):
    """Two-round half-approximate 1/1 overlap evaluation.

    The memory-bounded analog of the reference's spectral-Bloom round
    (plan/SmallToLargeTraversalStrategy.scala:178-260 with
    EvaluateHalfApproximateOverlapSets.scala:33-112): round 1 keeps at most
    `explicit_threshold` exact (dep, ref) counters per dependent and spills the
    tail into a count-min sketch (ops/sketch.py — the SpectralBloomFilter
    analog, `sbf_bits` per counter saturating, `sbf_width` counters).  Each
    explicit pair is then classified: exact (no sketch contribution), unknown
    (needs round 2), or infrequent (upper bound < min_support — dropped).
    Round 2 re-scans the join lines restricted to dependents with any spilled
    or unknown pair and filters partial rows by the sketch upper bound before
    the exact merge — bounding the merged-pair volume that the exact
    evaluation would materialize all at once.

    Output (dep, ref, cnt) contains exactly the pairs with cnt >= min_support,
    with exact counts: a pair below min_support can be neither a 1/1 CIND
    (cnt == |dep| >= min_support) nor a proper overlap, so the result is
    output-equivalent to the exact path for every downstream consumer.
    Sketch collisions only enlarge round 2, never change the output.

    This host-side round is single-device chunked-backend only.  Sharded runs
    (--dop > 1, any strategy verifying through models/sharded) have their own
    distributed descendant — RDFIND_SHARDED_HALF_APPROX=1 builds per-device
    count-min partial tables over the same pair stream, all-reduces them with
    a saturating psum (exchange.sketch_allreduce, bit-identical to host
    merge_count_min by the saturation lemma in ops/sketch.py), and applies the
    round-2 cut before exchange C — same soundness argument as above, same
    bit-identical-output contract.
    """
    cap = _sbf_cap(sbf_bits)
    threshold = max(0, int(explicit_threshold))

    # --- Round 1: bounded explicit store + count-min tail.
    exp_keys = np.zeros(0, np.int64)   # sorted (dep<<32)|ref
    exp_cnt = np.zeros(0, np.int64)
    exp_per_dep: dict[int, int] = {}
    spilled_deps: set[int] = set()
    cm_table = np.zeros(sbf_width, np.int32)
    n_spilled = 0
    def _match_explicit(key):
        """(hit mask, clamped positions) of `key` in the sorted explicit store."""
        if len(exp_keys) == 0:
            return np.zeros(len(key), bool), np.zeros(len(key), np.int64)
        pos = np.minimum(np.searchsorted(exp_keys, key), len(exp_keys) - 1)
        return exp_keys[pos] == key, pos

    for d, r, c in _iter_chunk_pairs(line_val_h, line_cap_h, dep_ok, ref_ok,
                                     budget, stats, "pairs_11"):
        key = (d << 32) | r
        hit, pos_c = _match_explicit(key)
        # Existing explicit entries accumulate exactly (merge semantics of
        # MultiunionHalfApproximateOverlapCandidates: explicit counts sum).
        np.add.at(exp_cnt, pos_c[hit], c[hit])
        # New keys: admit up to the per-dep budget, spill the rest.
        new_d, new_key, new_c = d[~hit], key[~hit], c[~hit]
        if new_key.size:
            order = np.argsort(new_key, kind="stable")
            new_d, new_key, new_c = new_d[order], new_key[order], new_c[order]
            rank_in_dep = np.zeros(len(new_d), np.int64)
            srt_starts = np.empty(len(new_d), bool)
            srt_starts[0] = True
            srt_starts[1:] = new_d[1:] != new_d[:-1]
            run_start_idx = np.flatnonzero(srt_starts)
            run_len = np.diff(np.append(run_start_idx, len(new_d)))
            rank_in_dep = (np.arange(len(new_d))
                           - np.repeat(run_start_idx, run_len))
            used = np.array([exp_per_dep.get(int(dd), 0)
                             for dd in new_d[run_start_idx]])
            budget_left = np.maximum(threshold - used, 0)
            admit = rank_in_dep < np.repeat(budget_left, run_len)
            # Admitted: merge into the sorted explicit store.
            if admit.any():
                a_key, a_c, a_d = new_key[admit], new_c[admit], new_d[admit]
                merged = np.concatenate([exp_keys, a_key])
                order2 = np.argsort(merged, kind="stable")
                exp_keys = merged[order2]
                exp_cnt = np.concatenate([exp_cnt, a_c])[order2]
                for dd, cnt_new in zip(*np.unique(a_d, return_counts=True)):
                    exp_per_dep[int(dd)] = exp_per_dep.get(int(dd), 0) + int(cnt_new)
            # Spilled: add to the count-min sketch, mark the dep inexact.
            spill = ~admit
            if spill.any():
                s_key, s_c = new_key[spill], new_c[spill]
                n_spilled += int(spill.sum())
                spilled_deps.update(int(x) for x in np.unique(new_d[spill]))
                kcap = segments.pow2_capacity(len(s_key))
                t = sketch.count_min_add(
                    jnp.asarray(allatonce._pad_np(_pair_hash32(s_key), kcap, 0)),
                    jnp.asarray(allatonce._pad_np(
                        np.minimum(s_c, cap).astype(np.int32), kcap, 0)),
                    jnp.arange(kcap) < len(s_key),
                    bits=sbf_width, num_hashes=sketch.DEFAULT_HASHES, cap=cap)
                cm_table = sketch.merge_count_min([cm_table, np.asarray(t)],
                                                  cap=cap)

    if len(exp_keys) == 0 and not spilled_deps:
        z = np.zeros(0, np.int64)
        return z, z, z

    # --- Classify explicit pairs (EvaluateHalfApproximateOverlapSets).
    cm_dev = jnp.asarray(cm_table)

    def cm_query(key64):
        if key64.size == 0:
            return np.zeros(0, np.int64)
        kcap = segments.pow2_capacity(len(key64))
        q = sketch.count_min_query(
            cm_dev,
            jnp.asarray(allatonce._pad_np(_pair_hash32(key64), kcap, 0)),
            bits=sbf_width, num_hashes=sketch.DEFAULT_HASHES)
        return np.asarray(q)[:len(key64)].astype(np.int64)

    approx = cm_query(exp_keys)
    exp_dep = exp_keys >> 32
    exact_pair = approx == 0
    frequent_exact = exact_pair & (exp_cnt >= min_support)
    infrequent = (exp_cnt + approx < min_support)
    unknown = ~exact_pair & ~infrequent

    # --- Round 2: exact re-evaluation for inexact dependents only.
    r2_deps = set(spilled_deps)
    r2_deps.update(int(x) for x in np.unique(exp_dep[unknown]))
    if r2_deps:
        dep_ok2 = np.zeros(len(dep_ok), bool)
        dep_ok2[np.fromiter(r2_deps, np.int64, len(r2_deps))] = True
        dep_ok2 &= dep_ok
        parts2 = []
        n_r2_rows = 0
        for d, r, c in _iter_chunk_pairs(line_val_h, line_cap_h, dep_ok2,
                                         ref_ok, budget, stats, "pairs_11"):
            key = (d << 32) | r
            # Upper bound = explicit part + sketch part; below min_support the
            # true total is provably below too -> drop before the merge.
            hit, pos_c = _match_explicit(key)
            e_part = (np.where(hit, exp_cnt[pos_c], 0)
                      if len(exp_cnt) else np.zeros(len(key), np.int64))
            upper = e_part + cm_query(key)
            keep = upper >= min_support
            n_r2_rows += int(keep.sum())
            if keep.any():
                parts2.append((d[keep], r[keep], c[keep]))
        d2, r2, c2 = _merge_pair_parts(parts2)
        k2 = c2 >= min_support
        d2, r2, c2 = d2[k2], r2[k2], c2[k2]
    else:
        d2 = r2 = c2 = np.zeros(0, np.int64)
        n_r2_rows = 0

    # --- Assemble: exact round-1 pairs of clean deps + round-2 pairs.
    # Round 2 recomputed every surviving pair of its dependents from scratch,
    # so round-1 output keeps only exact-frequent pairs of clean dependents.
    r2_dep_arr = (np.fromiter(r2_deps, np.int64, len(r2_deps))
                  if r2_deps else np.zeros(0, np.int64))
    keep1 = frequent_exact & ~np.isin(exp_dep, r2_dep_arr)
    d1 = exp_dep[keep1]
    r1 = exp_keys[keep1] & 0xFFFFFFFF
    c1 = exp_cnt[keep1]
    if stats is not None:
        metrics.set_many(stats, ha_spilled=n_spilled,
                         ha_round2_deps=len(r2_deps),
                         ha_explicit_pairs=len(exp_keys),
                         ha_round2_merged_pairs=int(d2.size),
                         ha_round2_rows=n_r2_rows)
    d_out = np.concatenate([d1, d2])
    r_out = np.concatenate([r1, r2])
    c_out = np.concatenate([c1, c2])
    order = np.argsort((d_out << 32) | r_out, kind="stable")
    return d_out[order], r_out[order], c_out[order]


# ---------------------------------------------------------------------------
# Dense cooc backend: one membership matmul answers every lattice level.
# ---------------------------------------------------------------------------

@jax.jit
def _stage_cooc_full(m):
    """(c_pad, c_pad) int32 co-occurrence counts from the membership matrix."""
    return cooc_ops.cooc_dot(m, m)


class _DenseCooc:
    """Device-array carrier for the dense lattice (_run_lattice_dense): the
    membership matrix, the resident M^T M cooc matrix, and per-capture
    supports, plus the shape scalars the host loop needs."""

    def __init__(self, m, cooc_m, support_d, c_pad, n_lines, num_caps):
        self.m = m
        self.cooc = cooc_m
        self.support_d = support_d  # (c_pad,) int32 per-capture support
        self.c_pad = c_pad
        self.n_lines = n_lines
        self.num_caps = num_caps


def _prepare_dense(padded, n, min_support, projections, use_fc_filter, use_ars,
                   stats):
    """Device prep for the dense backend.  Returns (cooc_fn, cap_code, cap_v1,
    cap_v2, dep_count, num_caps) or None (fall back / empty input -> ()). """
    prep = allatonce._stage_prepare(
        padded, jnp.int32(n), jnp.int32(min_support), projections=projections,
        use_fc_filter=use_fc_filter, use_ars=use_ars)
    (line_gid, cap_id, cand_valid, n_lines_d, cap_code_d, cap_v1_d, cap_v2_d,
     num_caps_d) = prep
    # The first pull waits for the whole join-line build.
    with tracer.span("pull-prepare-counts", cat=tracer.CAT_PULL):
        n_lines, num_caps = (int(x) for x in
                             jax.device_get((n_lines_d, num_caps_d)))
    if n_lines == 0 or num_caps == 0:
        return ()
    plan = cooc_ops.dense_plan(n_lines, num_caps)
    if plan is None or plan.c_pad > allatonce.SINGLE_SHOT_C:
        return None
    l_pad, c_pad = plan.l_pad, plan.c_pad
    m, dep_count_d, lens = allatonce._stage_membership(
        line_gid, cap_id, cand_valid, jnp.int32(min_support),
        l_pad=l_pad, c_pad=c_pad, membership_dtype=plan.dtype)
    cooc_m = _stage_cooc_full(m)
    with tracer.span("pull-capture-table", cat=tracer.CAT_PULL):
        (cap_code, cap_v1, cap_v2, dep_count, lens_h) = jax.device_get(
            (cap_code_d[:num_caps], cap_v1_d[:num_caps], cap_v2_d[:num_caps],
             jax.lax.slice(dep_count_d, (0,), (num_caps,)),
             jax.lax.slice(lens, (0,), (n_lines,))))
    if stats is not None:
        # Host reductions over the per-line vector, while the device idles.
        with tracer.span("line-stats", cat=tracer.CAT_STAGE):
            lens64 = lens_h.astype(np.int64)
            metrics.set_many(
                stats, n_triples=n, n_lines=int((lens64 > 0).sum()),
                n_frequent_rows=int(lens64.sum()),
                n_line_rows=int(dep_count.astype(np.int64).sum()),
                n_captures=num_caps, total_pairs=0,
                max_line=int(lens64.max()) if lens64.size else 0,
                pair_backend="matmul",
                join_sort_rows=emission.join_sort_rows(padded.shape[0],
                                                       projections),
                dense_plan=plan.describe(), cooc_dtype=plan.dtype,
                plane_bits=plan.plane_bits)
            metrics.struct_set(stats, "kernel_resolution",
                               cooc_ops.resolution_report())
            if datastats.enabled():
                datastats.publish_line_stats(
                    stats, hist=datastats.log2_bucket_counts(lens64),
                    n_lines=int((lens64 > 0).sum()),
                    max_line=int(lens64.max()) if lens64.size else 0,
                    source="single")
                sup = dep_count.astype(np.int64)
                datastats.publish_capture_spectrum(
                    stats, hist=datastats.log2_bucket_counts(sup),
                    n_captures=num_caps,
                    max_support=int(sup.max()) if sup.size else 0,
                    source="single")
    fn = _DenseCooc(m, cooc_m, dep_count_d, c_pad, n_lines, num_caps)
    return (fn, cap_code.astype(np.int64), cap_v1.astype(np.int64),
            cap_v2.astype(np.int64), dep_count.astype(np.int64), num_caps)


# ---------------------------------------------------------------------------
# Fully-device lattice: every level is boolean algebra on the resident cooc
# matrix.  Candidate generation — the Generate*/Infer* group-reduces — becomes
# subcapture-indexed gathers: a binary capture IS the merge of its two unary
# subcaptures, so "pairs of relations sharing a dep/ref" is Rel[s1[m]] AND
# Rel[s2[m]].  No host pair enumeration (the numpy group-quadratics dominated
# wall clock and memory past ~100k triples).
# ---------------------------------------------------------------------------

_pack_bool = cooc_ops.pack_bool


@jax.jit
def _lat11(cooc_m, support, u_freq, ms):
    """1/1 level: K = CIND matrix, P = proper-overlap matrix (both unary&freq,
    off-diagonal).  Returns (K, P, packed K, |P|)."""
    c = cooc_m.shape[0]
    idx = jnp.arange(c, dtype=jnp.int32)
    base = (u_freq[:, None] & u_freq[None, :]
            & (idx[:, None] != idx[None, :]))
    full = cooc_m == support[:, None]
    k = base & full
    p = base & (cooc_m >= ms) & ~full
    return k, p, _pack_bool(k), p.sum()


@jax.jit
def _scatter_pairs(dep_idx, ref_idx, valid, template):
    """Rebuild a (c, c) bool relation from host pair lists (AR-filtered K)."""
    d = jnp.where(valid, dep_idx, template.shape[0])
    return jnp.zeros_like(template).at[d, ref_idx].set(True, mode="drop")


@jax.jit
def _lat12(k, m_mat, cooc_m, support, ms, bin_ids, s1, s2, sub_ok, freq_d):
    """1/2 level: candidates K[d,s1[m]] & K[d,s2[m]] plus the trivial-merge
    refinement (GenerateUnaryBinaryCindCandidates.scala:16-41), verified as
    cooc == support.  Returns (cind12 (c x B), packed, candidate count,
    u_l line stat)."""
    c = cooc_m.shape[0]
    nb = bin_ids.shape[0]
    ar_b = jnp.arange(nb, dtype=jnp.int32)
    cand = k[:, s1] & k[:, s2] & sub_ok[None, :]
    # Refinement: for m's subs {a, b}: (a, m) iff K[a, b]; (b, m) iff K[b, a].
    cand = cand.at[s1, ar_b].max(k[s1, s2] & sub_ok)
    cand = cand.at[s2, ar_b].max(k[s2, s1] & sub_ok)
    cooc_b = cooc_m[:, bin_ids]
    cind = cand & (cooc_b == support[:, None]) & (support[:, None] >= ms)
    dep_any = cand.any(axis=1)
    ref_any = jnp.zeros(c, bool).at[bin_ids].set(cand.any(axis=0), mode="drop")
    u_l = _union_line_counts(m_mat, (dep_any | ref_any) & freq_d)
    return cind, _pack_bool(cind), cand.sum(), u_l


@jax.jit
def _lat21(k, p, m_mat, cooc_m, support, ms, bin_ids, s1, s2, sub_ok, freq_d):
    """2/1 level: candidates from pairs of proper overlaps sharing the ref
    (GenerateBinaryUnaryCindCandidates), inferred non-minimal 2/1s from
    marked pairs (InferDoubleSingleCinds), verified; implied pairs (ref a
    value-matched subcapture of dep) masked by sub-id equality."""
    c = cooc_m.shape[0]
    o = k | p
    cand = p[s1, :] & p[s2, :] & sub_ok[:, None]
    inf = ((k[s1, :] & o[s2, :]) | (o[s1, :] & k[s2, :])) & sub_ok[:, None]
    support_b = support[bin_ids]
    cooc_b = cooc_m[bin_ids, :]  # symmetric: rows at binary ids
    idx = jnp.arange(c, dtype=jnp.int32)
    implied = (idx[None, :] == s1[:, None]) | (idx[None, :] == s2[:, None])
    cind = (cand & (cooc_b == support_b[:, None])
            & (support_b[:, None] >= ms) & ~implied)
    rel_all = cind | inf
    dep_any = jnp.zeros(c, bool).at[bin_ids].set(cand.any(axis=1), mode="drop")
    ref_any = cand.any(axis=0)
    u_l = _union_line_counts(m_mat, (dep_any | ref_any) & freq_d)
    return rel_all, _pack_bool(cind), inf.sum(), cand.sum(), u_l


@jax.jit
def _lat22(rel_all, cind12, m_mat, cooc_m, support, ms, bin_ids, s1, s2,
           sub_ok, code_b, v1_b, v2_b, freq_d):
    """2/2 level: candidates rel21[b,s1[m]] & rel21[b,s2[m]] plus the
    substituted-subcapture refinement (GenerateBinaryBinaryCindCandidates),
    pruned against 1/2 CINDs (documented intent of PruneNonMinimalDouble
    DoubleCindCandidates) and the equal-code implied quirk, verified."""
    c = cooc_m.shape[0]
    nb = bin_ids.shape[0]
    g1 = rel_all[:, s1]
    g2 = rel_all[:, s2]
    same_code = code_b[:, None] == code_b[None, :]
    eq1 = s1[None, :] == s1[:, None]
    eq2 = s2[None, :] == s2[:, None]
    cand = (g1 & g2) | (same_code & ((eq2 & g1) | (eq1 & g2)))
    cand &= sub_ok[:, None] & sub_ok[None, :]
    cand &= jnp.arange(nb)[:, None] != jnp.arange(nb)[None, :]
    # Equal-code implied quirk (Condition.isImpliedBy, pinned in test_oracle).
    cand &= ~(same_code & (v1_b[None, :] == v2_b[:, None]))
    # Prune candidates implied by a 1/2 CIND on a value-matched dep subcapture.
    cand &= ~(cind12[s1, :] | cind12[s2, :])
    support_b = support[bin_ids]
    cooc_bb = cooc_m[bin_ids[:, None], bin_ids[None, :]]
    cind = cand & (cooc_bb == support_b[:, None]) & (support_b[:, None] >= ms)
    dep_any = jnp.zeros(c, bool).at[bin_ids].set(cand.any(axis=1), mode="drop")
    ref_any = jnp.zeros(c, bool).at[bin_ids].set(cand.any(axis=0), mode="drop")
    u_l = _union_line_counts(m_mat, (dep_any | ref_any) & freq_d)
    return _pack_bool(cind), cand.sum(), u_l


def _union_line_counts(m_mat, union_mask):
    """Per-line count of union-flagged captures — the chunked backend's pair
    accounting (stat = sum u*(u-1)), kept for backend comparability."""
    return cooc_ops.cooc_dot(m_mat, union_mask.astype(m_mat.dtype),
                             dims=((1,), (0,)))


def _run_lattice_dense(dc, cap_code, cap_v1, cap_v2, dep_count, num_caps,
                       min_support, use_ars, rules, stats) -> CindTable:
    """S2L lattice walk on the resident cooc matrix (dense backend); the
    caller removes implied CINDs."""
    c_pad = dc.c_pad
    n_lines = dc.n_lines
    cooc_m = dc.cooc
    m_mat = dc.m
    support_d = dc.support_d  # (c_pad,) int32 on device
    ms = jnp.int32(min_support)

    unary = np.asarray(cc.is_unary(cap_code))
    freq = dep_count >= min_support
    u_freq = np.zeros(c_pad, bool)
    u_freq[:num_caps] = unary & freq
    freq_pad = np.zeros(c_pad, bool)
    freq_pad[:num_caps] = freq
    freq_d = jnp.asarray(freq_pad)

    # Deferred stats: every per-level device value (union-line vectors,
    # candidate counts, n_prop, n_inf) is collected and pulled in ONE
    # device_get after the whole walk is dispatched — per-level host syncs
    # were the lattice's dominant non-matmul cost (r4: 2.3x
    # AllAtOnce wall at fewer verified pairs; VERDICT item 5).
    pending = []  # (key, u_l device vec, n_cand device scalar | None)

    def stat_add(key, u_l, n_cand=None):
        if stats is not None:
            pending.append((key, u_l, n_cand))

    def flush_stats(extras=()):
        """One batched pull of every deferred level stat plus `extras`
        (device scalars); returns the pulled extras.  Writes level stats
        with the chunked backend's only-when-candidates gate so the two
        backends stay comparable."""
        if stats is None:
            return ()  # extras feed stats only; skip the pull entirely
        with tracer.span("pull-lattice-stats", cat=tracer.CAT_PULL):
            flat = jax.device_get([x for _, u, nc in pending
                                   for x in (u,) + ((nc,) if nc is not None
                                                    else ())] + list(extras))
        it = iter(flat)
        with tracer.span("lattice-stats", cat=tracer.CAT_STAGE):
            for key, _, nc in pending:
                u = np.asarray(next(it), np.int64)[:n_lines]
                n_cand = None if nc is None else int(next(it))
                if n_cand is not None and n_cand == 0:
                    continue
                n_pairs = int((u * (u - 1)).sum())
                metrics.gauge_set(stats, key, n_pairs)
                metrics.counter_add(stats, "total_pairs", n_pairs)
        return tuple(it)

    # --- 1/1.
    k, p, k_packed, n_prop = _lat11(
        cooc_m, support_d, jnp.asarray(u_freq), ms)
    if stats is not None:
        stat_add("pairs_11", _union_line_counts(m_mat, jnp.asarray(u_freq)))
    cind11 = None
    if use_ars:
        # The AR filter rewrites K before 1/2 generation, so this one decode
        # cannot be deferred into the end-of-walk batch.
        cind11_d, cind11_r = cooc_ops.extract_packed(k_packed, num_caps,
                                                     num_caps)
        keep = ~frequency.ar_implied_pair_mask(
            cap_code[cind11_d], cap_code[cind11_r],
            cap_v1[cind11_d], cap_v1[cind11_r], rules)
        cind11 = (cind11_d[keep], cind11_r[keep])
        cap = segments.pow2_capacity(max(1, len(cind11[0])))
        k = _scatter_pairs(
            jnp.asarray(allatonce._pad_np(cind11[0].astype(np.int32), cap, 0)),
            jnp.asarray(allatonce._pad_np(cind11[1].astype(np.int32), cap, 0)),
            jnp.arange(cap) < len(cind11[0]), k)

    # --- Binary-capture metadata (host, O(num_caps)).
    bin_ids_h = np.flatnonzero(np.asarray(cc.is_binary(cap_code)))
    nb = len(bin_ids_h)
    if nb == 0:
        if cind11 is None:
            cind11 = cooc_ops.extract_packed(k_packed, num_caps, num_caps)
        cind11_d, cind11_r = cind11
        extras = flush_stats((n_prop,))
        table = CindTable(
            dep_code=cap_code[cind11_d], dep_v1=cap_v1[cind11_d],
            dep_v2=cap_v2[cind11_d], ref_code=cap_code[cind11_r],
            ref_v1=cap_v1[cind11_r], ref_v2=cap_v2[cind11_r],
            support=dep_count[cind11_d])
        if stats is not None:
            metrics.set_many(stats, n_cinds_11=len(cind11_d),
                             n_proper_overlaps=int(extras[0]),
                             n_cinds_12=0, n_cinds_21=0, n_inferred_21=0,
                             n_cinds_22=0)
        return table
    b_pad = segments.pow2_capacity(nb)
    s1_h = _lookup_capture_ids(
        cap_code, cap_v1, cap_v2,
        np.asarray(cc.first_subcapture(cap_code[bin_ids_h])),
        cap_v1[bin_ids_h], np.full(nb, NO_VALUE, np.int64))
    s2_h = _lookup_capture_ids(
        cap_code, cap_v1, cap_v2,
        np.asarray(cc.second_subcapture(cap_code[bin_ids_h])),
        cap_v2[bin_ids_h], np.full(nb, NO_VALUE, np.int64))
    sub_ok_h = (s1_h >= 0) & (s2_h >= 0)
    pad = allatonce._pad_np
    bin_ids = jnp.asarray(pad(bin_ids_h.astype(np.int32), b_pad, 0))
    s1 = jnp.asarray(pad(np.maximum(s1_h, 0).astype(np.int32), b_pad, 0))
    s2 = jnp.asarray(pad(np.maximum(s2_h, 0).astype(np.int32), b_pad, 0))
    sub_ok = jnp.asarray(pad(sub_ok_h, b_pad, False))
    code_b = jnp.asarray(pad(cap_code[bin_ids_h].astype(np.int32), b_pad, -1))
    v1_b = jnp.asarray(pad(cap_v1[bin_ids_h].astype(np.int32), b_pad, -1))
    v2_b = jnp.asarray(pad(cap_v2[bin_ids_h].astype(np.int32), b_pad, -2))

    # --- 1/2.
    cind12, cind12_packed, n_cand12, u12 = _lat12(
        k, m_mat, cooc_m, support_d, ms, bin_ids, s1, s2, sub_ok, freq_d)
    stat_add("pairs_12", u12, n_cand12)

    # --- 2/1 (+ inferred).
    rel_all, cind21_packed, n_inf, n_cand21, u21 = _lat21(
        k, p, m_mat, cooc_m, support_d, ms, bin_ids, s1, s2, sub_ok, freq_d)
    stat_add("pairs_21", u21, n_cand21)

    # --- 2/2.
    cind22_packed, n_cand22, u22 = _lat22(
        rel_all, cind12, m_mat, cooc_m, support_d, ms, bin_ids, s1, s2,
        sub_ok, code_b, v1_b, v2_b, freq_d)
    stat_add("pairs_22", u22, n_cand22)

    # Decode all relations (the deferred 1/1 plus the three binary levels)
    # through the shared batched two-phase decoder, then flush every deferred
    # stat scalar/vector in one more pull — the whole walk costs O(1) host
    # syncs instead of O(levels).
    relations = [(cind12_packed, num_caps, nb), (cind21_packed, nb, num_caps),
                 (cind22_packed, nb, nb)]
    bin_bits = max(p.shape[0] * p.shape[1] * 32 for p, _, _ in relations)
    k_bits = k_packed.shape[0] * k_packed.shape[1] * 32
    if cind11 is None and max(k_bits, bin_bits) <= cooc_ops.EXTRACT_DEVICE_ELEMS:
        # The 1/1 tile fits the batch bound: one decode batch for all four.
        decoded = cooc_ops.extract_packed_iter(
            [lambda p=p, rr=rr, rc=rc: (p, rr, rc)
             for p, rr, rc in [(k_packed, num_caps, num_caps)] + relations],
            max(k_bits, bin_bits))
        cind11, decoded = decoded[0], decoded[1:]
    else:
        # Oversized 1/1 tile strip-decodes on its own; keep the three small
        # binary relations in one batch rather than un-batching all four.
        if cind11 is None:
            cind11 = cooc_ops.extract_packed(k_packed, num_caps, num_caps)
        decoded = cooc_ops.extract_packed_iter(
            [lambda p=p, rr=rr, rc=rc: (p, rr, rc) for p, rr, rc in relations],
            bin_bits)
    cind11_d, cind11_r = cind11
    (d12, r12b), (d21b, r21), (d22b, r22b) = decoded
    r12 = bin_ids_h[r12b]
    d21 = bin_ids_h[d21b]
    d22, r22 = bin_ids_h[d22b], bin_ids_h[r22b]
    extras = flush_stats((n_prop, n_inf))

    if stats is not None:
        metrics.set_many(stats, n_cinds_11=len(cind11_d),
                         n_proper_overlaps=int(extras[0]),
                         n_cinds_12=len(d12), n_cinds_21=len(d21),
                         n_inferred_21=int(extras[1]),
                         n_cinds_22=len(d22))

    all_d = np.concatenate([cind11_d, d12, d21, d22])
    all_r = np.concatenate([cind11_r, r12, r21, r22])
    all_s = dep_count[all_d]
    return CindTable(
        dep_code=cap_code[all_d], dep_v1=cap_v1[all_d], dep_v2=cap_v2[all_d],
        ref_code=cap_code[all_r], ref_v1=cap_v1[all_r], ref_v2=cap_v2[all_r],
        support=all_s)


# ---------------------------------------------------------------------------
# Host-side candidate generation (the Generate*/Infer* group-reduces).
# ---------------------------------------------------------------------------

def _np_group_pairs(group_key: np.ndarray):
    """All ordered (i, j), i != j pairs of row indices within equal-key runs.

    `group_key` must be sorted.  Same rotation layout as ops/pairs.py, on host.
    """
    n = group_key.shape[0]
    if n == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    starts = np.empty(n, bool)
    starts[0] = True
    starts[1:] = group_key[1:] != group_key[:-1]
    start_rows = np.flatnonzero(starts)
    lens = np.diff(np.append(start_rows, n)).astype(np.int64)
    length = np.repeat(lens, lens)
    start_idx = np.repeat(start_rows, lens)
    pos = np.arange(n, dtype=np.int64) - start_idx
    reps = length - 1
    total = int(reps.sum())
    if total == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    row = np.repeat(np.arange(n, dtype=np.int64), reps)
    k = np.arange(total, dtype=np.int64) - np.repeat(np.cumsum(reps) - reps, reps)
    partner = start_idx[row] + (pos[row] + k + 1) % length[row]
    return row, partner


def _merge_refs(code_i, v_i, code_j, v_j):
    """Canonical merged binary capture from two unary captures (lower code first).

    Callers guarantee code_i < code_j, equal secondary, disjoint primaries, so v_i
    belongs to the lower condition field — canonical (field-ascending) value order,
    as in GenerateXxxBinaryCindCandidates.scala:44-58.
    """
    return code_i | code_j, v_i, v_j


def _mergeable(code_a, code_b):
    """Two unary captures can merge into a valid binary capture."""
    return ((cc.secondary(code_a) == cc.secondary(code_b))
            & (cc.primary(code_a) != cc.primary(code_b)))


def _generate_x2_candidates(dep_cols, ref_code, ref_v1):
    """x/2 candidates from CINDs sharing a dependent capture.

    dep_cols: tuple of arrays identifying the dep (id or code+values); ref_code/
    ref_v1: unary referenced captures.  Returns per-candidate (dep_row_index,
    merged_ref_code, ref_v1, ref_v2) following GenerateXxxBinaryCindCandidates'
    pair phase.  Refinements are family-specific (callers).
    """
    n = ref_code.shape[0]
    if n == 0:
        return (np.zeros(0, np.int64),) * 4
    order = np.lexsort(tuple(reversed((*dep_cols, ref_code, ref_v1))))
    dep_sorted = tuple(cix[order] for cix in dep_cols)
    rc, rv = ref_code[order], ref_v1[order]
    gkey = np.zeros(n, np.int64)
    for cix in dep_sorted:
        gkey = gkey * (int(cix.max(initial=0)) + 2) + (cix + 1)
    i, j = _np_group_pairs(gkey)
    keep = (rc[i] < rc[j]) & _mergeable(rc[i], rc[j])
    i, j = i[keep], j[keep]
    mcode, mv1, mv2 = _merge_refs(rc[i], rv[i], rc[j], rv[j])
    return order[i], mcode, mv1, mv2


def _lookup_capture_ids_structured(cap_code, cap_v1, cap_v2, q_code, q_v1, q_v2):
    """Exact fallback at any value-space size (structured unique; slow)."""
    table = np.stack([cap_code, cap_v1, cap_v2], axis=1).astype(np.int64)
    query = np.stack([q_code, q_v1, q_v2], axis=1).astype(np.int64)
    allr = np.concatenate([table, query])
    uniq, inv = np.unique(allr, axis=0, return_inverse=True)
    pos = np.full(len(uniq), -1, np.int64)
    pos[inv[:len(table)]] = np.arange(len(table))
    return pos[inv[len(table):]]


def _lookup_capture_ids(cap_code, cap_v1, cap_v2, q_code, q_v1, q_v2):
    """Ids of query captures in the canonical capture table; -1 when absent.

    Rank-compresses the value space so each (code, v1, v2) row packs into one
    int64 key, then matches with sorted-key searchsorted — the structured
    np.unique(axis=0) this replaces dominated the whole lattice walk (r3
    profile: 8.6s of a 15.5s S2L run at 50k triples).
    """
    if len(cap_code) == 0 or len(q_code) == 0:
        return np.full(len(q_code), -1, np.int64)
    q_v1 = np.asarray(q_v1, np.int64)
    q_v2 = np.asarray(q_v2, np.int64)
    uniq = np.unique(np.concatenate([cap_v1, cap_v2, q_v1, q_v2]))
    bits = max(1, int(uniq.size).bit_length())
    if 6 + 2 * bits > 63:  # >= ~2^28 distinct values: exact slow path
        return _lookup_capture_ids_structured(cap_code, cap_v1, cap_v2,
                                              q_code, q_v1, q_v2)

    def key(c, v1, v2):
        r1 = np.searchsorted(uniq, v1).astype(np.int64)
        r2 = np.searchsorted(uniq, v2).astype(np.int64)
        return (np.asarray(c, np.int64) << (2 * bits)) | (r1 << bits) | r2

    tk = key(cap_code, cap_v1, cap_v2)
    order = np.argsort(tk, kind="stable")
    tks = tk[order]
    qk = key(q_code, q_v1, q_v2)
    pos = np.minimum(np.searchsorted(tks, qk), len(tks) - 1)
    return np.where(tks[pos] == qk, order[pos], -1).astype(np.int64)


def _semi_join(dep, ref, cnt, cand_dep, cand_ref):
    """Keep (dep, ref, cnt) rows whose (dep, ref) is in the candidate pair set."""
    if len(cand_dep) == 0 or len(dep) == 0:
        z = np.zeros(0, np.int64)
        return z, z, z
    keys = (dep.astype(np.int64) << 32) | ref.astype(np.int64)
    cand = np.unique((cand_dep.astype(np.int64) << 32) | cand_ref.astype(np.int64))
    keep = np.isin(keys, cand, assume_unique=False)
    return dep[keep], ref[keep], cnt[keep]


# ---------------------------------------------------------------------------
# The strategy.
# ---------------------------------------------------------------------------

def discover(triples, min_support: int, projections: str = "spo",
             use_frequent_condition_filter: bool = True,
             use_association_rules: bool = False,
             clean_implied: bool = False,
             pair_chunk_budget: int = allatonce.PAIR_CHUNK_BUDGET,
             pair_backend: str = "auto",
             explicit_threshold: int = -1,
             sbf_bits: int = -1,
             sbf_width: int = 1 << 20,
             balanced_11: bool = False,
             stats: dict | None = None) -> CindTable:
    """Discover CINDs level by level (SmallToLargeTraversalStrategy semantics).

    With clean_implied=True and no association rules the output equals
    allatonce.discover(clean_implied=True); raw output follows the reference's
    S2L, including its AR-before-generation ordering (see module docstring).

    pair_backend as in allatonce.discover: "matmul" verifies every level
    against one resident M^T M cooc matrix (_DenseCooc), "chunked" runs the
    per-level masked pair emission, "auto" picks matmul when it fits.

    explicit_threshold != -1 selects the memory-bounded half-approximate 1/1
    round (the reference's spectral-Bloom mode, gated on the same flag —
    SmallToLargeTraversalStrategy.scala:322-326): at most that many exact
    per-dependent counters in round 1, tail in a count-min sketch with
    `sbf_bits` per counter (--sbf-bytes; default sized to hold min_support)
    and `sbf_width` counters, exact round 2 only for inexact dependents.
    Output is identical to the exact path; it implies the chunked backend.
    That is by design, not a gap: the knob exists to bound MATERIALIZED PAIR
    memory, and the dense backend materializes no pairs at all (one bitpacked
    M^T M matmul whose footprint is the fixed l_pad x c_pad membership matrix)
    — on the dense path the bound it provides is already met by construction,
    so forcing chunked preserves the reference's "this flag selects the
    two-round algorithm" semantics instead of silently no-op'ing.

    balanced_11 (--balanced-overlap-candidates) halves the chunked backend's
    materialized 1/1 emission via rotation ownership (each unordered pair
    once; ops/pairs.py), symmetrizing the merged counts — output-identical.
    Implies the chunked backend; ignored under the half-approximate round
    (whose two-round bookkeeping tracks directed ownership separately).
    """
    min_support = max(int(min_support), 1)
    use_ars = use_association_rules and use_frequent_condition_filter
    if explicit_threshold != -1 or balanced_11:
        pair_backend = "chunked"
    if sbf_bits == -1:
        # Reference default: enough bits to encode min_support
        # (SmallToLargeTraversalStrategy.scala:182-186).
        sbf_bits = min_support.bit_length() + 1
    if explicit_threshold != -1 and             min((1 << max(1, sbf_bits)) - 1, sketch.MAX_COUNT_MIN_CAP) < min_support:
        # Reference upfront check (SmallToLargeTraversalStrategy.scala:189-193).
        raise ValueError(
            f"sbf_bits={sbf_bits} saturates below min_support {min_support}")

    triples = np.asarray(triples, np.int32)
    n = triples.shape[0]
    if n == 0 or not any(ch in projections for ch in "spo"):
        return CindTable.empty()

    dense = None
    if pair_backend in ("auto", "matmul"):
        # As in allatonce.discover: whether the dense plan fits is only known
        # after candidate prep, so a fallback to chunked pays emission +
        # interning twice.  Pass pair_backend="chunked" when the data is known
        # to exceed the budget.
        padded = allatonce.upload_triples(triples)
        dense = _prepare_dense(padded, n, min_support, projections,
                               use_frequent_condition_filter, use_ars, stats)
        if dense == ():
            return CindTable.empty()
        if dense is None and pair_backend == "matmul":
            raise ValueError("pair_backend='matmul' but the dense plan "
                             "does not fit the single-shot budget")

    if dense is not None:
        dc, cap_code, cap_v1, cap_v2, dep_count, num_caps = dense
        rules = (frequency.mine_association_rules(triples, min_support)
                 if use_ars else None)
        if use_ars and stats is not None:
            metrics.struct_set(stats, "association_rules", rules)
        table = _run_lattice_dense(dc, cap_code, cap_v1, cap_v2, dep_count,
                                   num_caps, min_support, use_ars, rules,
                                   stats)
        with tracer.span("postprocess", cat=tracer.CAT_STAGE):
            if clean_implied:
                table = minimality.minimize_table(table)
            integrity.publish_output(stats, table)
        return table
    # --- Chunked backend: shared phase A (join lines + capture table + filter).
    st = allatonce.prepare_join_lines(triples, min_support, projections,
                                      use_frequent_condition_filter,
                                      use_ars, stats)
    if st is None:
        return CindTable.empty()
    triples = st["triples"]
    line_val_h, line_cap_h = st["line_val_h"], st["line_cap_h"]
    cap_code, cap_v1, cap_v2 = st["cap_code"], st["cap_v1"], st["cap_v2"]
    dep_count, num_caps = st["dep_count"], st["num_caps"]
    if stats is not None:
        metrics.gauge_set(stats, "pair_backend", "chunked")

    def cooc_fn(dep_ok, ref_ok, stat_key):
        return _chunked_cooc(line_val_h, line_cap_h, dep_ok, ref_ok,
                             pair_chunk_budget, stats, stat_key)

    cooc_fn_11 = None
    if explicit_threshold != -1:
        def cooc_fn_11(dep_ok, ref_ok, stat_key):
            return _half_approx_cooc_11(
                line_val_h, line_cap_h, dep_ok, ref_ok, pair_chunk_budget,
                stats, min_support, explicit_threshold, sbf_bits, sbf_width)
    elif balanced_11:
        def cooc_fn_11(dep_ok, ref_ok, stat_key):
            return _chunked_cooc(line_val_h, line_cap_h, dep_ok, ref_ok,
                                 pair_chunk_budget, stats, stat_key,
                                 balanced=True)

    rules = (frequency.mine_association_rules(triples, min_support)
             if use_ars else None)
    if use_ars and stats is not None:
        # driver --ar-output reuses these
        metrics.struct_set(stats, "association_rules", rules)

    table = _run_lattice(cooc_fn, cap_code, cap_v1, cap_v2, dep_count,
                         num_caps, min_support, use_ars, rules, clean_implied,
                         stats, cooc_fn_11=cooc_fn_11)
    integrity.publish_output(stats, table)
    return table


def _run_lattice(cooc_fn, cap_code, cap_v1, cap_v2, dep_count, num_caps,
                 min_support, use_ars, rules, clean_implied,
                 stats, cooc_fn_11=None, mesh=None) -> CindTable:
    """The S2L lattice walk, generic over the verification backend.

    cooc_fn(dep_ok, ref_ok, stat_key) -> (dep_id, ref_id, count): global merged
    co-occurrence counts for flagged capture pairs.  The single-device backend
    is the chunked device loop over host join lines (_chunked_cooc); the
    multi-device backend is models.sharded._ShardedCooc (flag broadcast + masked
    pair phase over the mesh).  Everything else — candidate generation, pruning,
    assembly — is identical host logic, which is what makes the two strategies
    differentially testable against each other.
    """
    unary = np.asarray(cc.is_unary(cap_code))

    # --- Level 1/1: unary-unary overlaps (findFrequentSingleSingleConditionOverlaps).
    # cooc_fn_11 (the half-approximate two-round evaluation) applies to this
    # level only, as in the reference; its output is pre-filtered to
    # cnt >= min_support, which is output-neutral here (see its docstring).
    d11, r11, c11cnt = (cooc_fn_11 or cooc_fn)(unary, unary, "pairs_11")
    # Frequent overlaps only (findFrequentUnaryUnaryOverlapsDirectly's
    # rhs-count filter); lhs frequency is guaranteed by the capture filter.
    freq_ov = c11cnt >= min_support
    is_cind_11 = c11cnt == dep_count[d11]
    cind11_d, cind11_r = d11[is_cind_11], r11[is_cind_11]
    cind11_sup = c11cnt[is_cind_11]
    if use_ars:
        keep = ~frequency.ar_implied_pair_mask(
            cap_code[cind11_d], cap_code[cind11_r],
            cap_v1[cind11_d], cap_v1[cind11_r], rules)
        cind11_d, cind11_r, cind11_sup = (cind11_d[keep], cind11_r[keep],
                                          cind11_sup[keep])
    prop = freq_ov & ~is_cind_11
    prop_d, prop_r, prop_cnt = d11[prop], r11[prop], c11cnt[prop]
    if stats is not None:
        metrics.set_many(stats, n_cinds_11=len(cind11_d),
                         n_proper_overlaps=len(prop_d))

    # --- Level 1/2 (findSingleDoubleCinds).
    dep_idx, mcode, mv1, mv2 = _generate_x2_candidates(
        (cind11_d,), cap_code[cind11_r].astype(np.int64), cap_v1[cind11_r])
    c12_cand_dep = cind11_d[dep_idx]
    # Refinement: trivial 1/1 merge — d < r  =>  candidate d < merge(d, r)
    # (GenerateUnaryBinaryCindCandidates.scala:17-45).
    dcode, rcode = cap_code[cind11_d], cap_code[cind11_r]
    refn = _mergeable(dcode, rcode)
    lo_is_dep = cc.primary(dcode) < cc.primary(rcode)
    ref_mcode = np.where(refn, dcode | rcode, 0)
    ref_mv1 = np.where(lo_is_dep, cap_v1[cind11_d], cap_v1[cind11_r])
    ref_mv2 = np.where(lo_is_dep, cap_v1[cind11_r], cap_v1[cind11_d])
    c12_cand_dep = np.concatenate([c12_cand_dep, cind11_d[refn]])
    mcode = np.concatenate([mcode, ref_mcode[refn]])
    mv1 = np.concatenate([mv1, ref_mv1[refn]])
    mv2 = np.concatenate([mv2, ref_mv2[refn]])
    c12_cand_ref = _lookup_capture_ids(cap_code, cap_v1, cap_v2, mcode, mv1, mv2)
    ok = c12_cand_ref >= 0  # merged capture exists (and is frequent)
    c12_cand_dep, c12_cand_ref = c12_cand_dep[ok], c12_cand_ref[ok]
    cind12_d, cind12_r, cind12_sup = _verify_level(
        cooc_fn, c12_cand_dep, c12_cand_ref, num_caps, dep_count,
        cap_code, cap_v1, cap_v2, min_support, "pairs_12")

    # --- Level 2/1 (findDoubleSingleCindSets): candidates from pairs of proper
    # overlaps sharing the referenced capture (GenerateBinaryUnaryCindCandidates).
    c21_cand_dep, c21_cand_ref = _generate_2x_deps(
        prop_r, prop_d, cap_code, cap_v1, cap_v2, require_cind=None)
    cind21_d, cind21_r, cind21_sup = _verify_level(
        cooc_fn, c21_cand_dep, c21_cand_ref, num_caps, dep_count,
        cap_code, cap_v1, cap_v2, min_support, "pairs_21")

    # --- Inferred non-minimal 2/1s (InferDoubleSingleCinds): pairs of {1/1 CINDs
    # (marked), proper overlaps} on the same ref with >= 1 CIND.  Frequency of the
    # merged dep is exact here (capture table membership), cf. module docstring.
    inf_r = np.concatenate([cind11_r, prop_r])
    inf_d = np.concatenate([cind11_d, prop_d])
    inf_is_cind = np.concatenate([np.ones(len(cind11_d), bool),
                                  np.zeros(len(prop_d), bool)])
    inf21_dep, inf21_ref = _generate_2x_deps(
        inf_r, inf_d, cap_code, cap_v1, cap_v2, require_cind=inf_is_cind)
    all21_dep = np.concatenate([cind21_d, inf21_dep])
    all21_ref = np.concatenate([cind21_r, inf21_ref])

    # --- Level 2/2 (findDoubleDoubleCindSets).
    dep_idx, mcode, mv1, mv2 = _generate_x2_candidates(
        (all21_dep,), cap_code[all21_ref].astype(np.int64), cap_v1[all21_ref])
    c22_cand_dep = all21_dep[dep_idx]
    # Refinement: 2/1 with ref a value-substituted subcapture of dep
    # (GenerateBinaryBinaryCindCandidates.scala:20-42).
    dcode, rcode = cap_code[all21_dep], cap_code[all21_ref]
    refn = np.asarray(cc.is_subcode(cc.primary(rcode), cc.primary(dcode))) \
        & (cc.secondary(rcode) == cc.secondary(dcode))
    first_is_ref = cc.first_subcapture(dcode) == rcode
    ref_mv1 = np.where(first_is_ref, cap_v1[all21_ref], cap_v1[all21_dep])
    ref_mv2 = np.where(first_is_ref, cap_v2[all21_dep], cap_v1[all21_ref])
    c22_cand_dep = np.concatenate([c22_cand_dep, all21_dep[refn]])
    mcode = np.concatenate([mcode, dcode[refn]])
    mv1 = np.concatenate([mv1, ref_mv1[refn]])
    mv2 = np.concatenate([mv2, ref_mv2[refn]])
    c22_cand_ref = _lookup_capture_ids(cap_code, cap_v1, cap_v2, mcode, mv1, mv2)
    ok = c22_cand_ref >= 0
    c22_cand_dep, c22_cand_ref = c22_cand_dep[ok], c22_cand_ref[ok]
    # Drop self-pairs and pairs implied per Condition.isImpliedBy (incl. the
    # equal-code quirk) — the evidence extractors never emit those.
    ok = ~_implied_mask(c22_cand_dep, c22_cand_ref, cap_code, cap_v1, cap_v2)
    c22_cand_dep, c22_cand_ref = c22_cand_dep[ok], c22_cand_ref[ok]
    # Prune candidates implied by a 1/2 CIND (intended semantics of
    # PruneNonMinimalDoubleDoubleCindCandidates — see module docstring).
    keep = _prune_22_vs_12(c22_cand_dep, c22_cand_ref, cind12_d, cind12_r,
                           cap_code, cap_v1, cap_v2)
    c22_cand_dep, c22_cand_ref = c22_cand_dep[keep], c22_cand_ref[keep]
    cind22_d, cind22_r, cind22_sup = _verify_level(
        cooc_fn, c22_cand_dep, c22_cand_ref, num_caps, dep_count,
        cap_code, cap_v1, cap_v2, min_support, "pairs_22")

    if stats is not None:
        metrics.set_many(stats, n_cinds_12=len(cind12_d),
                         n_cinds_21=len(cind21_d),
                         n_inferred_21=len(inf21_dep),
                         n_cinds_22=len(cind22_d))

    # --- Assemble.
    all_d = np.concatenate([cind11_d, cind12_d, cind21_d, cind22_d])
    all_r = np.concatenate([cind11_r, cind12_r, cind21_r, cind22_r])
    all_s = np.concatenate([cind11_sup, cind12_sup, cind21_sup, cind22_sup])
    table = CindTable(
        dep_code=cap_code[all_d], dep_v1=cap_v1[all_d], dep_v2=cap_v2[all_d],
        ref_code=cap_code[all_r], ref_v1=cap_v1[all_r], ref_v2=cap_v2[all_r],
        support=all_s)
    if clean_implied:
        table = (minimality.minimize_table_sharded(table, mesh)
                 if mesh is not None else minimality.minimize_table(table))
    return table


def _generate_2x_deps(group_ref, member_dep, cap_code, cap_v1, cap_v2,
                      require_cind):
    """2/x dep-candidates: pairs of unary captures sharing a referenced capture.

    group_ref/member_dep: directed (dep, ref) pairs (capture ids) to group by ref.
    require_cind: None (all pairs; GenerateBinaryUnaryCindCandidates) or a bool
    array marking 1/1 CINDs, pairs needing >= 1 mark (InferDoubleSingleCinds).
    Returns (merged_dep_id, ref_id) for merged deps present in the capture table.
    """
    m = len(group_ref)
    if m == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    dcode = cap_code[member_dep]
    order = np.lexsort((cap_v1[member_dep], dcode, group_ref))
    gr, md = group_ref[order], member_dep[order]
    dc = dcode[order]
    marks = require_cind[order] if require_cind is not None else None
    i, j = _np_group_pairs(gr)
    keep = (dc[i] < dc[j]) & _mergeable(dc[i], dc[j])
    if marks is not None:
        keep &= marks[i] | marks[j]
    i, j = i[keep], j[keep]
    mcode = dc[i] | dc[j]
    mv1, mv2 = cap_v1[md[i]], cap_v1[md[j]]
    dep_ids = _lookup_capture_ids(cap_code, cap_v1, cap_v2, mcode, mv1, mv2)
    ok = dep_ids >= 0  # merged dep exists and is frequent (exact capture support)
    out_dep, out_ref = dep_ids[ok], gr[i][ok]
    if len(out_dep) == 0:
        return out_dep, out_ref
    both = np.unique((out_dep.astype(np.int64) << 32) | out_ref.astype(np.int64))
    return both >> 32, both & 0xFFFFFFFF


def _verify_level(cooc_fn, cand_dep, cand_ref, num_caps, dep_count,
                  cap_code, cap_v1, cap_v2, min_support, stat_key):
    """Verify candidate (dep, ref) pairs against the join lines by counting.

    CIND iff cooc(dep, ref) == |dep| (>= min_support by the capture filter).
    Replaces Extract*CindCandidates + IntersectCindCandidates + support filters.
    """
    if len(cand_dep) == 0:
        z = np.zeros(0, np.int64)
        return z, z, z
    dep_ok = np.zeros(num_caps, bool)
    dep_ok[cand_dep] = True
    ref_ok = np.zeros(num_caps, bool)
    ref_ok[cand_ref] = True
    d, r, cnt = cooc_fn(dep_ok, ref_ok, stat_key)
    d, r, cnt = _semi_join(d, r, cnt, cand_dep, cand_ref)
    is_cind = (cnt == dep_count[d]) & (dep_count[d] >= min_support)
    is_cind &= ~_implied_mask(d, r, cap_code, cap_v1, cap_v2)
    return d[is_cind], r[is_cind], dep_count[d[is_cind]]


def _implied_mask(dep_id, ref_id, cap_code, cap_v1, cap_v2):
    """Condition.isImpliedBy per pair of capture ids (same semantics as the
    oracle's _implies, vectorized), including dep == ref."""
    if len(dep_id) == 0:
        return np.zeros(0, bool)
    dcode, rcode = cap_code[dep_id], cap_code[ref_id]
    same = dep_id == ref_id
    sub = np.asarray(cc.is_subcode(rcode, dcode))
    first = cc.first_subcapture(dcode) == rcode
    vmatch = np.where(first, cap_v1[ref_id] == cap_v1[dep_id],
                      cap_v1[ref_id] == cap_v2[dep_id])
    return same | (sub & vmatch)


def _prune_22_vs_12(cand_dep, cand_ref, cind12_d, cind12_r,
                    cap_code, cap_v1, cap_v2):
    """Keep 2/2 candidates NOT implied by any 1/2 CIND: implied when a 1/2 CIND
    (a, ref) exists with a a value-matching unary subcapture of the candidate dep."""
    if len(cand_dep) == 0:
        return np.zeros(0, bool)
    if len(cind12_d) == 0:
        return np.ones(len(cand_dep), bool)
    # 1/2 CINDs keyed by (ref_id, dep unary capture id).
    cind_keys = np.unique((cind12_r.astype(np.int64) << 32)
                          | cind12_d.astype(np.int64))
    keep = np.ones(len(cand_dep), bool)
    dcode = cap_code[cand_dep]
    for sub_fn, val in ((cc.first_subcapture, cap_v1[cand_dep]),
                        (cc.second_subcapture, cap_v2[cand_dep])):
        sub_code = np.asarray(sub_fn(dcode))
        sub_ids = _lookup_capture_ids(
            cap_code, cap_v1, cap_v2, sub_code, val,
            np.full(len(cand_dep), NO_VALUE, np.int64))
        present = sub_ids >= 0
        key = (cand_ref.astype(np.int64) << 32) | np.where(present, sub_ids, 0)
        keep &= ~(present & np.isin(key, cind_keys))
    return keep


