"""AllAtOnce traversal strategy on a single device.

One pass over the data: emit join candidates, group into join lines, emit all
co-occurrence pairs, count, and read CINDs off the counts.  Mirrors the semantics of
the reference's AllAtOnceTraversalStrategy (plan/AllAtOnceTraversalStrategy.scala:
33-85) with the intersection of evidence refsets replaced by the equivalent
co-occurrence count test (see ops/pairs.py).

Built-in exact pruning that the reference approximates with Bloom filters:
  * frequent-condition prefilter at emission (ops/frequency.py);
  * frequent-*capture* filter before pair emission — a capture with fewer than
    min_support distinct join values can appear in no CIND, on either side (the
    reference's --find-frequent-captures path, RDFind.scala:348-400, optional and
    approximate there; exact and always-on here).

Execution model (the TPU-shaped part): the pipeline is jitted fixed-shape stages
with validity masks.  The host reads a few scalars between stages and pads the next
stage's inputs to a power-of-two capacity, so compiled programs are reused across
datasets and chunk sizes; there is no data-dependent shape inside any stage.

Pair emission is *chunked*: join lines are greedily packed into chunks of at most
PAIR_CHUNK_BUDGET pairs (whole lines stay together), each chunk produces partial
(dep, ref, count) rows, and a final merge stage sums counts across chunks before the
CIND test.  This bounds peak memory on skewed data (quadratic pair counts overflow
int32 and HBM alike), replaces the reference's windowed BulkMergeDependencies
backpressure (candidate_merging/BulkMergeDependencies.scala:48-165), and is the same
merge shape the multi-chip path uses across devices.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import conditions as cc
from ..data import CindTable
from ..obs import datastats, integrity, metrics, tracer
from ..ops import cooc, emission, frequency, minimality, pairs, segments

SENTINEL = segments.SENTINEL

# Max co-occurrence pairs materialized per chunk (before dedup); 2^22 rows ~= 100 MB
# of intermediate sort state -- far below HBM, large enough to keep the MXU-era
# pipeline busy.  A single line larger than the budget still gets its own chunk.
PAIR_CHUNK_BUDGET = 1 << 22


def _pad_np(arr: np.ndarray, capacity: int, fill) -> np.ndarray:
    if arr.shape[0] >= capacity:
        return arr[:capacity]
    return np.concatenate([arr, np.full(capacity - arr.shape[0], fill, arr.dtype)])


def upload_triples(triples: np.ndarray):
    """The (N, 3) int32 triple table padded to a power-of-two row count (so
    compiled stages are reused across inputs) and put on the device."""
    n = triples.shape[0]
    with tracer.span("upload-triples", cat=tracer.CAT_DISPATCH):
        return jnp.asarray(np.pad(
            triples, ((0, segments.pow2_capacity(n) - n), (0, 0)),
            constant_values=np.iinfo(np.int32).max))


def _emit_candidates(triples, n_valid, min_support, projections, use_fc_filter,
                     use_ars):
    """The frequent-condition prefilter, candidate emission and capture
    interning shared by _stage_candidates and _stage_prepare, each under its
    device scope.  Returns (cands, (cap_id, num_caps, cap_code, cap_v1,
    cap_v2)).

    With the filter off the condition sorts still run, at support 1, where
    every valid row passes as with frequency.no_filter: interning reads its
    ranks off them on either path and never sorts the candidate rows.
    """
    with jax.named_scope(tracer.SCOPE_FREQUENT_CONDITIONS):
        valid_t = jnp.arange(triples.shape[0], dtype=jnp.int32) < n_valid
        freq = frequency.triple_frequencies(
            triples, valid_t, min_support if use_fc_filter else 1,
            find_ar_implied=use_ars and use_fc_filter)
    with jax.named_scope(tracer.SCOPE_EMIT_CANDIDATES):
        cands = emission.emit_join_candidates(triples, freq, projections)
    # Capture table in canonical (code, v1, v2) order, matching the
    # reference's Condition.compare.
    with jax.named_scope(tracer.SCOPE_INTERN_CAPTURES):
        return cands, emission.intern_captures(freq, projections)


@functools.partial(jax.jit,
                   static_argnames=("projections", "use_fc_filter", "use_ars"))
def _stage_candidates(triples, n_valid, min_support, *, projections, use_fc_filter,
                      use_ars=False):
    """Triples -> deduped join-line rows (sorted by (value, capture)) + capture table.

    Returns (line_val, line_cap, n_rows, cap_code, cap_v1, cap_v2, num_caps); all
    arrays have capacity 3*|projections|*N with valid data compacted to the front.
    """
    cands, (cap_id, num_caps, cap_code, cap_v1, cap_v2) = _emit_candidates(
        triples, n_valid, min_support, projections, use_fc_filter, use_ars)

    # Join lines: distinct (join value, capture) occurrences, sorted by value.
    with jax.named_scope(tracer.SCOPE_LINE_IDS):
        cap_id_keyed = jnp.where(cands.valid, cap_id, SENTINEL)
        (line_cols, _, _, n_rows) = segments.masked_unique(
            [cands.join_val, cap_id_keyed], cands.valid)

    return (line_cols[0], line_cols[1], n_rows,
            cap_code, cap_v1, cap_v2, num_caps)


@jax.jit
def _stage_capture_filter(line_val, line_cap, n_rows, min_support):
    """Exact capture support + frequent-capture pruning.

    dep_count[c] = number of distinct join values containing capture c (= |c|, the
    capture's true size).  Keeps only rows whose capture is frequent; order stays
    (value, capture) sorted.
    """
    n = line_val.shape[0]
    valid = jnp.arange(n, dtype=jnp.int32) < n_rows
    caps = jnp.where(valid, line_cap, 0)
    dep_count = jax.ops.segment_sum(valid.astype(jnp.int32), caps, num_segments=n)
    keep = valid & (dep_count[caps] >= min_support)
    (out_val, out_cap), n_keep = segments.compact([line_val, line_cap], keep)
    return out_val, out_cap, n_keep, dep_count


@functools.partial(jax.jit,
                   static_argnames=("projections", "use_fc_filter", "use_ars"))
def _stage_prepare(triples, n_valid, min_support, *, projections, use_fc_filter,
                   use_ars=False):
    """Candidate emission + capture interning + dense line ids, for the dense
    cooc path.  No host row data, and the 3|projections|N candidate rows are
    never sorted: capture ids come from the frequent-condition sorts, line
    ids from one sort of the |projections|N join-value rows
    (emission.intern_captures, emission.candidate_line_ids).

    Unlike the chunked pipeline, this deliberately skips BOTH the
    (value, capture) row dedupe (the membership scatter's .set(1) dedupes for
    free) and the frequent-capture row filter: containment forces
    |ref| >= |dep| = support >= min_support, so infrequent captures can never
    survive the CIND test on either side — they are just dead columns of M.
    dep_count and per-line lengths fall out of M as column/row sums
    (_stage_membership).

    Returns (line_gid, cap_id, valid, n_lines, cap_code, cap_v1, cap_v2,
    num_caps): line_gid of shape (|projections|, N), one line id per
    projection and triple row (emission.candidate_line_ids), the rest at
    candidate-row capacity.
    """
    cands, (cap_id, num_caps, cap_code, cap_v1, cap_v2) = _emit_candidates(
        triples, n_valid, min_support, projections, use_fc_filter, use_ars)
    with jax.named_scope(tracer.SCOPE_LINE_IDS):
        line_gid, n_lines = emission.candidate_line_ids(cands,
                                                        triples.shape[0])
    return (line_gid, cap_id, cands.valid, n_lines,
            cap_code, cap_v1, cap_v2, num_caps)


@functools.partial(jax.jit,
                   static_argnames=("l_pad", "c_pad", "membership_dtype"))
def _stage_membership(line_gid, cap_id, valid, min_support, *, l_pad, c_pad,
                      membership_dtype):
    """Membership matrix + the aggregates that fall out of it.

    line_gid is _stage_prepare's (|projections|, N) line ids; each candidate
    row takes its own inside the membership scatter.

    `membership_dtype` (callers pass the dense plan's resolved dtype) is
    load-bearing: it
    both keys this jit's cache and selects the dtype build_membership
    actually uses (inlined here, the inputs' avals don't carry it).

    Returns (m, dep_count, lens): dep_count[c] = distinct join values
    containing capture c (column sums — exact in f32 below 2^24 lines);
    lens[l] = frequent captures in line l (matvec against the frequency mask),
    matching the chunked path's per-line pair accounting.
    """
    with jax.named_scope(tracer.SCOPE_MEMBERSHIP):
        m = cooc.build_membership(emission.candidate_row_ids(line_gid),
                                  cap_id, valid, l_pad=l_pad,
                                  c_pad=c_pad, dtype=membership_dtype)
        acc = jnp.int32 if m.dtype == jnp.int8 else jnp.float32
        dep_count = jnp.sum(m, axis=0, dtype=acc).astype(jnp.int32)
        freq_mask = (dep_count >= min_support).astype(m.dtype)
        lens = cooc.cooc_dot(m, freq_mask, dims=((1,), (0,)))
    return m, dep_count, lens


# One-shot cooc ceiling: the full (c_pad, c_pad) f32 cooc block.  16384^2 f32
# = 1 GB — past that, fall back to the tiled host loop.
SINGLE_SHOT_C = 16384


@functools.partial(jax.jit,
                   static_argnames=("l_pad", "c_pad", "membership_dtype"))
def _stage_dense_all(line_gid, cap_id, valid, min_support,
                     cap_code, cap_v1, cap_v2, *, l_pad, c_pad,
                     membership_dtype):
    """Membership + full cooc + CIND test + bit-pack, fused in one dispatch.

    Fusing everything after candidate prep keeps host round trips out of
    the loop: one dispatch, then one bundled pull of (packed bits, dep_count,
    lens) — per-dispatch latency was a third of the r2.5 wall clock.
    """
    m, dep_count, lens = _stage_membership(line_gid, cap_id, valid, min_support,
                                           l_pad=l_pad, c_pad=c_pad,
                                           membership_dtype=membership_dtype)
    packed = cooc.cooc_cind_tile(
        m, jnp.int32(0), dep_count,
        _fit_device(cap_code, c_pad), _fit_device(cap_v1, c_pad),
        _fit_device(cap_v2, c_pad), min_support, tile=c_pad)
    # int32 is exact: the bit matrix has at most SINGLE_SHOT_C^2 = 2^28 bits.
    n_cinds = jax.lax.population_count(packed).sum(dtype=jnp.int32)
    return packed, dep_count, lens, n_cinds




def _fit_device(arr, length: int):
    """Slice-or-pad a 1-D device array to `length` without a host round trip."""
    if arr.shape[0] >= length:
        return jax.lax.slice(arr, (0,), (length,))
    return jnp.pad(arr, (0, length - arr.shape[0]))


@functools.partial(jax.jit, static_argnames=("capacity",))
def _stage_pair_counts(line_cap, pos, length, start_idx, *, capacity):
    """One chunk: emit pairs, dedupe, count.  Returns (dep, ref, cnt, n_pairs)
    compacted to the front (cnt = co-occurrence count within this chunk)."""
    row, partner, pair_valid = pairs.emit_pair_indices(pos, length, start_idx, capacity)
    dep = jnp.where(pair_valid, line_cap[row], SENTINEL)
    ref = jnp.where(pair_valid, line_cap[partner], SENTINEL)
    perm = segments.lexsort([dep, ref])
    ds, rs, vs = dep[perm], ref[perm], pair_valid[perm]
    starts = segments.run_starts([ds, rs]) & vs
    gid = jnp.cumsum(starts).astype(jnp.int32) - 1
    cnt = jax.ops.segment_sum(vs.astype(jnp.int32), gid, num_segments=capacity)[gid]
    (d_out, r_out, c_out), n_out = segments.compact([ds, rs, cnt], starts)
    return d_out, r_out, c_out, n_out


@jax.jit
def _stage_merge(dep, ref, cnt, n_valid, min_support, dep_count,
                 cap_code, cap_v1, cap_v2):
    """Merge per-chunk pair counts, apply the CIND test, drop implied pairs.

    Returns (dep_id, ref_id, support, n_cinds) compacted to the front.
    """
    m = dep.shape[0]
    valid = jnp.arange(m, dtype=jnp.int32) < n_valid
    dep = jnp.where(valid, dep, SENTINEL)
    ref = jnp.where(valid, ref, SENTINEL)
    perm = segments.lexsort([dep, ref])
    ds, rs, vs = dep[perm], ref[perm], valid[perm]
    cs = jnp.where(vs, cnt[perm], 0)
    starts = segments.run_starts([ds, rs]) & vs
    gid = jnp.cumsum(starts).astype(jnp.int32) - 1
    cooc = jax.ops.segment_sum(cs, gid, num_segments=m)[gid]

    nc = cap_code.shape[0]
    d_safe = jnp.clip(ds, 0, nc - 1)
    r_safe = jnp.clip(rs, 0, nc - 1)
    support = dep_count[jnp.clip(ds, 0, dep_count.shape[0] - 1)]
    is_cind = (cooc == support) & (support >= min_support)

    # Trivially implied pairs (data/Condition.scala:35-43 semantics, including the
    # equal-code quirk pinned in tests/test_oracle.py).
    d_code, r_code = cap_code[d_safe], cap_code[r_safe]
    implied = cc.is_subcode(r_code, d_code) & jnp.where(
        cc.first_subcapture(d_code) == r_code,
        cap_v1[r_safe] == cap_v1[d_safe],
        cap_v1[r_safe] == cap_v2[d_safe])

    keep = starts & is_cind & ~implied
    (d_out, r_out, s_out), n_out = segments.compact([ds, rs, support], keep)
    return d_out, r_out, s_out, n_out


@functools.partial(jax.jit,
                   static_argnames=("projections", "use_fc_filter", "pair_capacity"))
def fused_step(triples, n_valid, min_support, *, projections="spo",
               use_fc_filter=True, pair_capacity=1 << 18):
    """The whole single-device discovery step as ONE jitted program (no host syncs).

    This is the compile-check entry point (__graft_entry__.entry) and the inner body
    a future scan-over-chunks uses.  `pair_capacity` statically bounds materialized
    pairs; the returned `overflow` is the number of truncated pairs (callers retry
    with a larger capacity or fall back to the chunked `discover`).

    Returns (dep_code, dep_v1, dep_v2, ref_code, ref_v1, ref_v2, support, n_cinds,
    overflow) with CIND rows compacted to the front of capacity-sized arrays.
    """
    line_val, line_cap, n_rows, cap_code, cap_v1, cap_v2, _ = _stage_candidates(
        triples, n_valid, min_support, projections=projections,
        use_fc_filter=use_fc_filter)
    line_val, line_cap, n_keep, dep_count = _stage_capture_filter(
        line_val, line_cap, n_rows, min_support)
    pos, length, start_idx, total_pairs = pairs.line_layout(line_val, n_keep)
    overflow = jnp.maximum(total_pairs - pair_capacity, 0)
    dep, ref, cnt, n_pairs = _stage_pair_counts(
        line_cap, pos, length, start_idx, capacity=pair_capacity)
    d_out, r_out, s_out, n_out = _stage_merge(
        dep, ref, cnt, n_pairs, min_support, dep_count, cap_code, cap_v1, cap_v2)
    return (cap_code[jnp.clip(d_out, 0, cap_code.shape[0] - 1)],
            cap_v1[jnp.clip(d_out, 0, cap_v1.shape[0] - 1)],
            cap_v2[jnp.clip(d_out, 0, cap_v2.shape[0] - 1)],
            cap_code[jnp.clip(r_out, 0, cap_code.shape[0] - 1)],
            cap_v1[jnp.clip(r_out, 0, cap_v1.shape[0] - 1)],
            cap_v2[jnp.clip(r_out, 0, cap_v2.shape[0] - 1)],
            s_out, n_out, overflow)


def prepare_join_lines(triples, min_support, projections,
                       use_frequent_condition_filter, use_ars, stats):
    """Shared phase A of every strategy: join-line rows + capture table.

    Runs _stage_candidates + _stage_capture_filter and pulls the results to host.
    Returns None when the plan is trivially empty, else a dict with the triples,
    the (value, capture)-sorted frequent join-line rows, the canonical capture
    table columns, per-capture exact supports, and num_caps.
    """
    triples = np.asarray(triples, np.int32)
    n = triples.shape[0]
    if n == 0 or not any(ch in projections for ch in "spo"):
        return None
    padded = upload_triples(triples)
    (line_val, line_cap, n_rows, cap_code_d, cap_v1_d, cap_v2_d, num_caps) = \
        _stage_candidates(padded, jnp.int32(n), jnp.int32(min_support),
                          projections=projections,
                          use_fc_filter=use_frequent_condition_filter,
                          use_ars=use_ars)
    n_rows = int(n_rows)
    if n_rows == 0:
        return None
    cap_l = segments.pow2_capacity(n_rows)
    line_val, line_cap, n_keep, dep_count_d = _stage_capture_filter(
        jnp.asarray(_pad_np(np.asarray(line_val), cap_l, SENTINEL)),
        jnp.asarray(_pad_np(np.asarray(line_cap), cap_l, SENTINEL)),
        jnp.int32(n_rows), jnp.int32(min_support))
    n_keep = int(n_keep)
    num_caps = int(num_caps)
    if n_keep == 0 or num_caps == 0:
        return None
    state = dict(
        triples=triples,
        line_val_h=np.asarray(line_val)[:n_keep],
        line_cap_h=np.asarray(line_cap)[:n_keep],
        cap_code=np.asarray(cap_code_d)[:num_caps].astype(np.int64),
        cap_v1=np.asarray(cap_v1_d)[:num_caps].astype(np.int64),
        cap_v2=np.asarray(cap_v2_d)[:num_caps].astype(np.int64),
        dep_count=np.asarray(dep_count_d)[:num_caps].astype(np.int64),
        num_caps=num_caps)
    if stats is not None:
        metrics.set_many(stats, n_triples=n, n_line_rows=n_rows,
                         n_frequent_rows=n_keep, n_captures=num_caps,
                         total_pairs=0)
        if datastats.enabled():
            # line_val_h is (value, capture)-sorted: run lengths ARE the
            # join-line sizes.
            lens = np.unique(state["line_val_h"], return_counts=True)[1]
            datastats.publish_line_stats(
                stats, hist=datastats.log2_bucket_counts(lens),
                n_lines=int(lens.size),
                max_line=int(lens.max()) if lens.size else 0,
                source="single")
            datastats.publish_capture_spectrum(
                stats, hist=datastats.log2_bucket_counts(state["dep_count"]),
                n_captures=num_caps,
                max_support=int(state["dep_count"].max()), source="single")
    return state


def filter_ar_implied_cinds(table: CindTable, mined_rules) -> CindTable:
    """Drop 1/1 CIND pairs that restate a perfect-confidence association rule.

    Mirrors the evidence-level exclusion (CreateDependencyCandidates.scala:125-130
    with its AR broadcast initializer :164-178, and FilterAssociationRuleImpliedCinds
    .scala:30-58): the pair (dep=antecedent capture, ref=consequent capture) with the
    shared third-field projection is suppressed.  `mined_rules` comes from
    frequency.mine_association_rules.
    """
    if len(table) == 0:
        return table
    keep = ~frequency.ar_implied_pair_mask(
        table.dep_code, table.ref_code, table.dep_v1, table.ref_v1, mined_rules)
    return CindTable(*(np.asarray(c)[keep] for c in (
        table.dep_code, table.dep_v1, table.dep_v2,
        table.ref_code, table.ref_v1, table.ref_v2, table.support)))


def _discover_dense(triples, padded, n, min_support, projections, use_fc_filter,
                    use_ars, clean_implied, stats):
    """Dense cooc-matmul discovery (ops/cooc.py).  Returns None when the
    membership matrix exceeds the HBM budget (caller falls back to chunked).

    Host traffic is scalars, per-line lengths, the packed CIND bits, and the
    final capture-table columns — never the row arrays.
    """
    (line_gid, cap_id, cand_valid, n_lines_d, cap_code, cap_v1, cap_v2,
     num_caps_d) = _stage_prepare(
        padded, jnp.int32(n), jnp.int32(min_support), projections=projections,
        use_fc_filter=use_fc_filter, use_ars=use_ars)
    # The first pull waits for the whole join-line build.
    with tracer.span("pull-prepare-counts", cat=tracer.CAT_PULL):
        n_lines, num_caps = (int(x) for x in
                             jax.device_get((n_lines_d, num_caps_d)))
    if n_lines == 0 or num_caps == 0:
        return CindTable.empty()
    plan = cooc.dense_plan(n_lines, num_caps)
    if plan is None:
        return None
    l_pad, c_pad, tile = plan.l_pad, plan.c_pad, plan.tile
    if stats is not None:
        metrics.struct_set(stats, "dense_plan", plan.describe())
        metrics.gauge_set(stats, "cooc_dtype", plan.dtype)
        metrics.gauge_set(stats, "plane_bits", plan.plane_bits)
        metrics.gauge_set(stats, "fuse_verdict", plan.fuse_verdict)
        metrics.struct_set(stats, "kernel_resolution",
                           cooc.resolution_report())

    # The fused-verdict sweep always runs tiled (its kernel is the tile
    # dispatch); the one-dispatch single-shot program is the materialized
    # path's latency optimization.
    if c_pad <= SINGLE_SHOT_C and not plan.fuse_verdict:
        packed, dep_count, lens, n_bits = _stage_dense_all(
            line_gid, cap_id, cand_valid, jnp.int32(min_support),
            cap_code, cap_v1, cap_v2, l_pad=l_pad, c_pad=c_pad,
            membership_dtype=plan.dtype)
        # Two-dispatch pair extraction: pull the exact CIND count (8 bytes,
        # fused into the main dispatch), then pull only that many (dep, ref)
        # indices — never the bit matrix (cooc.extract_packed's rationale).
        with tracer.span("pull-cind-count", cat=tracer.CAT_PULL):
            n_cinds = int(jax.device_get(n_bits))
        pulls = [jax.lax.slice(lens, (0,), (n_lines,)),
                 jax.lax.slice(dep_count, (0,), (num_caps,)),
                 cap_code[:num_caps], cap_v1[:num_caps], cap_v2[:num_caps]]
        if n_cinds:
            pulls += cooc.packed_nonzero(
                packed, jnp.int32(packed.shape[0]),
                jnp.int32(packed.shape[1] * 32),
                cap=segments.pow2_capacity(n_cinds))
        else:
            pulls += [np.zeros(0, np.int32)] * 2
        with tracer.span("pull-cind-ids", cat=tracer.CAT_PULL):
            (lens_h, dep_count_h, code_h, v1_h, v2_h, dep_id, ref_id) = \
                jax.device_get(pulls)
        lens_h = lens_h.astype(np.int64)
        dep_id = dep_id[:n_cinds].astype(np.int64)
        ref_id = ref_id[:n_cinds].astype(np.int64)
        support = dep_count_h[dep_id]
    else:
        m, dep_count, lens = _stage_membership(
            line_gid, cap_id, cand_valid, jnp.int32(min_support),
            membership_dtype=plan.dtype,
            l_pad=l_pad, c_pad=c_pad)
        with tracer.span("pull-line-lengths", cat=tracer.CAT_PULL):
            lens_h = np.asarray(jax.lax.slice(lens, (0,), (n_lines,)),
                                np.int64)
        dep_id, ref_id, support = cooc.discover_pairs_dense(
            m, dep_count, _fit_device(cap_code, c_pad),
            _fit_device(cap_v1, c_pad), _fit_device(cap_v2, c_pad),
            min_support, num_caps, tile, starts=plan.dep_tile_starts,
            plan=plan, stats=stats)
        with tracer.span("pull-capture-table", cat=tracer.CAT_PULL):
            (code_h, v1_h, v2_h, dep_count_h) = jax.device_get(
                (cap_code[:num_caps], cap_v1[:num_caps], cap_v2[:num_caps],
                 jax.lax.slice(dep_count, (0,), (num_caps,))))

    # Host reductions over the per-line vector, while the device idles.
    with tracer.span("line-stats", cat=tracer.CAT_STAGE):
        total_pairs = int((lens_h * (lens_h - 1)).sum())
        if stats is not None:
            # Stat semantics match the chunked backend: n_lines counts lines
            # that kept >= 1 frequent capture, n_line_rows the deduped
            # (value, capture) rows (= total memberships, the column-sum
            # total of M).
            metrics.set_many(
                stats, n_triples=n, n_frequent_rows=int(lens_h.sum()),
                n_line_rows=int(np.asarray(dep_count_h, np.int64).sum()),
                n_lines=int((lens_h > 0).sum()), n_captures=num_caps,
                total_pairs=total_pairs,
                max_line=int(lens_h.max()) if n_lines else 0,
                pair_backend="matmul",
                join_sort_rows=emission.join_sort_rows(padded.shape[0],
                                                       projections))
            if datastats.enabled():
                datastats.publish_line_stats(
                    stats, hist=datastats.log2_bucket_counts(lens_h),
                    n_lines=int((lens_h > 0).sum()),
                    max_line=int(lens_h.max()) if n_lines else 0,
                    source="single")
                sup = np.asarray(dep_count_h, np.int64)
                datastats.publish_capture_spectrum(
                    stats, hist=datastats.log2_bucket_counts(sup),
                    n_captures=num_caps,
                    max_support=int(sup.max()) if sup.size else 0,
                    source="single")
    if dep_id.size == 0:
        return CindTable.empty()
    table = CindTable(
        dep_code=code_h[dep_id].astype(np.int64),
        dep_v1=v1_h[dep_id].astype(np.int64),
        dep_v2=v2_h[dep_id].astype(np.int64),
        ref_code=code_h[ref_id].astype(np.int64),
        ref_v1=v1_h[ref_id].astype(np.int64),
        ref_v2=v2_h[ref_id].astype(np.int64),
        support=support.astype(np.int64),
    )
    return _postprocess(table, triples, min_support, use_ars, clean_implied,
                        stats)


def _postprocess(table, triples, min_support, use_ars, clean_implied, stats):
    """The AR filter, minimality and the output digest, as one stage span."""
    with tracer.span("postprocess", cat=tracer.CAT_STAGE):
        if use_ars:
            rules = frequency.mine_association_rules(triples, min_support)
            if stats is not None:
                metrics.struct_set(stats, "association_rules", rules)
            table = filter_ar_implied_cinds(table, rules)
        if clean_implied:
            table = minimality.minimize_table(table)
        integrity.publish_output(stats, table)
    return table


def _chunk_boundaries(pairs_per_line: np.ndarray, budget: int) -> list[int]:
    """Greedy packing of whole lines into chunks of <= budget pairs each.

    Returns line-index boundaries [0, ..., num_lines]; a single line over budget
    gets its own chunk.
    """
    bounds = [0]
    acc = 0
    for i, p in enumerate(pairs_per_line):
        if acc > 0 and acc + p > budget:
            bounds.append(i)
            acc = 0
        acc += int(p)
    bounds.append(len(pairs_per_line))
    return bounds


def discover(triples, min_support: int, projections: str = "spo",
             use_frequent_condition_filter: bool = True,
             use_association_rules: bool = False,
             clean_implied: bool = False,
             pair_chunk_budget: int = PAIR_CHUNK_BUDGET,
             pair_backend: str = "auto",
             stats: dict | None = None) -> CindTable:
    """Discover all CINDs in an (N, 3) int32 triple-id table.

    If `stats` is a dict, it is filled with pipeline statistics (candidate rows,
    join lines, total co-occurrence pairs checked, chunks) — the accumulator/counter
    role of the reference's CountItems operators (operators/CountItems.scala:11-33).

    pair_backend selects the quadratic phase: "matmul" runs the dense
    co-occurrence matmul (ops/cooc.py — the MXU path), "chunked" the legacy
    sort-and-count chunk loop, "auto" (default) picks matmul whenever the
    membership matrix fits the HBM budget.
    """
    triples = np.asarray(triples, np.int32)
    n = triples.shape[0]
    if n == 0 or not any(ch in projections for ch in "spo"):
        return CindTable.empty()
    min_support = max(int(min_support), 1)

    padded = upload_triples(triples)
    use_ars = use_association_rules and use_frequent_condition_filter

    if pair_backend in ("auto", "matmul"):
        # Whether the dense plan fits is only known after candidate prep
        # (n_lines/num_caps are data-dependent), so a fallback to chunked pays
        # candidate emission + interning twice.  Callers that know their data
        # exceeds the membership budget should pass pair_backend="chunked".
        table = _discover_dense(triples, padded, n, min_support, projections,
                                use_frequent_condition_filter, use_ars,
                                clean_implied, stats)
        if table is not None:
            return table
        if pair_backend == "matmul":
            raise ValueError("pair_backend='matmul' but the dense plan "
                             "does not fit the HBM budget")

    (line_val, line_cap, n_rows, cap_code, cap_v1, cap_v2, num_caps) = \
        _stage_candidates(padded, jnp.int32(n), jnp.int32(min_support),
                          projections=projections,
                          use_fc_filter=use_frequent_condition_filter,
                          use_ars=use_ars)
    n_rows = int(n_rows)
    if n_rows == 0:
        return CindTable.empty()

    cap_l = segments.pow2_capacity(n_rows)
    line_val, line_cap, n_keep, dep_count = _stage_capture_filter(
        jnp.asarray(_pad_np(np.asarray(line_val), cap_l, SENTINEL)),
        jnp.asarray(_pad_np(np.asarray(line_cap), cap_l, SENTINEL)),
        jnp.int32(n_rows), jnp.int32(min_support))
    n_keep = int(n_keep)
    if n_keep == 0:
        return CindTable.empty()

    # Host-side line layout (int64-safe) + greedy chunking over whole lines.
    line_val_h = np.asarray(line_val)[:n_keep]
    line_cap_h = np.asarray(line_cap)[:n_keep]
    starts_h = np.empty(n_keep, bool)
    starts_h[0] = True
    starts_h[1:] = line_val_h[1:] != line_val_h[:-1]
    line_start_rows = np.flatnonzero(starts_h)
    line_lens = np.diff(np.append(line_start_rows, n_keep)).astype(np.int64)
    pairs_per_line = line_lens * (line_lens - 1)
    if stats is not None:
        metrics.set_many(
            stats, n_triples=n, n_line_rows=n_rows, n_frequent_rows=n_keep,
            n_lines=int(line_lens.shape[0]), n_captures=int(num_caps),
            total_pairs=int(pairs_per_line.sum()),
            max_line=int(line_lens.max()) if line_lens.size else 0)
        if datastats.enabled():
            datastats.publish_line_stats(
                stats, hist=datastats.log2_bucket_counts(line_lens),
                n_lines=int(line_lens.shape[0]),
                max_line=int(line_lens.max()) if line_lens.size else 0,
                source="single")
            sup = np.asarray(dep_count)[:int(num_caps)]
            datastats.publish_capture_spectrum(
                stats, hist=datastats.log2_bucket_counts(sup),
                n_captures=int(num_caps),
                max_support=int(sup.max()) if sup.size else 0,
                source="single")
    if int(pairs_per_line.sum()) == 0:
        return CindTable.empty()

    num_caps = int(num_caps)
    if stats is not None:
        metrics.gauge_set(stats, "pair_backend", "chunked")
    pos_h = (np.arange(n_keep, dtype=np.int64)
             - np.repeat(line_start_rows, line_lens)).astype(np.int32)
    len_h = np.repeat(line_lens, line_lens).astype(np.int32)

    bounds = _chunk_boundaries(pairs_per_line, pair_chunk_budget)
    parts_d, parts_r, parts_c = [], [], []
    for bi in range(len(bounds) - 1):
        lo_line, hi_line = bounds[bi], bounds[bi + 1]
        if lo_line == hi_line:
            continue
        rs = int(line_start_rows[lo_line])
        re = int(line_start_rows[hi_line]) if hi_line < len(line_start_rows) else n_keep
        chunk_pairs = int(pairs_per_line[lo_line:hi_line].sum())
        if chunk_pairs == 0:
            continue
        row_cap = segments.pow2_capacity(re - rs)
        pair_cap = segments.pow2_capacity(chunk_pairs)
        d, r, c, n_out = _stage_pair_counts(
            jnp.asarray(_pad_np(line_cap_h[rs:re], row_cap, SENTINEL)),
            jnp.asarray(_pad_np(pos_h[rs:re], row_cap, 0)),
            jnp.asarray(_pad_np(len_h[rs:re], row_cap, 1)),
            jnp.asarray(_pad_np(
                (np.arange(rs, re, dtype=np.int32) - pos_h[rs:re]) - rs, row_cap, 0)),
            capacity=pair_cap)
        n_out = int(n_out)
        parts_d.append(np.asarray(d)[:n_out])
        parts_r.append(np.asarray(r)[:n_out])
        parts_c.append(np.asarray(c)[:n_out])

    all_d = np.concatenate(parts_d) if parts_d else np.zeros(0, np.int32)
    if all_d.shape[0] == 0:
        return CindTable.empty()
    all_r = np.concatenate(parts_r)
    all_c = np.concatenate(parts_c)
    cap_m = segments.pow2_capacity(all_d.shape[0])
    d_out, r_out, s_out, n_out = _stage_merge(
        jnp.asarray(_pad_np(all_d, cap_m, SENTINEL)),
        jnp.asarray(_pad_np(all_r, cap_m, SENTINEL)),
        jnp.asarray(_pad_np(all_c, cap_m, 0)),
        jnp.int32(all_d.shape[0]), jnp.int32(min_support), dep_count,
        cap_code, cap_v1, cap_v2)
    n_out = int(n_out)
    if n_out == 0:
        return CindTable.empty()

    dep_id = np.asarray(d_out[:n_out])
    ref_id = np.asarray(r_out[:n_out])
    support = np.asarray(s_out[:n_out])
    cap_code = np.asarray(cap_code[:num_caps])
    cap_v1 = np.asarray(cap_v1[:num_caps])
    cap_v2 = np.asarray(cap_v2[:num_caps])
    table = CindTable(
        dep_code=cap_code[dep_id].astype(np.int64),
        dep_v1=cap_v1[dep_id].astype(np.int64),
        dep_v2=cap_v2[dep_id].astype(np.int64),
        ref_code=cap_code[ref_id].astype(np.int64),
        ref_v1=cap_v1[ref_id].astype(np.int64),
        ref_v2=cap_v2[ref_id].astype(np.int64),
        support=support.astype(np.int64),
    )
    return _postprocess(table, triples, min_support, use_ars, clean_implied,
                        stats)
