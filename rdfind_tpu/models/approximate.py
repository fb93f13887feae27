"""ApproximateAllAtOnce traversal strategy (the reference's id 2).

Two rounds, exact end-to-end (plan/ApproximateAllAtOnceTraversalStrategy.scala:
27-114):

  round 1 — instead of materializing every co-occurrence pair (AllAtOnce), build a
      fixed-width Bloom **refset sketch per dependent capture**: OR the hash bits of
      each join line's captures into a line Bloom, then AND the line Blooms over
      every line containing the dependent (ops/sketch.py).  The AND of Blooms is a
      conservative superset of the Bloom of the exact refset intersection — the
      same guarantee the reference gets from BloomFilter.intersect
      (IntersectHalfApproximateCindCandidates.scala:40-44).
  candidate generation — "which captures r could be in dep d's refset" is answered
      for all (d, r) at once by the bitset-containment **matmul on the MXU**
      (sketch.contains_matrix), tiled over dependents.
  round 2 — exact verification by co-occurrence counting restricted to candidate
      pairs: rows whose capture is neither a candidate dep nor a candidate ref are
      dropped before the quadratic pair emission, surviving pairs are semi-joined
      against the candidate set, and the CIND test cooc(d, r) == |d| runs on exact
      counts (mirrors the re-evaluation round, CreateApproximatedCindCandidates
      .scala:59-163, without its small-join-line skip: counting needs every line).

Design difference vs. the reference, on purpose: the reference keeps small refsets
exact in round 1 and sketches only those above `--exactness-threshold`; here row-1
state is one fixed-shape sketch matrix for ALL dependents (num_caps × bits), which
is the TPU-friendly layout (static shapes, scatter/matmul, no per-evidence variable
width).  False positives cost only round-2 verification work, never correctness, so
raw output is identical to raw AllAtOnce (differential-tested).
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from ..data import CindTable
from ..obs import integrity, metrics
from ..ops import cooc as cooc_ops
from ..ops import frequency, minimality, segments, sketch
from ..runtime import dispatch
from . import allatonce, small_to_large

DEP_TILE = 1 << 12


def _build_sketches(line_val_h, line_cap_h, num_caps, *, bits, num_hashes,
                    row_budget=sketch.BUILD_ROW_BUDGET):
    """Packed (cap_pad, bits//32) refset sketches, RESIDENT ON DEVICE.

    Rows arrive sorted by (join value, capture).  Line Blooms are built per
    line-aligned chunk; dependent sketches are AND-accumulated across chunks
    on device (sketch.intersect_dep_sketches_acc) — nothing is pulled to
    the host during the build (r4 pulled every partial sketch matrix to host
    and ANDed in numpy; VERDICT's first strategy-2 bottleneck).  cap_pad
    follows the cooc padding policy (tile-multiple by default — the
    containment matmul then issues almost no padding rows — pow2-bucketed
    under RDFIND_TILE_SCHEDULE=0); padded captures keep the all-ones
    empty-AND sketch and are masked out by _candidate_pairs' dep/ref masks.
    """
    n = line_val_h.shape[0]
    starts = np.empty(n, bool)
    starts[0] = True
    starts[1:] = line_val_h[1:] != line_val_h[:-1]
    line_gid = np.cumsum(starts, dtype=np.int64) - 1
    line_start_rows = np.flatnonzero(starts)
    num_lines = len(line_start_rows)

    cap_pad = cooc_ops.cap_pad(num_caps)
    sketches = jnp.full((cap_pad, bits // 32), 0xFFFFFFFF, jnp.uint32)
    # Chunk over whole lines so each line's Bloom is complete within its chunk.
    chunk_first_line = 0
    while chunk_first_line < num_lines:
        last = chunk_first_line
        rs = int(line_start_rows[chunk_first_line])
        while last < num_lines:
            re = (int(line_start_rows[last + 1]) if last + 1 < num_lines else n)
            if re - rs > row_budget and last > chunk_first_line:
                break
            last += 1
        re = int(line_start_rows[last]) if last < num_lines else n
        rows = slice(rs, re)
        m = re - rs
        row_cap = segments.pow2_capacity(m)
        lines_cap = segments.pow2_capacity(last - chunk_first_line)
        pad = allatonce._pad_np
        gid_local = (line_gid[rows] - chunk_first_line).astype(np.int32)
        cap_local = line_cap_h[rows]
        valid = jnp.arange(row_cap, dtype=jnp.int32) < m
        gid_d = jnp.asarray(pad(gid_local, row_cap, 0))
        cap_d = jnp.asarray(pad(cap_local, row_cap, 0))
        blooms = sketch.build_line_blooms(
            gid_d, cap_d, valid,
            num_lines=lines_cap, bits=bits, num_hashes=num_hashes)
        sketches = sketch.intersect_dep_sketches_acc(
            sketches, cap_d, blooms[gid_d], valid)
        chunk_first_line = last
    return sketches


@functools.partial(jax.jit, static_argnames=("tile", "bits", "num_hashes"))
def _stage_cand_tile(sketches, lo, dep_ok, ref_ok, ref_ids, ref_pack, *,
                     tile: int, bits: int, num_hashes: int):
    """One (tile x cap_pad) candidate block, bit-packed along refs.

    Slices the device-resident sketch matrix, runs the containment matmul,
    applies the dep/ref masks and the no-self-pair diagonal, and packs —
    the host never sees the bool matrix, only the decoded index pairs
    (the strategy-0 decode discipline, ops/cooc.py).
    """
    tile_sk = jax.lax.dynamic_slice(sketches, (lo, 0),
                                    (tile, sketches.shape[1]))
    cand = sketch.contains_matrix(tile_sk, ref_ids, ref_ok, bits=bits,
                                  num_hashes=num_hashes, ref_pack=ref_pack)
    d_idx = lo + jnp.arange(tile, dtype=jnp.int32)
    cand &= dep_ok[d_idx][:, None]
    cand &= d_idx[:, None] != ref_ids[None, :]
    return cooc_ops.pack_bool(cand)


def _candidate_pairs(sketches, num_caps, *, bits, num_hashes,
                     dep_mask=None, ref_mask=None, dep_tile=DEP_TILE):
    """All (dep, ref) capture-id pairs passing the sketch test, dep != ref.

    Tiled over dependents; each tile is one MXU containment matmul whose
    masked output is bit-packed on device and decoded by popcount + sized
    nonzero (cooc_ops.extract_packed), so only the candidate index pairs
    travel to the host — never the (tile x caps) bool matrix (r4 pulled the
    full uint8 matrix per tile; VERDICT's second strategy-2 bottleneck).
    Optional dep_mask/ref_mask restrict either side (the LateBB rounds).
    """
    cap_pad = sketches.shape[0]
    # Tile width must divide cap_pad: a clamped dynamic_slice start would
    # silently recompute earlier dep rows and mislabel their indices.
    tile = cooc_ops.tile_for(cap_pad, dep_tile)
    ref_ids = jnp.arange(cap_pad, dtype=jnp.int32)
    ref_ok_h = np.zeros(cap_pad, bool)
    ref_ok_h[:num_caps] = True if ref_mask is None else ref_mask[:num_caps]
    ref_ok = jnp.asarray(ref_ok_h)
    dep_ok_h = np.zeros(cap_pad, bool)
    dep_ok_h[:num_caps] = True if dep_mask is None else dep_mask[:num_caps]
    dep_ok = jnp.asarray(dep_ok_h)
    # Pack the shared ref side once; every dep tile reuses it (pallas backend).
    ref_pack = (sketch.pack_ref_bits(ref_ids, bits=bits, num_hashes=num_hashes)
                if sketch.pallas_eligible(bits) else None)
    los = [lo for lo in range(0, num_caps, tile)
           if dep_mask is None or dep_mask[lo:min(lo + tile, num_caps)].any()]

    def make(lo):
        return lambda: (_stage_cand_tile(sketches, jnp.int32(lo), dep_ok,
                                         ref_ok, ref_ids, ref_pack, tile=tile,
                                         bits=bits, num_hashes=num_hashes),
                        min(num_caps - lo, tile), num_caps)

    pairs = cooc_ops.extract_packed_iter([make(lo) for lo in los],
                                         tile * cap_pad)
    out_d = [d + lo for lo, (d, _) in zip(los, pairs) if d.size]
    out_r = [r for _, (d, r) in zip(los, pairs) if d.size]
    if not out_d:
        z = np.zeros(0, np.int64)
        return z, z
    return np.concatenate(out_d), np.concatenate(out_r)


@functools.partial(jax.jit, static_argnames=("tile",))
def _stage_tile_counts(m, dep_lo, d_local, r_idx, valid, *, tile: int):
    """Exact co-occurrence counts for candidate pairs inside one dep tile.

    m: (l_pad, c_pad) bf16 membership matrix; one (tile x c_pad) MXU matmul
    computes the tile's cooc block, then the candidate (dep, ref) positions are
    gathered on device — only the per-pair counts travel back to the host.
    """
    m_tile = jax.lax.dynamic_slice(m, (0, dep_lo), (m.shape[0], tile))
    cooc = cooc_ops.cooc_dot(m_tile, m)
    return jnp.where(valid, cooc[d_local, r_idx], 0)


def _dense_verify_counts(line_val_h, line_cap_h, num_caps, cand_dep, cand_ref,
                         dep_ok, ref_ok, stats, stat_key):
    """Round-2 verification on the dense MXU path: exact cooc counts for the
    candidate pairs, or None when the membership matrix exceeds the HBM budget
    (caller falls back to the chunked loop).

    Same row filter as the chunked backend (_iter_chunk_pairs): rows flagged
    for neither side belong to captures in no candidate pair, so dropping them
    cannot change any candidate's count.  Replaces the per-chunk host loop of
    CreateApproximatedCindCandidates.scala:59-163 with one membership scatter
    plus a tiled matmul sweep — the same stage AllAtOnce verifies with, here
    restricted to the sketch survivors.
    """
    row_keep = dep_ok[line_cap_h] | ref_ok[line_cap_h]
    lv, lc = line_val_h[row_keep], line_cap_h[row_keep]
    n = lv.shape[0]
    if n == 0:
        return np.zeros(len(cand_dep), np.int64)
    starts = np.empty(n, bool)
    starts[0] = True
    starts[1:] = lv[1:] != lv[:-1]
    line_gid = np.cumsum(starts, dtype=np.int64) - 1
    num_lines = int(line_gid[-1]) + 1
    plan = cooc_ops.dense_plan(num_lines, num_caps)
    if plan is None or plan.c_pad > allatonce.SINGLE_SHOT_C:
        return None
    l_pad, c_pad, tile = plan.l_pad, plan.c_pad, plan.tile
    if stats is not None:
        lens = np.diff(np.append(np.flatnonzero(starts), n)).astype(np.int64)
        tot = int((lens * (lens - 1)).sum())
        metrics.counter_add(stats, stat_key, tot)
        metrics.counter_add(stats, "total_pairs", tot)
        metrics.struct_set(stats, "dense_plan", plan.describe())
        metrics.gauge_set(stats, "cooc_dtype", plan.dtype)
        metrics.gauge_set(stats, "plane_bits", plan.plane_bits)
        metrics.struct_set(stats, "kernel_resolution",
                           cooc_ops.resolution_report())

    row_cap = segments.pow2_capacity(n)
    pad = allatonce._pad_np
    m = cooc_ops.build_membership(
        jnp.asarray(pad(line_gid.astype(np.int32), row_cap, l_pad)),
        jnp.asarray(pad(lc.astype(np.int32), row_cap, c_pad)),
        jnp.arange(row_cap, dtype=jnp.int32) < n, l_pad=l_pad, c_pad=c_pad,
        dtype=plan.dtype)

    # Candidates grouped by dep tile (defensive sort: _candidate_pairs emits
    # dep-ascending, but the contract here is order-insensitive).  All tile
    # gathers are dispatched first and pulled in ONE device_get — per-tile
    # pulls cost one host round trip each (r5).
    order = np.argsort(cand_dep, kind="stable")
    d_sorted, r_sorted = cand_dep[order], cand_ref[order]
    cnt_sorted = np.zeros(len(cand_dep), np.int64)
    spans, pulls, pend_bytes = [], [], 0

    def drain():
        nonlocal spans, pulls, pend_bytes
        for (a, b), got in zip(spans, jax.device_get(pulls)):
            cnt_sorted[a:b] = got[:b - a]
        spans, pulls, pend_bytes = [], [], 0

    for lo in plan.dep_tile_starts:
        a = np.searchsorted(d_sorted, lo)
        b = np.searchsorted(d_sorted, lo + tile)
        if a == b:
            continue
        k = b - a
        k_cap = segments.pow2_capacity(k)
        spans.append((a, b))
        pulls.append(_stage_tile_counts(
            m, jnp.int32(lo),
            jnp.asarray(pad((d_sorted[a:b] - lo).astype(np.int32), k_cap, 0)),
            jnp.asarray(pad(r_sorted[a:b].astype(np.int32), k_cap, 0)),
            jnp.arange(k_cap, dtype=jnp.int32) < k, tile=tile))
        # Start the device->host copy the moment the gather is enqueued: the
        # drain's batched device_get then mostly finds the counts already on
        # host while later tiles' matmuls are still computing.
        dispatch.stage_to_host(pulls[-1:])
        # Pending tiles pin padded inputs + outputs on device (~13 bytes per
        # slot); drain under the shared pull budget so huge candidate sets
        # cannot stack GB of buffers next to the near-budget matrix `m`.
        pend_bytes += 13 * k_cap
        if pend_bytes >= cooc_ops.PULL_BYTES_BUDGET:
            drain()
    drain()
    cnt = np.empty_like(cnt_sorted)
    cnt[order] = cnt_sorted
    return cnt


def _record_backend(stats, stat_key, backend):
    """Per-call backend attribution + a run-level scalar ("mixed" when a
    multi-round strategy's rounds land on different backends)."""
    if stats is None:
        return
    metrics.gauge_set(stats, stat_key + "_backend", backend)
    prev = stats.get("pair_backend")
    metrics.gauge_set(stats, "pair_backend",
                      backend if prev in (None, backend) else "mixed")


def verify_candidates(st, cand_dep, cand_ref, min_support, *, pair_backend,
                      pair_chunk_budget, stats, stat_key):
    """Exact verification of candidate (dep, ref) pairs: (d, r, sup) arrays.

    Backend dispatch shared by the approximate and LateBB strategies: the
    dense membership-matmul gather when the plan fits ("auto"/"matmul"),
    otherwise the chunked host loop via _verify_level.
    """
    if len(cand_dep) == 0:
        # No candidates: no pair phase runs on either backend.
        z = np.zeros(0, np.int64)
        return z, z, z
    cnt = None
    if pair_backend in ("auto", "matmul"):
        dep_ok = np.zeros(st["num_caps"], bool)
        dep_ok[cand_dep] = True
        ref_ok = np.zeros(st["num_caps"], bool)
        ref_ok[cand_ref] = True
        cnt = _dense_verify_counts(
            st["line_val_h"], st["line_cap_h"], st["num_caps"],
            cand_dep, cand_ref, dep_ok, ref_ok, stats, stat_key)
        if cnt is None and pair_backend == "matmul":
            raise ValueError("pair_backend='matmul' but the dense plan "
                             "does not fit the single-shot budget")
    if cnt is not None:
        _record_backend(stats, stat_key, "matmul")
        sup_all = st["dep_count"][cand_dep]
        is_cind = (cnt == sup_all) & (sup_all >= min_support)
        is_cind &= ~small_to_large._implied_mask(
            cand_dep, cand_ref, st["cap_code"], st["cap_v1"], st["cap_v2"])
        return cand_dep[is_cind], cand_ref[is_cind], sup_all[is_cind]

    _record_backend(stats, stat_key, "chunked")

    def cooc_fn(dep_ok, ref_ok, key):
        return small_to_large._chunked_cooc(
            st["line_val_h"], st["line_cap_h"], dep_ok, ref_ok,
            pair_chunk_budget, stats, key)

    return small_to_large._verify_level(
        cooc_fn, cand_dep, cand_ref, st["num_caps"], st["dep_count"],
        st["cap_code"], st["cap_v1"], st["cap_v2"], min_support, stat_key)


# Shared phase A lives with the staging code it drives.
prepare_join_lines = allatonce.prepare_join_lines


def discover(triples, min_support: int, projections: str = "spo",
             use_frequent_condition_filter: bool = True,
             use_association_rules: bool = False,
             clean_implied: bool = False,
             pair_chunk_budget: int = allatonce.PAIR_CHUNK_BUDGET,
             sketch_bits: int = sketch.DEFAULT_BITS,
             sketch_hashes: int = sketch.DEFAULT_HASHES,
             pair_backend: str = "auto",
             stats: dict | None = None) -> CindTable:
    """Discover all CINDs; raw output equals allatonce.discover's raw output.

    pair_backend selects the round-2 verification: "matmul" gathers exact
    counts from the dense membership matmul (requires the dense plan to fit),
    "chunked" runs the legacy host chunk loop, "auto" (default) picks matmul
    whenever the membership matrix fits the HBM budget.  Round 1 (the sketch
    build and the candidate containment matmul) is backend-independent.
    """
    if pair_backend not in ("auto", "matmul", "chunked"):
        raise ValueError(f"unknown pair_backend {pair_backend!r}")
    min_support = max(int(min_support), 1)
    use_ars = use_association_rules and use_frequent_condition_filter
    st = prepare_join_lines(triples, min_support, projections,
                            use_frequent_condition_filter, use_ars, stats)
    if st is None:
        return CindTable.empty()

    sketches = _build_sketches(st["line_val_h"], st["line_cap_h"],
                               st["num_caps"], bits=sketch_bits,
                               num_hashes=sketch_hashes)
    # Infrequent captures were row-filtered out of the join lines: their sketches
    # stay all-ones (empty AND) and they can appear in no CIND on either side —
    # mask them out of candidate generation entirely.
    frequent = st["dep_count"] >= min_support
    cand_dep, cand_ref = _candidate_pairs(sketches, st["num_caps"],
                                          bits=sketch_bits,
                                          num_hashes=sketch_hashes,
                                          dep_mask=frequent, ref_mask=frequent)
    if stats is not None:
        metrics.gauge_set(stats, "n_sketch_candidates", len(cand_dep))
    # The sketch matrix is dead past candidate generation; drop the reference
    # so its HBM is free for round 2's membership matrix.
    del sketches

    d, r, sup = verify_candidates(
        st, cand_dep, cand_ref, min_support, pair_backend=pair_backend,
        pair_chunk_budget=pair_chunk_budget, stats=stats,
        stat_key="pairs_verify")

    cap_code, cap_v1, cap_v2 = st["cap_code"], st["cap_v1"], st["cap_v2"]
    table = CindTable(
        dep_code=cap_code[d], dep_v1=cap_v1[d], dep_v2=cap_v2[d],
        ref_code=cap_code[r], ref_v1=cap_v1[r], ref_v2=cap_v2[r],
        support=sup)
    if use_ars:
        rules = frequency.mine_association_rules(st["triples"], min_support)
        if stats is not None:
            metrics.struct_set(stats, "association_rules", rules)
        table = allatonce.filter_ar_implied_cinds(table, rules)
    if clean_implied:
        table = minimality.minimize_table(table)
    integrity.publish_output(stats, table)
    return table
