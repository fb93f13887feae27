"""Implied-CIND removal (--clean-implied) as device sort-merge joins.

The reference's minimality cleanup is four distributed coGroups
(plan/TraversalStrategy.scala:126-168 with RemoveNonMinimalDoubleXxxCinds /
RemoveNonMinimalXxxSingleCinds): a CIND is dropped when a *directly* implying
CIND exists —

  pass A: a 2/1 whose dep has a unary subcapture forming a 1/1 with the same ref;
  pass B: a 2/1 whose ref is a unary subcapture of a 2/2's ref with the same dep;
  pass C: a 1/1 whose ref is a unary subcapture of a 1/2's ref with the same dep;
  pass D: a 2/2 whose dep has a unary subcapture forming a 1/2 with the same ref.

All 1/2 CINDs are kept, and only direct implications are checked (the
reference's documented limitation) — oracle.minimize_cinds is the independent
host-set-algebra cross-check, used by tests only.

TPU formulation: all four passes are membership tests of 6-column keys, so
they fuse into ONE tag-sorted merge join — keys carry a pass-id column, the
implying side and the query side are each 6 fixed n-row segments, and a single
masked_unique + masked_table_index answers every pass at once (one device sort
over 12n rows instead of 4 hash joins).

Sharded (--dop > 1): both sides are hash-partitioned by key to their owner
device (parallel/exchange.route), the owner joins locally, and verdicts ride
the reply collective back to the asking rows — the coGroup recast as a
fixed-capacity exchange with the usual measured-capacity + overflow-retry
contract.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from .. import conditions as cc
from ..data import NO_VALUE, CindTable
from ..obs import tracer
from . import hashing, segments

_N_SEG = 6  # key segments per side (see _implying_keys/_query_keys)


def _families(dep_code, ref_code, valid):
    dep_bin = cc.is_binary(dep_code)
    ref_bin = cc.is_binary(ref_code)
    return dict(
        f11=valid & ~dep_bin & ~ref_bin,
        f12=valid & ~dep_bin & ref_bin,
        f21=valid & dep_bin & ~ref_bin,
        f22=valid & dep_bin & ref_bin,
    )


def _implying_keys(cols, fam):
    """(7 key columns, valid) of the implying side: 6 segments of n rows.

    Segment layout (pass id first key column):
      A (0): 1/1 rows as   (ref, dep)
      B (1): 2/2 rows as   (dep, ref-subcapture-q)   for q = 1, 2
      C (2): 1/2 rows as   (dep, ref-subcapture-q)   for q = 1, 2
      D (3): 1/2 rows as   (ref, dep)
    """
    dc, d1, d2, rc, r1, r2 = cols
    no_v = jnp.full_like(d1, NO_VALUE)
    sub1_r, sub2_r = cc.first_subcapture(rc), cc.second_subcapture(rc)
    segs = [
        (0, fam["f11"], (rc, r1, r2, dc, d1, d2)),
        (1, fam["f22"], (dc, d1, d2, sub1_r, r1, no_v)),
        (1, fam["f22"], (dc, d1, d2, sub2_r, r2, no_v)),
        (2, fam["f12"], (dc, d1, d2, sub1_r, r1, no_v)),
        (2, fam["f12"], (dc, d1, d2, sub2_r, r2, no_v)),
        (3, fam["f12"], (rc, r1, r2, dc, d1, d2)),
    ]
    return _stack_segments(segs)


def _query_keys(cols, fam):
    """(7 key columns, valid) of the query side: 6 segments of n rows.

    Segment layout (matches _implying_keys pass ids):
      A (0): 2/1 rows as   (ref, dep-subcapture-q)   for q = 1, 2
      B (1): 2/1 rows as   (dep, ref)
      C (2): 1/1 rows as   (dep, ref)
      D (3): 2/2 rows as   (ref, dep-subcapture-q)   for q = 1, 2
    """
    dc, d1, d2, rc, r1, r2 = cols
    no_v = jnp.full_like(d1, NO_VALUE)
    sub1_d, sub2_d = cc.first_subcapture(dc), cc.second_subcapture(dc)
    segs = [
        (0, fam["f21"], (rc, r1, r2, sub1_d, d1, no_v)),
        (0, fam["f21"], (rc, r1, r2, sub2_d, d2, no_v)),
        (1, fam["f21"], (dc, d1, d2, rc, r1, r2)),
        (2, fam["f11"], (dc, d1, d2, rc, r1, r2)),
        (3, fam["f22"], (rc, r1, r2, sub1_d, d1, no_v)),
        (3, fam["f22"], (rc, r1, r2, sub2_d, d2, no_v)),
    ]
    return _stack_segments(segs)


def _stack_segments(segs):
    n = segs[0][2][0].shape[0]
    pass_col = jnp.concatenate(
        [jnp.full(n, p, jnp.int32) for p, _, _ in segs])
    key_cols = [pass_col] + [
        jnp.concatenate([s[2][i] for s in segs]) for i in range(6)]
    valid = jnp.concatenate([s[1] for s in segs])
    return key_cols, valid


def _keep_from_found(found, fam, valid, n):
    """Fold the 6 query-segment verdicts back to a per-row keep mask."""
    seg = [found[i * n:(i + 1) * n] for i in range(_N_SEG)]
    killed = (seg[0] | seg[1] | seg[2]   # 2/1 via pass A (two subqueries) + B
              | seg[3]                   # 1/1 via pass C
              | seg[4] | seg[5])         # 2/2 via pass D (two subqueries)
    return valid & ~killed


@jax.jit
def _stage_keep_mask(dep_code, dep_v1, dep_v2, ref_code, ref_v1, ref_v2,
                     n_valid):
    """Single-device keep mask over pow2-padded columns."""
    with jax.named_scope(tracer.SCOPE_MINIMALITY):
        n = dep_code.shape[0]
        valid = jnp.arange(n, dtype=jnp.int32) < n_valid
        cols = (dep_code, dep_v1, dep_v2, ref_code, ref_v1, ref_v2)
        fam = _families(dep_code, ref_code, valid)
        imp_cols, imp_valid = _implying_keys(cols, fam)
        qry_cols, qry_valid = _query_keys(cols, fam)
        tab_cols, _, _, n_tab = segments.masked_unique(imp_cols, imp_valid)
        found = segments.masked_table_index(tab_cols, n_tab, qry_cols,
                                            qry_valid) >= 0
        return _keep_from_found(found, fam, valid, n)


def _pad_cols(table: CindTable):
    """CindTable -> (6 pow2-padded int32 device columns, n)."""
    n = len(table)
    cap = segments.pow2_capacity(n)
    out = []
    for c in (table.dep_code, table.dep_v1, table.dep_v2,
              table.ref_code, table.ref_v1, table.ref_v2):
        a = np.full(cap, segments.SENTINEL, np.int32)
        a[:n] = np.asarray(c, np.int64).astype(np.int32)
        out.append(jnp.asarray(a))
    return out, n


def _apply_keep(table: CindTable, keep: np.ndarray) -> CindTable:
    return CindTable(*(np.asarray(c)[keep] for c in (
        table.dep_code, table.dep_v1, table.dep_v2,
        table.ref_code, table.ref_v1, table.ref_v2, table.support)))


def implication_possible(table: CindTable) -> bool:
    """Whether any row of `table` can be killed by passes A-D at all.

    The minimality pre-filter of the fused dense sweep (ISSUE 6 rung 2):
    each pass joins a query family against an implying family (A: 2/1 vs
    1/1, B: 2/1 vs 2/2, C: 1/1 vs 1/2, D: 2/2 vs 1/2), so when no (query,
    implying) family pair co-occurs the whole device sort-merge join is a
    provable no-op and is skipped.  Host family counts are a handful of
    numpy popcounts over the code columns — negligible next to the padded
    12n-row device sort they avoid.  Output-neutral by construction.
    """
    dep_bin = np.asarray(cc.is_binary(np.asarray(table.dep_code)))
    ref_bin = np.asarray(cc.is_binary(np.asarray(table.ref_code)))
    n11 = int((~dep_bin & ~ref_bin).sum())
    n12 = int((~dep_bin & ref_bin).sum())
    n21 = int((dep_bin & ~ref_bin).sum())
    n22 = int((dep_bin & ref_bin).sum())
    # A: n11 implying x n21 query; B: n22 x n21; C: n12 x n11; D: n12 x n22.
    return bool((n21 or n12) and (n11 or n22))


def minimize_table(table: CindTable) -> CindTable:
    """Drop implied CINDs (device sort-merge join; single device)."""
    if len(table) == 0 or not implication_possible(table):
        return table
    cols, n = _pad_cols(table)
    keep = np.asarray(_stage_keep_mask(*cols, jnp.int32(n)))[:n]
    return _apply_keep(table, keep)


# --------------------------------------------------------------------------
# Sharded variant: hash-partitioned membership join over the mesh.
# --------------------------------------------------------------------------


import functools


@functools.lru_cache(maxsize=None)
def _stage_keep_sharded(mesh, capacity: int):
    """Compiled shard_map program: (D*blk,) row-sharded columns -> keep mask.

    Each device builds the key segments for its row block, routes both sides
    to the key's hash owner, joins there, and pulls the verdicts back via the
    reply collective.  Returns (keep, overflow).
    """
    from jax.sharding import PartitionSpec as P

    from ..parallel import exchange
    from ..parallel.mesh import AXIS, shard_map

    def f(dc, d1, d2, rc, r1, r2, valid):
        n = dc.shape[0]
        cols = (dc, d1, d2, rc, r1, r2)
        fam = _families(dc, rc, valid)
        imp_cols, imp_valid = _implying_keys(cols, fam)
        qry_cols, qry_valid = _query_keys(cols, fam)
        d = jax.lax.psum(1, AXIS)
        imp_bkt = hashing.bucket_of(imp_cols, d, seed=11)
        qry_bkt = hashing.bucket_of(qry_cols, d, seed=11)
        recv_imp, recv_imp_v, ovf_i, _ = exchange.route(
            imp_cols, imp_valid, imp_bkt, AXIS, capacity)
        recv_qry, recv_qry_v, ovf_q, state = exchange.route(
            qry_cols, qry_valid, qry_bkt, AXIS, capacity)
        tab_cols, _, _, n_tab = segments.masked_unique(recv_imp, recv_imp_v)
        found = (segments.masked_table_index(tab_cols, n_tab, recv_qry,
                                             recv_qry_v) >= 0)
        back = exchange.route_reply(found.astype(jnp.int32), state, AXIS) == 1
        keep = _keep_from_found(back, fam, valid, n)
        return keep, ovf_i + ovf_q

    return jax.jit(shard_map(
        f, mesh=mesh,
        in_specs=(P(AXIS),) * 7,
        out_specs=(P(AXIS), P())))


def minimize_table_sharded(table: CindTable, mesh) -> CindTable:
    """Drop implied CINDs with the join hash-partitioned over `mesh`.

    The four coGroups run as one fixed-capacity exchange per side; capacity is
    planned from the expected per-owner volume and doubled on overflow (the
    capacity-plan/retry contract every sharded exchange follows).
    """
    n = len(table)
    # The family pre-filter is computed from the replicated host table, so
    # every process takes the same branch — no collective is skipped on one
    # host only.
    if n == 0 or not implication_possible(table):
        return table
    num_dev = mesh.devices.size
    if num_dev == 1:
        return minimize_table(table)

    blk = max(64, segments.pow2_capacity(-(-n // num_dev)))
    cols = []
    for c in (table.dep_code, table.dep_v1, table.dep_v2,
              table.ref_code, table.ref_v1, table.ref_v2):
        a = np.full(num_dev * blk, segments.SENTINEL, np.int32)
        a[:n] = np.asarray(c, np.int64).astype(np.int32)
        cols.append(a)
    valid = np.zeros(num_dev * blk, bool)
    valid[:n] = True

    # Each side is 6 segments of blk rows per device; hashing spreads them
    # evenly, so per-(src, dst) volume ~ 6*blk/D.
    capacity = segments.pow2_capacity(
        max(64, (6 * blk) // num_dev + (6 * blk) // (num_dev * 4)))
    from ..parallel.mesh import host_gather, make_global

    max_retries = 4
    for _ in range(max_retries):
        if num_dev * capacity > (1 << 31) - 1:
            # route()'s (D * capacity) flat index is int32; wrapping it would
            # silently corrupt the keep mask, so fail the way every other
            # planned exchange does.
            raise RuntimeError(
                f"minimality exchange capacity {capacity} x {num_dev} "
                f"devices exceeds the int32 buffer budget; rerun with more "
                f"devices")
        prog = _stage_keep_sharded(mesh, capacity)
        # make_global: each process donates only the rows its devices own
        # (device_put of a host array is single-process-only).
        args = [make_global(c, mesh) for c in cols] + [
            make_global(valid, mesh)]
        keep, ovf = prog(*args)
        ovf = int(np.asarray(host_gather(ovf)).reshape(-1)[0])
        if ovf == 0:
            break
        capacity = segments.pow2_capacity(2 * capacity + ovf)
    else:
        raise RuntimeError(
            f"minimality exchange overflow persisted after {max_retries} "
            f"retries (ovf={ovf})")
    keep = np.asarray(host_gather(keep)).reshape(-1)[:n]
    return _apply_keep(table, keep)
