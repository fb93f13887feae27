"""Sharded SmallToLarge on the dense path: frequent conditions counted
over the whole mesh, each join line's incidences moved once to the device
that owns its value, and one membership matmul per device whose counts are
summed over the mesh.

The pair phase of models/sharded.py emits every (dependent, referenced)
capture pair of every join line and sorts them: with few frequent captures
on many lines that is hundreds of millions of rows per device.  Here, as on
the single-device dense path (small_to_large._prepare_dense), the pairs are
never materialized.  Two programs run over the mesh, each a shard_map over
the triples split by rows:

  _emit_step  the frequent conditions with their GLOBAL counts, and each
              triple row's capture id in each of its slices;
  _cooc_step  the one all_to_all of the incidences (join value, capture id)
              to the owner of the value, the owner's membership matrix,
              M^T M on the MXU, and its sum over the mesh.

Global frequent conditions without shuffling the triples: a condition with
at least `support` rows overall has at least ceil(support / D) rows on one
of the D devices (pigeonhole), so each device offers only its local keys
that reach that bound, the offers are all-gathered (a few thousand keys per
condition type at the benchmark's supports), every device counts each
offered key in its own rows by binary search over its sorted keys, and one
psum gives the exact global counts.  Keys that no device offers are below
the support on all devices together.  Capture ids are then base[code] +
the rank of the condition among the frequent ones of its type, the
canonical (code, v1, v2) order that emission.intern_captures gives on one
device.

Value v is owned by device v mod D, and is row v // D of that device's
membership matrix, so the owner needs no sort to find its lines.  A row
that repeats an incidence sets the same bit again, and cooc[d, r] =
|ext(d) & ext(r)| is the sum over the owners of their M^T M.  The lattice,
its candidate generation and the minimality pass are
small_to_large._run_lattice's, shared with the sharded pair path; only the
co-occurrence counts come from here.

Every sort has one signature, (int32 key, int32 payload), stable: the TPU
compiler's time grows with each distinct sort, and the cold compile is part
of a run's set-up.  So the sorts here are not segments.lexsort or
segments.compact, which sort all key and payload columns at once, one
signature per column count.  Device scopes (obs/tracer.py): the join-line
exchange, the packing of the send buffers and every collective, runs under
`exchange`; the counting under `frequent_conditions`, the capture ids under
`intern_captures` and the matmul under `membership`, the names the
single-device build uses for the same work.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..obs import metrics, tracer
from ..ops import cooc as cooc_ops
from ..ops import emission, frequency, segments
from ..parallel import exchange
from ..parallel.mesh import AXIS, shard_map

SENTINEL = segments.SENTINEL
NO_VALUE = emission.NO_VALUE
# The condition types: the three fields, then frequency._FIELD_PAIRS.
_KINDS = 6
# All-gathered condition offers are capped at this many rows per type (the
# offers of all devices together); past it the pair path runs instead.
MAX_OFFER_ROWS = 1 << 22
# Send-buffer slots, int32 indexed.
MAX_SLOTS = (1 << 31) - 1
# All-gathered offer lanes: a key column for each unary type, two for each
# pair type.
_LANES_OFFERS = 9
# Incidence lanes of the exchange: join value and capture id.
_LANES_LINES = 2


def _kind_keys(triples, kind: int) -> list:
    if kind < 3:
        return [triples[:, kind]]
    a, b = frequency._FIELD_PAIRS[kind - 3]
    return [triples[:, a], triples[:, b]]


def offer_capacity(t_loc: int, num_dev: int, min_support: int) -> int:
    """Rows one device offers per condition type: a key with local count
    >= ceil(support / D) takes at least that many of the t_loc rows."""
    return min(segments.pow2_capacity(t_loc // -(-min_support // num_dev) + 1),
               t_loc)


def _lex_search(sorted_cols, query_cols, right: bool):
    """For each query row, the first index of the ascending multi-column
    `sorted_cols` whose key is >= the query (> with `right`): a fixed
    binary search whose gathers touch only the query rows."""
    n = sorted_cols[0].shape[0]
    m = query_cols[0].shape[0]

    def step(_, bounds):
        lo, hi = bounds
        mid = (lo + hi) // 2
        at = [c[jnp.minimum(mid, n - 1)] for c in sorted_cols]
        # before: sorted key < query (<= with right), lexicographically.
        before = jnp.zeros(m, bool)
        eq = jnp.ones(m, bool)
        for a, q in zip(at, query_cols):
            before = before | (eq & (a < q))
            eq = eq & (a == q)
        if right:
            before = before | eq
        go_right = (mid < hi) & before
        return (jnp.where(go_right, mid + 1, lo), jnp.where(go_right, hi, mid))

    lo, _ = jax.lax.fori_loop(
        0, max(1, math.ceil(math.log2(n + 1))), step,
        (jnp.zeros(m, jnp.int32), jnp.full(m, n, jnp.int32)))
    return lo


def _sort(key, payload):
    """(key, payload) by key, stable.  Every sort of these programs has
    this one signature: the TPU compiler's time grows with each distinct
    sort (operand count, key count), and one signature compiles once."""
    return jax.lax.sort((key, payload), num_keys=1, is_stable=True)


def _lex_sort(keys):
    """(perm, sorted key columns) of the rows by `keys`, first column
    major: one stable sort per column, from the last to the first."""
    perm = jnp.arange(keys[0].shape[0], dtype=jnp.int32)
    k_s, perm = _sort(keys[-1], perm)
    for k in reversed(keys[:-1]):
        k_s, perm = _sort(k[perm], perm)
    return perm, [k_s] + [k[perm] for k in keys[1:]]


def _compact(cols, keep, capacity: int):
    """Rows with keep=True, in order, at the front of `capacity` rows
    (SENTINEL after them): one cumsum and a scatter per column.  The caller
    guarantees at most `capacity` kept rows."""
    at = jnp.where(keep, jnp.cumsum(keep, dtype=jnp.int32) - 1, capacity)
    return [jnp.full(capacity, SENTINEL, jnp.int32).at[at].set(c, mode="drop")
            for c in cols]


def _sorted_conditions(triples, valid):
    """Per condition type: (perm, sorted keys, run starts, run counts) of
    the valid triple rows; invalid rows carry SENTINEL keys and sort last."""
    out = []
    for kind in range(_KINDS):
        perm, keys = _lex_sort([jnp.where(valid, k, SENTINEL)
                                for k in _kind_keys(triples, kind)])
        ok = keys[0] != SENTINEL
        runs = segments.run_starts(keys)
        counts = segments.run_sums(runs, ok.astype(jnp.int32))
        out.append((perm, keys, runs, runs & ok, counts))
    return out


def _table(offered):
    """Distinct offered keys, ascending, SENTINEL-padded; and validity."""
    _, keys = _lex_sort(offered)
    start = segments.run_starts(keys) & (keys[0] != SENTINEL)
    keys = _compact(keys, start, keys[0].shape[0])
    return keys, keys[0] != SENTINEL


def _verdicts(tables, gcnt, min_support, use_ars):
    """Per type: which table keys form captures, by the single-device
    verdicts (frequency.pair_forms_capture) on global counts."""
    ok = []
    for kind in range(_KINDS):
        t_keys, t_valid = tables[kind]
        cnt = gcnt[kind]
        if kind < 3:
            ok.append(t_valid & (cnt >= min_support))
            continue
        # Without association rules a pair's count stands in for its fields'
        # counts, which it bounds from below.  With them, a frequent pair's
        # fields are frequent, so each is a key of its field's table.
        fields = [cnt, cnt]
        if use_ars:
            fields = []
            for f, col in zip(frequency._FIELD_PAIRS[kind - 3], t_keys):
                u_keys = tables[f][0]
                i = jnp.minimum(_lex_search(u_keys, [col], right=False),
                                u_keys[0].shape[0] - 1)
                fields.append(gcnt[f][i])
        ok.append(t_valid & frequency.pair_forms_capture(
            cnt, *fields, min_support, use_ars))
    return ok


def _row_ranks(cond, lo, hi, ok):
    """Per triple row (row order): the rank of its condition among the
    frequent table keys, -1 where its condition forms no capture.  The rank
    counts the frequent keys of the whole mesh, those absent from this
    device too, so it is not frequency.ConditionSort's rank among the local
    runs.  It is written at the first sorted row of each frequent key
    present here and carried down its run by a running max (ranks rise with
    the keys), then the rows go back to row order by one sort on their
    index."""
    perm, _, runs, _, _ = cond
    n = perm.shape[0]
    incl = jnp.cumsum(ok, dtype=jnp.int32)
    excl = incl - ok.astype(jnp.int32)
    at = jnp.where(ok & (hi > lo), lo, n)
    mark = jnp.full(n, -1, jnp.int32).at[at].set(excl, mode="drop")
    idx = jnp.arange(n, dtype=jnp.int32)
    mark_pos = jax.lax.cummax(jnp.where(mark >= 0, idx, -1))
    start_pos = jax.lax.cummax(jnp.where(runs, idx, -1))
    rank = jnp.where(mark_pos == start_pos, jax.lax.cummax(mark), -1)
    return _sort(perm, rank)[1], incl[-1]


def _emit_device(triples, n_valid, min_support, *, projections, use_ars,
                 offer_cap):
    n = triples.shape[0]
    num_dev = jax.lax.psum(1, AXIS)
    valid = jnp.arange(n, dtype=jnp.int32) < n_valid[0]

    with jax.named_scope(tracer.SCOPE_FREQUENT_CONDITIONS):
        conds = _sorted_conditions(triples, valid)
        # Offers: local keys with at least ceil(support / D) rows.
        bound = (min_support + num_dev - 1) // num_dev
        offers = [_compact(keys, starts & (counts >= bound), offer_cap)
                  for _, keys, _, starts, counts in conds]
    with jax.named_scope(tracer.SCOPE_EXCHANGE):
        offered = [[jax.lax.all_gather(k, AXIS, tiled=True) for k in keys]
                   for keys in offers]
    with jax.named_scope(tracer.SCOPE_FREQUENT_CONDITIONS):
        tables = [_table(keys) for keys in offered]
        bounds = []
        for (_, keys, _, _, _), (t_keys, _) in zip(conds, tables):
            bounds.append((_lex_search(keys, t_keys, right=False),
                           _lex_search(keys, t_keys, right=True)))
        local = jnp.stack([jnp.where(t_valid, hi - lo, 0) for (lo, hi), (
            _, t_valid) in zip(bounds, tables)])
    with jax.named_scope(tracer.SCOPE_EXCHANGE):
        gcnt = jax.lax.psum(local, AXIS)
    with jax.named_scope(tracer.SCOPE_FREQUENT_CONDITIONS):
        ok = _verdicts(tables, gcnt, min_support, use_ars)
        ranked = [_row_ranks(conds[k], *bounds[k], ok[k])
                  for k in range(_KINDS)]

    with jax.named_scope(tracer.SCOPE_INTERN_CAPTURES):
        # Capture ids in canonical (code, v1, v2) order: base[code] + rank,
        # one row of ids per slice.
        base = jnp.int32(0)
        ids = []
        for s in emission.slices(projections):
            rank, n_kind = ranked[s.kind]
            ids.append(jnp.where(rank >= 0, base + rank, -1))
            base = base + n_kind
        # Frequent keys of each type, in key order, for the host's table.
        q = tables[0][0][0].shape[0]
        fk_keys = []
        for (t_keys, _), k_ok in zip(tables, ok):
            keys = _compact(t_keys, k_ok, q)
            if len(keys) == 1:
                keys.append(jnp.full(q, NO_VALUE, jnp.int32))
            fk_keys.append(jnp.stack(keys))
        fk_keys = jnp.stack(fk_keys)
        fk_n = jnp.stack([k_ok.sum(dtype=jnp.int32) for k_ok in ok])

    with jax.named_scope(tracer.SCOPE_EXCHANGE):
        # Incidences (join value, capture id) per owner device.
        per_dest = jnp.zeros(num_dev, jnp.int32)
        for s, c in zip(emission.slices(projections), ids):
            dest = triples[:, s.proj] % num_dev
            per_dest = per_dest + jnp.stack(
                [((c >= 0) & (dest == k)).sum(dtype=jnp.int32)
                 for k in range(num_dev)])
        plan = jnp.stack([jax.lax.pmax(per_dest.max(), AXIS), base])
    return jnp.stack(ids), plan, fk_keys, fk_n


@functools.partial(jax.jit, static_argnames=("mesh", "projections",
                                             "use_ars", "offer_cap"))
def _emit_step(triples, n_valid, min_support, *, mesh, projections, use_ars,
               offer_cap):
    fn = functools.partial(_emit_device, projections=projections,
                           use_ars=use_ars, offer_cap=offer_cap)
    return shard_map(fn, mesh=mesh, in_specs=(P(AXIS, None), P(AXIS), P()),
                     out_specs=(P(None, AXIS), P(), P(), P()),
                     check_vma=False)(triples, n_valid, min_support)


def _cooc_device(triples, ids, *, projections, cap, l_pad, c_pad, dtype):
    num_dev = jax.lax.psum(1, AXIS)
    with jax.named_scope(tracer.SCOPE_EXCHANGE):
        # Each incidence's slot in the (D * cap) send buffers: its owner's
        # block, after the incidences bound there before it.
        flat, vals = [], []
        filled = [jnp.int32(0)] * num_dev
        for s, c in zip(emission.slices(projections), ids):
            v = triples[:, s.proj]
            dest = v % num_dev
            at = jnp.full(c.shape, num_dev * cap, jnp.int32)
            for k in range(num_dev):
                mine = (c >= 0) & (dest == k)
                run = jnp.cumsum(mine, dtype=jnp.int32)
                at = jnp.where(mine, k * cap + filled[k] + run - 1, at)
                filled[k] = filled[k] + run[-1]
            flat.append(at)
            vals.append(v)
        flat = jnp.concatenate(flat)
        # One scatter per lane: on a v5e one scatter of the same incidences
        # as two-lane rows took 11.7 s where each lane takes 1.3 s.
        bufs = [jnp.full(num_dev * cap, -1, jnp.int32).at[flat].set(
                    col, mode="drop")
                for col in (jnp.concatenate(vals), ids.reshape(-1))]
        v, cap_id = (jax.lax.all_to_all(
            b.reshape(num_dev, cap), AXIS, split_axis=0, concat_axis=0,
            tiled=True).reshape(-1) for b in bufs)
    with jax.named_scope(tracer.SCOPE_MEMBERSHIP):
        # The owner's line of value v is row v // D of its membership.
        m = cooc_ops.build_membership(v // num_dev, cap_id, v >= 0,
                                      l_pad=l_pad, c_pad=c_pad, dtype=dtype)
        part = cooc_ops.cooc_dot(m, m)
    with jax.named_scope(tracer.SCOPE_EXCHANGE):
        return jax.lax.psum(part, AXIS)


@functools.partial(jax.jit, static_argnames=("mesh", "projections", "cap",
                                             "l_pad", "c_pad", "dtype"))
def _cooc_step(triples, ids, *, mesh, projections, cap, l_pad, c_pad, dtype):
    fn = functools.partial(_cooc_device, projections=projections, cap=cap,
                           l_pad=l_pad, c_pad=c_pad, dtype=dtype)
    return shard_map(fn, mesh=mesh, in_specs=(P(AXIS, None), P(None, AXIS)),
                     out_specs=P(), check_vma=False)(triples, ids)


def _capture_table(projections, fk_keys, fk_n):
    """Host capture table, canonical (code, v1, v2) order: per slice in code
    order, its condition type's frequent keys in key order."""
    code, v1, v2 = [], [], []
    for s in emission.slices(projections):
        k = int(fk_n[s.kind])
        code.append(np.full(k, s.code, np.int64))
        v1.append(fk_keys[s.kind, 0, :k].astype(np.int64))
        v2.append(fk_keys[s.kind, 1, :k].astype(np.int64))
    return np.concatenate(code), np.concatenate(v1), np.concatenate(v2)


class DenseCooc:
    """The global co-occurrence counts of the frequent captures, answering
    the lattice's per-level queries (small_to_large._run_lattice's
    cooc_fn) on the host."""

    def __init__(self, counts: np.ndarray):
        self.counts = counts

    def __call__(self, dep_ok, ref_ok, stat_key):
        d_idx, r_idx = np.flatnonzero(dep_ok), np.flatnonzero(ref_ok)
        block = self.counts[np.ix_(d_idx, r_idx)]
        nz = (block > 0) & (d_idx[:, None] != r_idx[None, :])
        i, j = np.nonzero(nz)
        return d_idx[i], r_idx[j], block[i, j].astype(np.int64)


def eligible(num_dev: int, t_loc: int, min_support: int, use_fis: bool) -> bool:
    """Whether the dense path can take this run: the frequent-condition
    filter is on (without it every condition is a capture) and the offers
    fit their all-gather."""
    return (use_fis and num_dev > 1 and
            num_dev * offer_capacity(t_loc, num_dev, min_support)
            <= MAX_OFFER_ROWS)


def prepare(mesh, g_triples, g_n_valid, t_loc: int, n_values: int,
            min_support: int, projections: str, use_ars: bool, stats):
    """Run the two programs over value ids in [0, n_values).  Returns
    (DenseCooc, cap_code, cap_v1, cap_v2, dep_count) over the frequent
    captures, () when there are none, or None when the membership matrix
    does not fit the dense plan (the caller then runs the pair path)."""
    num_dev = mesh.devices.size
    offer_cap = offer_capacity(t_loc, num_dev, min_support)
    ids, plan, fk_keys, fk_n = _emit_step(
        g_triples, g_n_valid, jnp.int32(min_support), mesh=mesh,
        projections=projections, use_ars=use_ars, offer_cap=offer_cap)
    with tracer.span("pull-dense-plan", cat=tracer.CAT_PULL):
        plan, fk_keys, fk_n = (np.asarray(x) for x in jax.device_get(
            (plan, fk_keys, fk_n)))
    max_rows, num_caps = int(plan[0]), int(plan[1])
    if num_caps == 0:
        return ()
    exchange.log_exchange(stats, "freq", num_dev=num_dev, capacity=offer_cap,
                          lanes=_LANES_OFFERS)
    # The exact per-owner counts, bucketed to a power of two so that the
    # seeds of one graph share the compiled program.
    cap = segments.pow2_capacity(max(max_rows, 1))
    dplan = cooc_ops.dense_plan(-(-n_values // num_dev), num_caps)
    # Send-buffer slots are int32.
    if dplan is None or num_dev * cap > MAX_SLOTS:
        return None
    exchange.log_exchange(stats, "exchange_a", num_dev=num_dev, capacity=cap,
                          lanes=_LANES_LINES)
    counts = _cooc_step(g_triples, ids, mesh=mesh, projections=projections,
                        cap=cap, l_pad=dplan.l_pad, c_pad=dplan.c_pad,
                        dtype=dplan.dtype)
    cap_code, cap_v1, cap_v2 = _capture_table(projections, fk_keys, fk_n)
    with tracer.span("pull-dense-cooc", cat=tracer.CAT_PULL):
        counts = np.asarray(jax.device_get(counts))[:num_caps, :num_caps]
    support = np.diagonal(counts).astype(np.int64)
    freq = np.flatnonzero(support >= min_support)
    if stats is not None:
        metrics.set_many(stats, n_condition_captures=num_caps,
                         pair_backend="matmul", dense_plan=dplan.describe(),
                         cooc_dtype=dplan.dtype)
    if freq.size == 0:
        return ()
    return (DenseCooc(counts[np.ix_(freq, freq)]), cap_code[freq],
            cap_v1[freq], cap_v2[freq], support[freq])
