"""Sorted-run / segment algebra — the TPU-side replacement for hash shuffles.

Every Flink ``groupBy`` in the reference plan (RDFind.scala:332-346,
AllAtOnceTraversalStrategy.scala:60-68) becomes: lexicographic sort of int32 key
columns + run detection + segment reduction.  All indices stay int32 (no x64 needed),
shapes stay static per input size, and the sorts map onto XLA's TPU sort.
"""

from __future__ import annotations

import dataclasses

import jax.lax
import jax.numpy as jnp


def lexsort(cols):
    """Permutation sorting rows by the given key columns (first column = major key).

    `cols` is a sequence of equal-length 1-D arrays.  Returns int32 indices.
    jnp.lexsort takes the *last* key as primary, so reverse here.
    """
    return jnp.lexsort(tuple(reversed(tuple(cols))))


def run_starts(sorted_cols):
    """Boolean mask marking the first row of each distinct-key run in sorted rows."""
    n = sorted_cols[0].shape[0]
    if n == 0:
        return jnp.zeros(0, bool)
    neq = jnp.zeros(n - 1, bool)
    for c in sorted_cols:
        neq = neq | (c[1:] != c[:-1])
    return jnp.concatenate([jnp.ones(1, bool), neq])


# ---------------------------------------------------------------------------
# Jit-safe (fixed-shape, mask-based) variants.  Convention: invalid rows carry
# SENTINEL in every key column, so they sort to the end and form one garbage run.
# ---------------------------------------------------------------------------

SENTINEL = jnp.iinfo(jnp.int32).max


def pow2_capacity(n: int) -> int:
    """Smallest power of two >= n (>= 1): the capacity-bucketing policy that keeps
    compiled stage programs reusable across datasets."""
    return 1 << max(0, int(n) - 1).bit_length() if n > 1 else 1


def unsort(perm, cols):
    """Columns given in the order `perm` sorted the rows, put back in row
    order (out[perm[i]] = col[i]).  One sort by perm: on the TPU a sort that
    carries its columns costs less than a scatter or a gather by a
    permutation."""
    return list(jax.lax.sort((perm, *cols), num_keys=1)[1:])


def run_sums(starts, x):
    """For each sorted row, the sum of int32 `x` >= 0 over its run, from
    the run-start mask: three scans, no scatter or gather."""
    n = x.shape[0]
    if n == 0:
        return x
    total = jnp.cumsum(x, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(starts, total - x, 0))
    ends = jnp.concatenate([starts[1:], jnp.ones(1, bool)])
    upto = jax.lax.cummin(jnp.where(ends, total, total[-1]), reverse=True)
    return upto - before


@dataclasses.dataclass
class SortedRuns:
    """One sort of masked key columns (masked_sorted_runs), all in key order."""

    perm: jnp.ndarray  # (N,) int32 rows in ascending key order, invalid last
    keys: list  # the masked key columns
    starts: jnp.ndarray  # (N,) bool first row of each valid key's run
    counts: jnp.ndarray  # (N,) int32 valid rows of each row's key (0 if invalid)
    payload: list  # the payload columns


def masked_sorted_runs(cols, valid, payload=()):
    """One sort of the masked key columns that carries the validity, the
    row index and `payload`, and the run counts read off it by scans: no
    gather or scatter by the permutation (unsort puts results back in row
    order)."""
    n = cols[0].shape[0]
    k = len(cols)
    keys = [jnp.where(valid, c, SENTINEL) for c in cols]
    out = jax.lax.sort((*keys, valid, *payload,
                        jnp.arange(n, dtype=jnp.int32)), num_keys=k)
    sorted_keys, v_sorted = list(out[:k]), out[k]
    runs = run_starts(sorted_keys)
    v_int = v_sorted.astype(jnp.int32)
    return SortedRuns(out[-1], sorted_keys, runs & v_sorted,
                      run_sums(runs, v_int) * v_int, list(out[k + 1:-1]))


def masked_row_counts(cols, valid):
    """For each row, how many valid rows share its key.  Fixed-shape, jittable.

    Invalid rows get count 0.
    """
    runs = masked_sorted_runs(cols, valid)
    return unsort(runs.perm, [runs.counts])[0]


def masked_weighted_row_counts(cols, weights, valid):
    """For each row, the sum of `weights` over valid rows sharing its key.

    The weighted generalization of masked_row_counts — the reduce side of a
    distributed count whose combiner pre-summed local multiplicities.  Invalid
    rows get 0.
    """
    n = cols[0].shape[0]
    cols = [jnp.where(valid, c, SENTINEL) for c in cols]
    perm = lexsort(cols)
    sorted_cols = [c[perm] for c in cols]
    v_sorted = valid[perm]
    w_sorted = jnp.where(v_sorted, weights[perm], 0).astype(jnp.int32)
    gid = jnp.cumsum(run_starts(sorted_cols)).astype(jnp.int32) - 1
    sums = jax.ops.segment_sum(w_sorted, gid, num_segments=n)
    per_row_sorted = sums[gid] * v_sorted.astype(jnp.int32)
    return jnp.zeros(n, jnp.int32).at[perm].set(per_row_sorted)


def masked_unique(cols, valid):
    """Distinct valid rows, compacted to the front in sorted key order.

    Returns (out_cols, out_valid, inverse, n_unique):
      out_cols  -- fixed-shape columns; rows [0, n_unique) are the distinct keys in
                   ascending order, the rest is garbage;
      inverse   -- for each input row, the dense id of its key (garbage for invalid
                   rows);
      n_unique  -- scalar array, number of distinct valid keys.
    """
    n = cols[0].shape[0]
    cols = [jnp.where(valid, c, SENTINEL) for c in cols]
    perm = lexsort(cols)
    sorted_cols = [c[perm] for c in cols]
    v_sorted = valid[perm]
    is_new = run_starts(sorted_cols) & v_sorted
    gid = jnp.cumsum(is_new).astype(jnp.int32) - 1  # valid rows only; garbage run inherits last id
    n_unique = is_new.sum().astype(jnp.int32)
    inverse = jnp.zeros(n, jnp.int32).at[perm].set(gid)
    # Compact distinct rows to the front, preserving sorted order: scatter each
    # first-of-run row to its dense id (gid increments in sorted order), which
    # replaces a full argsort with one scatter.  Rows >= n_unique are SENTINEL.
    target = jnp.where(is_new, gid, n)
    out_cols = [jnp.full(n, SENTINEL, c.dtype).at[target].set(c, mode="drop")
                for c in sorted_cols]
    out_valid = jnp.arange(n, dtype=jnp.int32) < n_unique
    return out_cols, out_valid, inverse, n_unique


def masked_table_index(table_cols, n_table, query_cols, query_valid):
    """For each query row, the index of its key in a sorted table, else -1.

    table_cols: valid-prefix columns, rows [0, n_table) sorted ascending and
    distinct (masked_unique output shape).  A sort-merge join: table rows tag 0
    sort before equal-key query rows, so each run's first element carries the
    table index for the whole run.  Invalid/absent queries get -1.
    """
    n_t = table_cols[0].shape[0]
    n_q = query_cols[0].shape[0]
    t_valid = jnp.arange(n_t, dtype=jnp.int32) < n_table
    cols = [jnp.concatenate([jnp.where(t_valid, tc, SENTINEL),
                             jnp.where(query_valid, qc, SENTINEL)])
            for tc, qc in zip(table_cols, query_cols)]
    # Tags: valid table 0 < query 1 < invalid table 2, so the SENTINEL garbage
    # run can never begin with a padded table row (which would donate a bogus
    # index to invalid queries).
    tag = jnp.concatenate([jnp.where(t_valid, 0, 2).astype(jnp.int32),
                           jnp.ones(n_q, jnp.int32)])
    perm = lexsort(cols + [tag])
    starts = run_starts([c[perm] for c in cols])
    idx = jnp.arange(n_t + n_q, dtype=jnp.int32)
    start_pos = jax.lax.cummax(jnp.where(starts, idx, 0))
    first_orig = perm[start_pos]
    run_idx = jnp.where(first_orig < n_t, first_orig, -1)
    out = jnp.zeros(n_t + n_q, jnp.int32).at[perm].set(run_idx)
    return jnp.where(query_valid, out[n_t:], -1)


def masked_dense_ids(col, valid):
    """Dense ids (0..n_ids-1, in ascending key order) for one key column.

    The light sibling of masked_unique for callers that need only the inverse
    mapping and the count: one sort by key that carries the validity and
    the row index, and one back to row order (unsort).  Invalid rows get a
    garbage id; mask them.
    """
    n = col.shape[0]
    key = jnp.where(valid, col, SENTINEL)
    key_s, v_sorted, perm = jax.lax.sort(
        (key, valid, jnp.arange(n, dtype=jnp.int32)), num_keys=1)
    is_new = run_starts([key_s]) & v_sorted
    gid = jnp.cumsum(is_new, dtype=jnp.int32) - 1
    (inverse,) = unsort(perm, [gid])
    return inverse, is_new.sum(dtype=jnp.int32)


def compact(cols, keep):
    """Move rows with keep=True to the front (preserving order).  Jittable.

    Returns (out_cols, n_kept).  One stable sort on ~keep that carries the
    columns, where a gather by its permutation would cost more on the TPU.
    """
    out = jax.lax.sort((~keep, *cols), num_keys=1, is_stable=True)
    return list(out[1:]), keep.sum().astype(jnp.int32)
