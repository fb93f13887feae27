"""CIND-evidence pair generation — the quadratic hot path, as rotations.

The reference emits, per join line, one evidence per dependent capture carrying the
whole line as referenced set (CreateAllCindCandidates.scala:106-121) and k-way
intersects them (IntersectCindCandidates.scala:14-51).  Equivalent count formulation
used here: for captures d, r

    CIND d ⊆ r  <=>  cooc(d, r) == |lines containing d|  (and support >= min_support)

so evidence extraction becomes emitting all ordered co-occurrence pairs and counting.

Pair enumeration is rotation-based: for a line of length L laid out contiguously,
rotation j (1 <= j < L) pairs each element with the one j slots ahead (mod L).  The
whole enumeration is one flat repeat + gather with a *static* output capacity — a
constant number of XLA ops, fully jittable, however skewed the line-size distribution
is.  Total real work is sum_l L_l (L_l - 1), the evidence count itself.

All functions here are fixed-shape and mask-based (see ops/segments.py conventions):
rows beyond the valid count are garbage and must be masked by callers.
"""

from __future__ import annotations

import jax.lax
import jax.numpy as jnp
import jax.ops
import numpy as np

from . import segments

# Saturation bound for pair-count prefix sums: large enough that any real capacity
# is below it, small enough that a single add can never wrap int32.
# Plain int (not jnp.int32): a module-scope device array would initialize the
# default backend at import time, before a caller could pick its platform.
SAT = np.int32(1 << 30)


def saturating_cumsum(x):
    """Inclusive prefix sum of non-negative int32 with saturation at SAT.

    min(a+b, SAT) is associative for non-negative operands, so this lowers to an
    O(log n) associative scan; unlike a plain cumsum it cannot wrap int32, which
    keeps overflow *detection* exact however quadratic the pair counts get.
    """
    x = jnp.minimum(x, SAT)
    return jax.lax.associative_scan(lambda a, b: jnp.minimum(a + b, SAT), x)


def line_layout(line_val, n_valid):
    """Run layout over candidate rows sorted by join value, valid-prefix masked.

    `line_val` must be sorted ascending among its first `n_valid` rows (rows beyond
    are garbage).  Returns (pos, length, start_idx, total_pairs):
      pos       -- position of each row within its line;
      length    -- line length (1 for invalid rows, so they contribute no pairs);
      start_idx -- index of the line's first row;
      total_pairs -- scalar, sum of length*(length-1) over lines.
    """
    n = line_val.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    valid = idx < n_valid
    jv = jnp.where(valid, line_val, segments.SENTINEL)
    starts = segments.run_starts([jv])
    gid = jnp.cumsum(starts).astype(jnp.int32) - 1
    counts = jax.ops.segment_sum(valid.astype(jnp.int32), gid, num_segments=n)
    length = jnp.where(valid, counts[gid], 1)
    run_start = jax.lax.cummax(jnp.where(starts, idx, 0))
    pos = idx - run_start
    # Saturating sum: exact below SAT, pinned at SAT beyond -- callers compare it
    # against capacities far below SAT, so overflow handling stays correct.
    cum = saturating_cumsum(length - 1)
    total_pairs = cum[-1] if n else jnp.int32(0)
    return pos, length, run_start, total_pairs


def emit_pair_indices(pos, length, start_idx, capacity: int,
                      balanced: bool = False, emit=None):
    """Row/partner indices of all ordered co-occurrence pairs, statically padded.

    Returns (row, partner, pair_valid): gather payload columns at `row` (dependent)
    and `partner` (referenced) to materialize pairs.  Rows beyond the true total are
    garbage (masked by pair_valid).  If total pairs exceed `capacity`, the excess is
    truncated — callers must compare line_layout's total against capacity and
    retry/chunk on overflow.

    `emit` (optional bool per row) suppresses emission for rows where it is
    False: those rows take ZERO output slots (they still appear as partners of
    emitting rows).  This is what makes dependent-side restriction reduce the
    required capacity — a masked-after-emission design would still allocate
    the full quadratic — and it is the mechanism behind both the S2L level
    masks and the bounded-memory dep-slice pair passes.

    balanced=True emits each *unordered* pair exactly once — rotations
    j <= (L-1)//2 per row, plus (for even L) the antipodal rotation L/2 for the
    first half of positions.  This is the TPU-rotation form of the reference's
    ring-distance ownership (AbstractExtractBalancedUnaryUnaryOverlapCandidates
    .scala:64-120): per line, every element owns ~half its partners, total
    emission L*(L-1)/2, with even per-element load.  Callers must symmetrize
    the merged counts (ownership is positional, so the same capture pair can
    be owned in either direction in different lines).
    """
    n = pos.shape[0]
    if balanced:
        reps = ((length - 1) // 2) + ((length % 2 == 0) & (pos < length // 2))
        reps = reps.astype(jnp.int32)
    else:
        reps = length - 1
    if emit is not None:
        reps = jnp.where(emit, reps, 0)
    # Saturating prefix sum instead of jnp.repeat's internal cumsum: immune to int32
    # wrap on quadratic totals (see saturating_cumsum).
    cum = saturating_cumsum(reps)
    total = cum[-1]
    out_idx = jnp.arange(capacity, dtype=jnp.int32)
    pair_valid = out_idx < total
    # Row owning output slot k: first row whose inclusive cumsum exceeds k.
    row = jnp.searchsorted(cum, out_idx, side="right").astype(jnp.int32)
    row = jnp.clip(row, 0, n - 1)
    block_start = cum[row] - reps[row]
    j = out_idx - block_start + 1
    partner = start_idx[row] + (pos[row] + j) % length[row]
    partner = jnp.clip(partner, 0, n - 1)  # tail rows repeat the last real row; masked
    return row, partner, pair_valid
