"""Join-candidate emission: triples -> (join_value, capture) rows, fully vectorized.

Replaces CreateJoinPartners (operators/CreateJoinPartners.scala:86-147).  Per triple
and enabled projection field there are up to 3 captures sharing the projected value as
join value: two unary (condition on one other field) and one binary (condition on both,
values in ascending field-bit order).

Two deliberate divergences from the reference's emission, both output-neutral:

* The reference suppresses one unary partner when the binary partner is emitted and
  recreates it by splitting binary captures at the consumer
  (CreateDependencyCandidates.scala:90-105).  Emitting both unaries up front + the
  downstream dedupe produces identical join-line capture sets with no consumer-side
  splitting — on TPU a static 9-way emission pattern beats data-dependent branching.

* Frequency pruning uses exact counts (ops/frequency.py) instead of Bloom filters, so
  it prunes a superset of what the reference's filters prune; both are conservative.

Fixed-shape and jittable: output rows carry a validity mask instead of being
compacted; the projection set is a static (compile-time) argument.

The candidate rows are never sorted.  Each copies fields of one triple row, so
capture ids come from the condition sorts that frequency counting already made
(intern_captures), and line ids from one sort of each projection's join-value
column (candidate_line_ids).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .. import conditions as cc
from . import segments
from .frequency import _FIELD_PAIRS, TripleFrequency

_FIELD_BITS = (cc.SUBJECT, cc.PREDICATE, cc.OBJECT)
_PAIR_INDEX = {pair: k for k, pair in enumerate(_FIELD_PAIRS)}
NO_VALUE = -1


@dataclasses.dataclass
class JoinCandidates:
    """Columnar join candidates with validity mask (fixed shape: 3 rows per triple
    per enabled projection)."""

    join_val: jnp.ndarray  # (C,) int32
    code: jnp.ndarray  # (C,) int32 capture code
    v1: jnp.ndarray  # (C,) int32
    v2: jnp.ndarray  # (C,) int32 (NO_VALUE for unary captures)
    valid: jnp.ndarray  # (C,) bool


@dataclasses.dataclass(frozen=True)
class Slice:
    """One N-row slice of the candidates: all triples under one capture code."""

    proj: int  # field index of the join value
    code: int  # capture code
    fields: tuple  # condition field indices, (a,) or (a, b) with a < b
    kind: int  # condition type: the field for unary, 3 + pair index for binary


def slices(projections: str) -> list:
    """The candidate slices in emission order, which is ascending code order:
    the projection bits are the code's high bits, and per projection the two
    unary codes precede the binary one."""
    out = []
    for proj_char, proj_bit in zip("spo", _FIELD_BITS):
        if proj_char not in projections:
            continue
        pi = cc.FIELD_INDEX[proj_bit]
        a, b = [i for i in range(3) if i != pi]
        bit_a, bit_b = _FIELD_BITS[a], _FIELD_BITS[b]
        out += [Slice(pi, cc.create(bit_a, secondary_condition=proj_bit), (a,), a),
                Slice(pi, cc.create(bit_b, secondary_condition=proj_bit), (b,), b),
                Slice(pi, cc.create(bit_a, bit_b, proj_bit), (a, b),
                      3 + _PAIR_INDEX[(a, b)])]
    return out


def join_sort_rows(n: int, projections: str) -> int:
    """Rows the join-line build sorts for `n` triple rows: one condition sort
    per condition type it interns from, one join-value column per projection."""
    sl = slices(projections)
    return n * (len({s.kind for s in sl}) + len({s.proj for s in sl}))


def _forms_capture(freq: TripleFrequency, kind: int):
    """Per triple row: its condition of type `kind` forms a capture (constant
    per condition, since every verdict is a function of its counts)."""
    if kind < 3:
        return freq.unary_ok[:, kind]
    k = kind - 3
    a, b = _FIELD_PAIRS[k]
    return (freq.unary_ok[:, a] & freq.unary_ok[:, b] & freq.binary_ok[:, k]
            & ~freq.binary_ar_implied[:, k])


def emit_join_candidates(triples, freq: TripleFrequency,
                         projections: str = "spo") -> JoinCandidates:
    """Emit all join candidates for an (N, 3) int32 triple table.

    Rows whose condition fails the frequency filter are emitted with valid=False.
    """
    n = triples.shape[0]
    parts = []  # (join_val, code_scalar, v1, v2, mask)
    for s in slices(projections):
        v2 = (triples[:, s.fields[1]] if len(s.fields) == 2
              else jnp.full(n, NO_VALUE, jnp.int32))
        parts.append((triples[:, s.proj], s.code, triples[:, s.fields[0]], v2,
                      _forms_capture(freq, s.kind)))

    if not parts:
        e = jnp.zeros(0, jnp.int32)
        return JoinCandidates(e, e, e, e, jnp.zeros(0, bool))

    return JoinCandidates(
        join_val=jnp.concatenate([p[0] for p in parts]),
        code=jnp.concatenate([jnp.full(n, p[1], jnp.int32) for p in parts]),
        v1=jnp.concatenate([p[2] for p in parts]),
        v2=jnp.concatenate([p[3] for p in parts]),
        valid=jnp.concatenate([p[4] for p in parts]),
    )


def intern_captures(freq: TripleFrequency, projections: str):
    """Dense capture ids for the candidate rows, from the condition sorts.

    `freq` comes from frequency.triple_frequencies, with its sorts.  A
    candidate row is valid exactly where its condition forms a capture, so
    the captures of a code are the capture-forming conditions of its type,
    and the canonical (code, v1, v2) order is the static code order, then
    each type's own key order.  A capture's id is therefore base[code] plus
    its condition's rank among the capture-forming conditions of its type;
    base is the running count over the codes before it.

    Returns (cap_id, num_caps, cap_code, cap_v1, cap_v2), equal to
    segments.masked_unique([code, v1, v2], valid) over the candidates:
    cap_id per candidate row (garbage where invalid), and the capture table
    at candidate-row capacity, in (code, v1, v2) order, SENTINEL from
    num_caps on.
    """
    sl = slices(projections)
    assert [s.code for s in sl] == sorted(s.code for s in sl)
    n = freq.unary_ok.shape[0]
    ranked = {}
    for s in sl:
        if s.kind not in ranked:
            srt = freq.sorts[s.kind]
            ranked[s.kind] = (srt.rank, *segments.compact(srt.keys, srt.first))
    table = [jnp.full(len(sl) * n, segments.SENTINEL, jnp.int32)
             for _ in range(3)]
    row = jnp.arange(n, dtype=jnp.int32)
    base = jnp.int32(0)
    cap_id = []
    for s in sl:
        rank, keys, n_kind = ranked[s.kind]
        cap_id.append(base + rank)
        v2 = keys[1] if len(keys) == 2 else jnp.full(n, NO_VALUE, jnp.int32)
        # Code i writes n rows at base_i <= i * n, so the table holds them;
        # each write starts past the captures of the codes before it and
        # overwrites only their SENTINEL tails.
        table = [jax.lax.dynamic_update_slice(
                     col, jnp.where(row < n_kind, part, segments.SENTINEL),
                     (base,))
                 for col, part in zip(table, (jnp.full(n, s.code, jnp.int32),
                                              keys[0], v2))]
        base = base + n_kind
    return (jnp.concatenate(cap_id), base, *table)


def candidate_line_ids(cands: JoinCandidates, n: int):
    """Dense line ids (ascending join value) of the candidate rows, one per
    projection and triple row, and their number.

    The 3 slices of one projection share its join-value column, so each
    column is sorted once, valid where any of its slices is.  Returns ids
    of shape (|projections|, n): candidate_row_ids(ids) gives each
    candidate row its id, as segments.masked_dense_ids(cands.join_val,
    cands.valid) would, without the 3|projections|n-row copy.  Ids are
    garbage on invalid rows.
    """
    by_proj = (-1, 3, n)
    join_val = cands.join_val.reshape(by_proj)[:, 0]
    valid = cands.valid.reshape(by_proj).any(axis=1)
    ids, n_lines = segments.masked_dense_ids(join_val.reshape(-1),
                                             valid.reshape(-1))
    return ids.reshape(-1, n), n_lines


def candidate_row_ids(ids):
    """(|projections|, n) ids of candidate_line_ids -> one per candidate row,
    in emission order (each projection's id repeated over its 3 slices)."""
    p, n = ids.shape
    return jnp.broadcast_to(ids[:, None, :], (p, 3, n)).reshape(-1)
