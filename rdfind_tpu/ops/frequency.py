"""Frequent-condition mining + association rules as segment counting.

Replaces the reference's FrequentConditionPlanner count pipelines
(plan/FrequentConditionPlanner.scala:291-311 unary, :374-394 binary, :130-194
association rules): a condition (field=value, or field-pair=value-pair) is
*frequent* when at least ``min_support`` triples satisfy it.  Frequency is a
conservative prefilter — a capture can never be larger than its condition's triple
count — so pruning on it never changes the final CIND set (the exact support test
happens downstream).

Instead of Bloom filters broadcast to workers, counts are computed exactly via
group-by-and-count and mapped straight back onto the triple rows that asked — the
query set and the count set are the same rows, so membership testing disappears.
The same trick makes association rules *local*: the perfect-confidence test for the
rule (a=va) -> (b=vb) is count(a=va ∧ b=vb) == count(a=va), evaluable per triple row
from the group counts — no rule broadcast needed at emission time.

Fixed-shape and jittable: `valid` masks padding rows, which always count as 0.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import conditions as cc
from . import segments

_FIELD_PAIRS = ((0, 1), (0, 2), (1, 2))  # (s,p), (s,o), (p,o) in ascending bit order
_FIELD_BITS = (cc.SUBJECT, cc.PREDICATE, cc.OBJECT)


@dataclasses.dataclass
class TripleFrequency:
    """Per-triple-row frequency verdicts.

    unary_ok[i, f]       -- field f's value in row i occurs >= min_support times;
    binary_ok[i, k]      -- row i's value pair for field-pair k occurs >= min_support
                            times (k indexes _FIELD_PAIRS);
    binary_ar_implied[i, k] -- the pair condition is implied by a perfect-confidence
                            association rule (either direction), i.e. the binary
                            capture equals one of its unary halves extensionally.
    """

    unary_ok: jnp.ndarray  # (N, 3) bool
    binary_ok: jnp.ndarray  # (N, 3) bool
    binary_ar_implied: jnp.ndarray  # (N, 3) bool
    # The condition sorts behind the verdicts, one per condition type (the
    # 3 fields, then _FIELD_PAIRS), for interning captures from them
    # (emission.intern_captures); empty where the verdicts were not sorted.
    sorts: tuple = ()


@dataclasses.dataclass
class ConditionSort:
    """One condition type's sort of the triple rows, for interning the
    captures of its capture-forming conditions (the keys whose verdicts
    pass): per row, the rank of its condition among them in ascending key
    order (garbage where its condition forms none); in key order, the key
    columns and the first row of each of them."""

    rank: jnp.ndarray  # (N,) int32, row order
    keys: list  # 1 or 2 (N,) int32 columns, key order
    first: jnp.ndarray  # (N,) bool, key order


def _rank_and_unsort(runs, first):
    """(row counts, ConditionSort) from a sort and its capture-forming first
    rows: the counts and the ranks go back to row order in one unsort."""
    counts, rank = segments.unsort(
        runs.perm, [runs.counts, jnp.cumsum(first, dtype=jnp.int32) - 1])
    return counts, ConditionSort(rank, runs.keys, first)


def _binary_verdicts(cnt, cnt_a, cnt_b, min_support, find_ar_implied):
    """(binary_ok, ar_implied) of field-pair conditions from their count and
    their two fields' counts, in whatever row order those come."""
    ok = cnt >= min_support
    if not find_ar_implied:
        return ok, jnp.zeros_like(ok)
    # Rule (a -> b) or (b -> a) with confidence 1 over frequent conditions:
    # emission then suppresses the redundant binary capture
    # (CreateJoinPartners.scala:100-146 with the AR broadcast).
    return ok, ((cnt == cnt_a) | (cnt == cnt_b)) & ok


def pair_forms_capture(cnt, cnt_a, cnt_b, min_support, find_ar_implied):
    """Whether a field-pair condition forms a capture, from its count and
    its two fields' counts: it and both fields are frequent, and no
    association rule implies it."""
    ok, ar = _binary_verdicts(cnt, cnt_a, cnt_b, min_support, find_ar_implied)
    return ok & ~ar & (cnt_a >= min_support) & (cnt_b >= min_support)


def triple_frequencies(triples, valid, min_support,
                       find_ar_implied: bool = False) -> TripleFrequency:
    """Exact unary + binary condition frequencies, evaluated on the triples' own rows.

    At min_support 1 without association rules every valid row passes, as
    with no_filter, and the sorts are still made for the capture interning.
    Each pair sort carries its two fields' counts, so a pair condition's
    verdicts are read in its own key order too.
    """
    unary_cnt, sorts = [], []
    for f in range(3):
        runs = segments.masked_sorted_runs([triples[:, f]], valid)
        cnt, srt = _rank_and_unsort(
            runs, runs.starts & (runs.counts >= min_support))
        unary_cnt.append(cnt)
        sorts.append(srt)
    ok, ar = [], []
    for a, b in _FIELD_PAIRS:
        runs = segments.masked_sorted_runs(
            [triples[:, a], triples[:, b]], valid,
            payload=(unary_cnt[a], unary_cnt[b]))
        cnt_a, cnt_b = runs.payload
        cnt, srt = _rank_and_unsort(
            runs, runs.starts & pair_forms_capture(
                runs.counts, cnt_a, cnt_b, min_support, find_ar_implied))
        sorts.append(srt)
        row_ok, row_ar = _binary_verdicts(cnt, unary_cnt[a], unary_cnt[b],
                                          min_support, find_ar_implied)
        ok.append(row_ok)
        ar.append(row_ar)
    unary_ok = jnp.stack([c >= min_support for c in unary_cnt], axis=1)
    return TripleFrequency(unary_ok=unary_ok, binary_ok=jnp.stack(ok, axis=1),
                           binary_ar_implied=jnp.stack(ar, axis=1),
                           sorts=tuple(sorts))


def no_filter(valid) -> TripleFrequency:
    """All-pass verdicts for valid rows (the --no-frequent-item-set path)."""
    ok = jnp.tile(valid[:, None], (1, 3))
    return TripleFrequency(ok, ok, jnp.zeros_like(ok))


def emit_rule_rows(triples, valid, min_support, unary_counts, binary_counts):
    """Distinct perfect-confidence rule rows from per-row condition counts.

    Shared emitter for the host and the distributed miners (they differ only
    in where counts come from: local segment counts vs the count exchange).
    unary_counts[f] / binary_counts[k] are (N,) per-row counts of field f's
    value / field-pair k's value pair.  Returns (cols, valid): five fixed-shape
    columns (ant_bit, cons_bit, ant_val, cons_val, support) with the distinct
    rule rows compacted to the front.
    """
    n = triples.shape[0]
    parts = []
    for k, (a, b) in enumerate(_FIELD_PAIRS):
        cnt_ab = binary_counts[k]
        for ant, con, cnt_u in ((a, b, unary_counts[a]), (b, a, unary_counts[b])):
            is_rule = valid & (cnt_ab == cnt_u) & (cnt_u >= min_support)
            parts.append((jnp.full(n, _FIELD_BITS[ant], jnp.int32),
                          jnp.full(n, _FIELD_BITS[con], jnp.int32),
                          triples[:, ant], triples[:, con], cnt_ab, is_rule))
    cols = [jnp.concatenate([p[i] for p in parts]) for i in range(5)]
    mask = jnp.concatenate([p[5] for p in parts])
    # Support (cnt_ab) is constant within a rule group, so it can ride along as a
    # fifth key column without affecting uniqueness.
    (full_cols, u_valid, _, n_rules) = segments.masked_unique(cols, mask)
    return full_cols, u_valid, n_rules


@jax.jit
def _stage_rules(triples, n_valid, min_support):
    """All perfect-confidence association rules, compacted to the front.

    Returns (ant_bit, cons_bit, ant_val, cons_val, support, n_rules): one row per
    directed rule (a=va) -> (b=vb) with count(a=va ∧ b=vb) == count(a=va) and the
    antecedent frequent (FrequentConditionPlanner.scala:130-194; the consequent is
    then automatically frequent).
    """
    n = triples.shape[0]
    valid = jnp.arange(n, dtype=jnp.int32) < n_valid
    unary = [segments.masked_row_counts([triples[:, f]], valid)
             for f in range(3)]
    binary = [segments.masked_row_counts([triples[:, a], triples[:, b]], valid)
              for a, b in _FIELD_PAIRS]
    full_cols, _, n_rules = emit_rule_rows(triples, valid, min_support,
                                           unary, binary)
    return (*full_cols, n_rules)


def ar_implied_pair_mask(dep_code, ref_code, dep_v1, ref_v1, mined_rules):
    """True where a capture pair restates a mined perfect-confidence rule.

    Host-side, shared by every strategy's AR post-filter
    (FilterAssociationRuleImpliedCinds.scala:30-58): the suppressed pairs are
    unary/unary with the same projection, whose (antecedent-field, consequent-field,
    antecedent-value, consequent-value) matches a rule.
    """
    dep_code = np.asarray(dep_code)
    ref_code = np.asarray(ref_code)
    out = np.zeros(len(dep_code), bool)
    ants, cons, avs, cvs, _ = mined_rules
    if len(ants) == 0 or len(dep_code) == 0:
        return out
    cand = np.flatnonzero(
        np.asarray(cc.is_unary(dep_code) & cc.is_unary(ref_code)
                   & (cc.secondary(dep_code) == cc.secondary(ref_code))
                   & (cc.primary(dep_code) != cc.primary(ref_code))))
    if cand.size == 0:
        return out
    dep_v1 = np.asarray(dep_v1)
    ref_v1 = np.asarray(ref_v1)
    # Membership of (ant_field, cons_field, ant_val, cons_val) rows in the rule
    # table via one row-wise unique — a sorted join, no per-row interpreter work.
    rule_rows = np.stack([ants, cons, avs, cvs], axis=1).astype(np.int64)
    cand_rows = np.stack([
        np.asarray(cc.primary(dep_code[cand]), np.int64),
        np.asarray(cc.primary(ref_code[cand]), np.int64),
        dep_v1[cand].astype(np.int64), ref_v1[cand].astype(np.int64)], axis=1)
    allr = np.concatenate([rule_rows, cand_rows])
    uniq, inv = np.unique(allr, axis=0, return_inverse=True)
    in_rules = np.zeros(len(uniq), bool)
    in_rules[inv[:len(rule_rows)]] = True
    out[cand] = in_rules[inv[len(rule_rows):]]
    return out


@functools.partial(jax.jit, static_argnames="field_groups")
def _stage_count_fcs(triples, n_valid, min_support, field_groups):
    """Distinct frequent conditions over `field_groups`, summed (device-side)."""
    n = triples.shape[0]
    valid = jnp.arange(n, dtype=jnp.int32) < n_valid
    total = jnp.int32(0)
    for fields in field_groups:
        cols = [triples[:, f] for f in fields]
        cnt = segments.masked_row_counts(cols, valid)
        ok = valid & (cnt >= min_support)
        _, _, _, n_u = segments.masked_unique(cols, ok)
        total += n_u
    return total


def _pad_to_device(triples_np):
    """(N, 3) int32 -> pow2-padded device array (SENTINEL-padded rows)."""
    n = triples_np.shape[0]
    cap = segments.pow2_capacity(n)
    padded = np.pad(triples_np, ((0, cap - n), (0, 0)),
                    constant_values=np.iinfo(np.int32).max)
    return jnp.asarray(padded)


def count_frequent_conditions(triples_np, min_support: int,
                              include_binary: bool):
    """Distinct frequent unary (and optionally binary) condition counts.

    The --find-only-fcs report path (RDFind.scala:298-306: level >= 1 emits the
    single-condition filters and stops; level >= 2 additionally emits the
    double-condition filters).  Runs on the same device segment-count ops as
    the real pipeline so the flag exercises the production frequency code.
    Returns (n_unary, n_binary) with n_binary None when not requested.
    """
    n = triples_np.shape[0]
    if n == 0:
        return 0, (0 if include_binary else None)
    dev = _pad_to_device(triples_np)
    ms = jnp.int32(max(int(min_support), 1))
    n_unary = int(_stage_count_fcs(dev, jnp.int32(n), ms, ((0,), (1,), (2,))))
    n_binary = (int(_stage_count_fcs(dev, jnp.int32(n), ms, _FIELD_PAIRS))
                if include_binary else None)
    return n_unary, n_binary


def mine_association_rules(triples_np, min_support: int):
    """Host wrapper: (N, 3) int32 -> numpy rule table (ant_bit, cons_bit, ant_val,
    cons_val, support)."""
    n = triples_np.shape[0]
    if n == 0:
        return [np.zeros(0, np.int32)] * 5
    out = _stage_rules(_pad_to_device(triples_np), jnp.int32(n),
                       jnp.int32(max(int(min_support), 1)))
    *cols, n_rules = out
    n_rules = int(n_rules)
    return [np.asarray(c[:n_rules]) for c in cols]
