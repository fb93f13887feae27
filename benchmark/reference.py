"""The plain reference: the minimal CIND set of an id-triple graph, by
definition, in numpy and scipy.

A capture is a projection field under an equality condition on one or two
of the other fields, coded in 6 bits: the low 3 bits are the condition
fields (s=1, p=2, o=4), the high 3 the projected field.  Its extension is
the set of projected values.  dep < ref holds when |ext(dep)| >= support,
ext(dep) is a subset of ext(ref), and dep does not imply ref.  The
minimal set drops implied CINDs as the reference RDFind's --clean-implied
pass does (TraversalStrategy.scala:126-168).

Only captures with at least `support` values can take part (a referenced
extension holds the dependent's), so the containment test runs over those:
one sparse value-by-capture incidence matrix M, and dep < ref where
(M^T M)[dep, ref] == |ext(dep)|.  This module imports nothing of the
program under test.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import scipy.sparse as sp

S, P, O = 1, 2, 4
FIELDS = (S, P, O)
NO_VALUE = -1


def code_of(cond_bits: int, proj_bit: int) -> int:
    return cond_bits | (proj_bit << 3)


def _lowest_bit(x):
    return x & (-x)


def _popcount3(x):
    return (x & 1) + ((x >> 1) & 1) + ((x >> 2) & 1)


def is_binary(code):
    return _popcount3(code & 7) == 2


def first_subcapture(code):
    return (code & ~7) | _lowest_bit(code & 7)


def second_subcapture(code):
    first = _lowest_bit(code & 7)
    return (code & ~7) | _lowest_bit((code & 7) & ~first)


@dataclasses.dataclass
class Captures:
    """The frequent captures (|ext| >= support) and their incidences."""

    code: np.ndarray      # (C,) capture codes
    v1: np.ndarray        # (C,) first condition value
    v2: np.ndarray        # (C,) second condition value, NO_VALUE if unary
    size: np.ndarray      # (C,) |ext|
    inc_value: np.ndarray  # (K,) projected value of each incidence
    inc_cap: np.ndarray   # (K,) capture index of each incidence

    def work(self) -> dict:
        """What any exact containment over these captures has to touch:
        join lines, incidences, and the (line, capture, capture) instances
        the pair phase pairs up."""
        _, per_line = np.unique(self.inc_value, return_counts=True)
        per_line = per_line.astype(np.int64)
        return {"captures": int(len(self.code)), "lines": int(len(per_line)),
                "incidences": int(len(self.inc_value)),
                "pair_instances": int((per_line * per_line).sum())}


def captures(triples: np.ndarray, support: int,
             fold_bits: int | None = None) -> Captures:
    """Every capture with at least `support` distinct values.

    `fold_bits` keeps only that many low bits of each projected value
    before the extensions are formed: the containment then runs on
    narrower value ids, which is what the control does."""
    t = np.asarray(triples, np.int64)
    if t.size and (t.min() < 0 or t.max() >= 1 << 31):
        raise ValueError("ids must lie in [0, 2^31)")
    low = (1 << 32) - 1
    codes, v1s, v2s, sizes, inc_v, inc_c = [], [], [], [], [], []
    n_caps = 0
    for pi, proj in enumerate(FIELDS):
        a, b = [i for i in range(3) if i != pi]
        pv = t[:, pi] if fold_bits is None else t[:, pi] & ((1 << fold_bits)
                                                           - 1)
        for conds in ((a,), (b,), (a, b)):
            # A condition's id: its value, or the index of its value pair.
            if len(conds) == 1:
                cond = t[:, conds[0]]
            else:
                pairs, cond = np.unique((t[:, a] << 32) | t[:, b],
                                        return_inverse=True)
            key = np.unique((cond.reshape(-1) << 32) | pv)
            prefix = key >> 32
            start = np.flatnonzero(np.r_[True, prefix[1:] != prefix[:-1]])
            length = np.diff(np.append(start, len(key)))
            keep = length >= support
            if not keep.any():
                continue
            g_start, g_len = start[keep], length[keep]
            gp = prefix[g_start]
            cond_bits = sum(FIELDS[c] for c in conds)
            codes.append(np.full(len(gp), code_of(cond_bits, proj)))
            if len(conds) == 1:
                v1s.append(gp)
                v2s.append(np.full(len(gp), NO_VALUE))
            else:
                v1s.append(pairs[gp] >> 32)
                v2s.append(pairs[gp] & low)
            sizes.append(g_len)
            member = np.repeat(np.arange(len(gp)), g_len) + n_caps
            rows = np.repeat(g_start, g_len) + (
                np.arange(g_len.sum()) - np.repeat(np.cumsum(g_len) - g_len,
                                                   g_len))
            inc_v.append(key[rows] & low)
            inc_c.append(member)
            n_caps += len(gp)
    if not codes:
        z = np.zeros(0, np.int64)
        return Captures(z, z, z, z, z, z)
    cat = np.concatenate
    return Captures(cat(codes), cat(v1s), cat(v2s), cat(sizes), cat(inc_v),
                    cat(inc_c))


def _implies(dc, d1, d2, rc, r1, r2):
    """dep implies ref (vectorised): ref is dep, or a value-matching
    subcapture of dep.  For two distinct binary captures with one code the
    subcode test degenerates to ref_v1 == dep_v2, as in the reference
    RDFind (data/Condition.scala:35-43)."""
    same = (dc == rc) & (d1 == r1) & (d2 == r2)
    sub = (rc & dc) == rc
    first = first_subcapture(dc) == rc
    match = np.where(first, r1 == d1, r1 == d2)
    return same | (sub & match)


def raw_cinds(caps: Captures, support: int) -> set[tuple]:
    """All CINDs dep < ref, as 7-tuples (dep_code, dep_v1, dep_v2,
    ref_code, ref_v1, ref_v2, support)."""
    n = len(caps.code)
    if n == 0:
        return set()
    m = sp.csr_matrix((np.ones(len(caps.inc_cap), np.int32),
                       (caps.inc_value, caps.inc_cap)),
                      shape=(int(caps.inc_value.max()) + 1, n))
    cooc = (m.T @ m).toarray()
    size = caps.size
    dep, ref = np.nonzero((cooc == size[:, None]) & (size[:, None] >= support))
    c, v1, v2 = caps.code, caps.v1, caps.v2
    keep = ~_implies(c[dep], v1[dep], v2[dep], c[ref], v1[ref], v2[ref])
    dep, ref = dep[keep], ref[keep]
    rows = np.stack([c[dep], v1[dep], v2[dep], c[ref], v1[ref], v2[ref],
                     size[dep]], 1)
    return set(map(tuple, rows.tolist()))


def minimize(cinds: set[tuple]) -> set[tuple]:
    """Drop implied CINDs, checking direct implications only and keeping
    every 1/2 CIND, as the reference RDFind's --clean-implied does."""
    def fam(c):
        return (2 if is_binary(c[0]) else 1, 2 if is_binary(c[3]) else 1)

    c11 = {c for c in cinds if fam(c) == (1, 1)}
    c12 = {c for c in cinds if fam(c) == (1, 2)}
    c21 = {c for c in cinds if fam(c) == (2, 1)}
    c22 = {c for c in cinds if fam(c) == (2, 2)}

    def subs(code, v1, v2):
        return ((first_subcapture(code), v1, NO_VALUE),
                (second_subcapture(code), v2, NO_VALUE))

    # 2/1 implied by 1/1: same ref, the 1/1's dep a subcapture of its dep.
    implying = {(c[3:6], c[0:3]) for c in c11}
    m21 = {c for c in c21
           if not any((c[3:6], s) in implying for s in subs(*c[0:3]))}
    # 2/1 implied by 2/2: same dep, its ref a subcapture of the 2/2's ref.
    implying = {(c[0:3], s) for c in c22 for s in subs(*c[3:6])}
    m21 = {c for c in m21 if (c[0:3], c[3:6]) not in implying}
    # 1/1 implied by 1/2: same dep, its ref a subcapture of the 1/2's ref.
    implying = {(c[0:3], s) for c in c12 for s in subs(*c[3:6])}
    m11 = {c for c in c11 if (c[0:3], c[3:6]) not in implying}
    # 2/2 implied by 1/2: same ref, the 1/2's dep a subcapture of its dep.
    implying = {(c[3:6], c[0:3]) for c in c12}
    m22 = {c for c in c22
           if not any((c[3:6], s) in implying for s in subs(*c[0:3]))}
    return m11 | m21 | c12 | m22


def minimal_cinds(triples: np.ndarray, support: int,
                  fold_bits: int | None = None):
    """(minimal CIND set, Captures) of a graph."""
    caps = captures(triples, support, fold_bits)
    return minimize(raw_cinds(caps, support)), caps
