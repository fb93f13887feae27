"""The comparison that decides `correct`: each CIND table a discovery wrote
with --output against the reference's minimal CIND set.

A table line reads `s[p=<v000000007>] < o[s=<v000000001>,p=<v000000002>]
(support=12)`.  Rows are compared whole: both captures with their values,
and the support.  The limits are 0: the comparison is exact.
"""

from __future__ import annotations

import re

from benchmark.gen import token_id
from benchmark.reference import NO_VALUE, code_of

_BIT = {"s": 1, "p": 2, "o": 4}
_CAPTURE = r"([spo])\[([spo])=(<[^>]*>)(?:,([spo])=(<[^>]*>))?\]"
_LINE = re.compile(rf"^{_CAPTURE} < {_CAPTURE} \(support=(\d+)\)$")

LIMITS = {"missing_rows": 0, "extra_rows": 0, "tables_wrong": 0}


def _capture(proj, f1, t1, f2, t2):
    cond = _BIT[f1] | (_BIT[f2] if f2 else 0)
    return (code_of(cond, _BIT[proj]), token_id(t1),
            token_id(t2) if f2 else NO_VALUE)


def parse_table(text: str) -> set[tuple]:
    """An --output table -> set of 7-tuples (dep_code, dep_v1, dep_v2,
    ref_code, ref_v1, ref_v2, support); a line that does not parse is a
    row of its own that no reference holds."""
    rows = set()
    for line in text.splitlines():
        if not line.strip():
            continue
        m = _LINE.match(line)
        if m is None:
            rows.add(("unparsed", line))
            continue
        g = m.groups()
        rows.add(_capture(*g[0:5]) + _capture(*g[5:10]) + (int(g[10]),))
    return rows


def compare(tables: list[str], want: set[tuple]) -> dict:
    """The compared numbers, each with its limit: the most rows any table
    lacks or adds against the reference, and how many tables differ."""
    missing = extra = wrong = 0
    for text in tables:
        got = parse_table(text)
        missing = max(missing, len(want - got))
        extra = max(extra, len(got - want))
        wrong += got != want
    values = {"missing_rows": missing, "extra_rows": extra,
              "tables_wrong": wrong}
    out = {k: {"value": v, "limit": LIMITS[k]} for k, v in values.items()}
    out["reference_cinds"] = len(want)
    return out


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for k, c in checks.items()
               if k in LIMITS)
