"""capture_intern_s: device seconds of the traced discovery under the
`intern_captures` scope: the sort and scatters that give each distinct
capture of the candidates a dense id (ops/segments.py `masked_unique`), in
the join-line build (benchmark/scopes.py)."""

from benchmark import scopes


def read(run):
    return scopes.read(run, "intern_captures")
