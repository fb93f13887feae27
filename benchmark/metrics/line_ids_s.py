"""line_ids_s: device seconds of the traced discovery under the `line_ids`
scope: the sort and scatter that number the join lines
(ops/segments.py `masked_dense_ids`, or the line-row `masked_unique` of the
chunked path), the last step of the join-line build
(benchmark/scopes.py)."""

from benchmark import scopes


def read(run):
    return scopes.read(run, "line_ids")
