"""freq_cond_s: device seconds of the traced discovery under the
`frequent_conditions` scope: the frequent-condition counts over the triple
table (ops/frequency.py) at the head of the join-line build
(benchmark/scopes.py)."""

from benchmark import scopes


def read(run):
    return scopes.read(run, "frequent_conditions")
