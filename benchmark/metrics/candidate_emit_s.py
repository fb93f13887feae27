"""candidate_emit_s: device seconds of the traced discovery under the
`emit_candidates` scope: the join candidates emitted per triple
(ops/emission.py), the first step of the join-line build
(benchmark/scopes.py)."""

from benchmark import scopes


def read(run):
    return scopes.read(run, "emit_candidates")
