"""exchange_s: device seconds of the traced discovery under the program's
`exchange` scope, the mean over the cell's chips: every all_to_all,
all_gather and psum of the sharded stages (models/sharded_dense.py), the
incidences' move to the owner of their value among them.  Each op instant
goes to the op that started last (benchmark/scopes.py).  Nothing where the
program has no such scope or nothing ran under it."""

import functools

import numpy as np

from benchmark import scopes

def scope_name():
    """The program's exchange scope, or None for a program without."""
    try:
        from rdfind_tpu.obs.tracer import SCOPE_EXCHANGE
    except ImportError:
        return None
    return SCOPE_EXCHANGE


def per_chip(run) -> dict:
    """{device plane: seconds under the exchange scope} of the traced
    discovery; empty where there is nothing to read."""
    scope = scope_name()
    path = (scopes.trace_file(run.cell["name"])
            if run.trace is not None and scope is not None else None)
    return {} if path is None else dict(_by_plane(path, scope))


@functools.lru_cache(maxsize=None)
def _by_plane(path: str, scope: str) -> tuple:
    """((plane, seconds under `scope`), ...) of the trace at `path`, read
    once per file (exchange_roofline reads it too)."""
    from benchmark import trace

    lo, hi, _ = trace._window(list(trace.load(path).planes))
    out = {}
    raw = memoryview(scopes._raw(path))
    for plane, (starts, ends, _, paths) in scopes.device_ops(raw).items():
        keep = (ends > lo) & (starts < hi)
        labels = [scopes.scope_of(p, (scope,))
                  for p, k in zip(paths, keep) if k]
        got = scopes._latest_start_wins(np.maximum(starts[keep], lo),
                                        np.minimum(ends[keep], hi), labels)
        out[plane] = float(got.get(scope, 0.0))
    return tuple(out.items())


def read(run):
    chips = per_chip(run)
    mean = sum(chips.values()) / len(chips) if chips else 0.0
    return mean if mean > 0 else None
