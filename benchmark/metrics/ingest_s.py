"""ingest_s: mean seconds per window discovery in ingest and interning,
the `read+parse`, `intern` and `distinct` phases of runtime/driver.py
(RunResult.timings)."""

PHASES = ("read+parse", "intern", "distinct")


def read(run):
    if not run.window:
        return None
    return sum(sum(d.timings.get(k, 0.0) for k in PHASES)
               for d in run.window) / len(run.window)
