"""discover_s: mean seconds per window discovery in the `discover` phase
of runtime/driver.py (RunResult.timings): frequent conditions, join lines,
the pair phase and minimality."""


def read(run):
    vals = [d.timings["discover"] for d in run.window
            if "discover" in d.timings]
    return sum(vals) / len(vals) if vals else None
