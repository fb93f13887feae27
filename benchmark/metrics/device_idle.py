"""device_idle: the share of the traced discovery in which no operation
ran on the device, 1 - busy / window, from the profiler trace
(benchmark/trace.py)."""


def read(run):
    if run.trace is None:
        return None
    return run.trace.idle_share
