"""pair_roofline: the least time the input's pair work needs on this chip,
as a percentage of pair_kernel_s.

The work comes from the generated input alone (benchmark/reference.py):
ops = 2 x the (line, capture, capture) instances, the sum over join
lines of (frequent captures on the line)^2; bytes = the smaller of two
encodings of the line-capture incidence, 4 bytes per incidence or one
bit per (line, capture) cell.  The least time is the larger of ops over
the int8 peak and bytes over HBM bandwidth (benchmark/peaks.py).  Any
exact implementation does at least this work, so the share cannot pass
100%."""

from benchmark.metrics import pair_kernel_s


def least_time(work, peaks):
    """(seconds, bound) of the least time for `work` at `peaks`."""
    ops = 2.0 * work["pair_instances"]
    nbytes = min(4.0 * work["incidences"],
                 work["lines"] * work["captures"] / 8.0)
    t_ops = ops / peaks["int8_ops"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "int8 ops") if t_ops >= t_bytes else (t_bytes, "HBM bytes")


def read(run):
    kernel_s = pair_kernel_s.read(run)
    if not kernel_s:
        return None
    return 100.0 * least_time(run.work, run.peaks)[0] / kernel_s
