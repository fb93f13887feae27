"""pair_kernel_s: device seconds of the pair phase's dense kernels in the
traced discovery: the programs (the trace's "XLA Modules" line) that
build the line-by-capture membership (`_stage_membership`), count its
non-empty blocks (`_stage_block_counts`), and run the fused verdict
kernel over it (`_fused_cind_tile`); see models/allatonce.py, ops/cooc.py
and ops/pallas_kernels.py.  Strategy 1 builds the same membership before
its lattice.  Nothing when none of them ran."""

import re

PROGRAMS = re.compile(r"^jit__(stage_membership|stage_block_counts"
                      r"|fused_cind_tile)\(")


def read(run):
    if run.trace is None:
        return None
    s = sum(v for k, v in run.trace.module_s.items() if PROGRAMS.search(k))
    return s if s > 0 else None
