"""Record the trace that `test_scopes.py` reduces.

    python3 benchmark/tests/make_scopes_fixture.py OUT.pbtxt.gz

On the chip: one run of a small LUBM cell (LUBM(2), support 100, strategy
0, so the join-line build `_stage_prepare` runs its four device scopes)
with --trace 1, then the traced discovery's profile trimmed to what
`scopes.py` and `trace.py` read: the window's host line, and each TPU
plane's "XLA Modules" line and "XLA Ops" line inside the window, each op
with its op-name path as a stat of its metadata (where the chip's profiler
puts it), as a gzipped XSpace text proto.
"""

from __future__ import annotations

import gzip
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import run, scopes, trace  # noqa: E402
from benchmark.tests.make_fixture import _quote  # noqa: E402

CELL = "scopes.aao"
BENCH = {
    "workloads": [{"name": CELL, "config": "scopes", "traffic": "aao",
                   "chips": 1}],
    "end_to_end": [], "per_layer": [],
}
CONFIG = {
    "name": "scopes", "projections": "spo",
    "flags": ["--use-fis", "--clean-implied"],
    "generator": {"kind": "lubm", "universities": 2, "structure_seed": 0},
}
TRAFFIC = {"support": 100, "flags": ["--traversal-strategy", "0"]}


def trim(raw, profile) -> str:
    """The XSpace text proto of what scopes.by_scope and trace.reduce
    read."""
    lo, hi, host_line = trace._window(list(profile.planes))
    ops = scopes.device_ops(raw)
    out = []
    host = ["  lines { id: 1 name: " + _quote(host_line.name)
            + " timestamp_ns: 0"]
    names: dict = {}
    for ev in host_line.events:
        if ev.start_ns + ev.duration_ns <= lo or ev.start_ns >= hi:
            continue
        mid = names.setdefault(ev.name, len(names) + 1)
        host.append(f"    events {{ metadata_id: {mid} "
                    f"offset_ps: {round((ev.start_ns - lo) * 1000)} "
                    f"duration_ps: {round(ev.duration_ns * 1000)} }}")
    host.append("  }")
    host += [f"  event_metadata {{ key: {i} value {{ id: {i} "
             f"name: {_quote(n)} }} }}" for n, i in names.items()]
    out.append("planes {\n  id: 1 name: \"/host:CPU\"\n" + "\n".join(host)
               + "\n}")
    stat_id = 1
    for pid, plane in enumerate(profile.planes, 2):
        if not trace._DEVICE_PLANE.match(plane.name):
            continue
        body, metas = [], {}
        mods = next((ln for ln in plane.lines
                     if ln.name == trace.MODULES_LINE), None)
        if mods is not None:
            evs = []
            for ev in mods.events:
                if ev.start_ns + ev.duration_ns <= lo or ev.start_ns >= hi:
                    continue
                mid = metas.setdefault((ev.name, ""), len(metas) + 1)
                evs.append(f"    events {{ metadata_id: {mid} "
                           f"offset_ps: {round((ev.start_ns - lo) * 1000)} "
                           f"duration_ps: {round(ev.duration_ns * 1000)} }}")
            body.append(f"  lines {{ id: 2 name: {_quote(trace.MODULES_LINE)}"
                        f" timestamp_ns: 0\n" + "\n".join(evs) + "\n  }")
        starts, ends, op_names, paths = ops[plane.name]
        evs = []
        for s, e, name, path in zip(starts, ends, op_names, paths):
            if e <= lo or s >= hi:
                continue
            mid = metas.setdefault((name, path), len(metas) + 1)
            evs.append(f"    events {{ metadata_id: {mid} "
                       f"offset_ps: {round((s - lo) * 1000)} "
                       f"duration_ps: {round((e - s) * 1000)} }}")
        body.append(f"  lines {{ id: 1 name: {_quote(trace.OPS_LINE)} "
                    f"timestamp_ns: 0\n" + "\n".join(evs) + "\n  }")
        for (name, path), i in metas.items():
            stat = (f" stats {{ metadata_id: {stat_id} "
                    f"str_value: {_quote(path)} }}" if path else "")
            body.append(f"  event_metadata {{ key: {i} value {{ id: {i} "
                        f"name: {_quote(name)}{stat} }} }}")
        body.append(f"  stat_metadata {{ key: {stat_id} value {{ "
                    f"id: {stat_id} name: {_quote(scopes.OP_NAME_STATS[0])}"
                    f" }} }}")
        out.append(f"planes {{\n  id: {pid} name: {_quote(plane.name)}\n"
                   + "\n".join(body) + "\n}")
    return "\n".join(out) + "\n"


def main(out_path: str) -> int:
    args = run.parse_args(["--workload", CELL, "--seed", "5",
                           "--seconds", "1", "--trace", "1"])
    res = run.run_cell(args, bench=BENCH, config=CONFIG, traffic=TRAFFIC)
    print(res, file=sys.stderr)
    path = scopes.trace_file(CELL)
    if path is None:
        return 1
    with gzip.open(out_path, "wt") as f:
        f.write(trim(memoryview(scopes._raw(path)), trace.load(path)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
