"""Record the small trace that `test_trace.py` reduces.

    python3 benchmark/tests/make_fixture.py OUT.pbtxt.gz

On the chip: one run of a small all-at-once cell (20k triples plus the
planted inclusion overlay, support 100, so the fused verdict kernel runs)
with --trace 1, then the traced discovery's profile trimmed to what the
reduction reads: the window's host line and each TPU plane's "XLA Ops"
and "XLA Modules" lines, inside the window, written as a gzipped XSpace
text proto.
"""

from __future__ import annotations

import gzip
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import run, trace  # noqa: E402

CELL = "fixture.aao"
BENCH = {
    "workloads": [{"name": CELL, "config": "fixture", "traffic": "aao",
                   "chips": 1}],
    "end_to_end": [], "per_layer": [],
}
CONFIG = {
    "name": "fixture", "projections": "spo",
    "flags": ["--use-fis", "--clean-implied"],
    "generator": {"kind": "triples_with_overlay", "n_triples": 20000,
                  "structure_seed": 7, "n_predicates": 16,
                  "n_entities": 2000,
                  "overlay": {"n_rules": 32, "ref_size": 150,
                              "dep_size": 120}},
}
TRAFFIC = {"support": 100, "flags": ["--traversal-strategy", "0"]}


def _quote(s: str) -> str:
    return '"' + s.replace("\\", "\\\\").replace('"', '\\"') + '"'


def trim(profile) -> str:
    """The XSpace text proto of what trace.reduce reads."""
    lo, hi, host_line = trace._window(list(profile.planes))
    keep = [("/host:CPU", [host_line])]
    for plane in profile.planes:
        if trace._DEVICE_PLANE.match(plane.name):
            keep.append((plane.name, [ln for ln in plane.lines if ln.name in
                                      (trace.OPS_LINE, trace.MODULES_LINE)]))
    out = []
    for pid, (pname, lines) in enumerate(keep, 1):
        names: dict = {}
        body = []
        for lid, line in enumerate(lines, 1):
            evs = []
            for ev in line.events:
                if ev.start_ns + ev.duration_ns <= lo or ev.start_ns >= hi:
                    continue
                mid = names.setdefault(ev.name, len(names) + 1)
                evs.append(f"    events {{ metadata_id: {mid} "
                           f"offset_ps: {round((ev.start_ns - lo) * 1000)} "
                           f"duration_ps: {round(ev.duration_ns * 1000)} }}")
            body.append(f"  lines {{ id: {lid} name: {_quote(line.name)} "
                        f"timestamp_ns: 0\n" + "\n".join(evs) + "\n  }")
        meta = [f"  event_metadata {{ key: {i} value {{ id: {i} "
                f"name: {_quote(n)} }} }}" for n, i in names.items()]
        out.append(f"planes {{\n  id: {pid} name: {_quote(pname)}\n"
                   + "\n".join(body + meta) + "\n}")
    return "\n".join(out) + "\n"


def main(out_path: str) -> int:
    args = run.parse_args(["--workload", CELL, "--seed", "5",
                           "--seconds", "1", "--trace", "1"])
    res = run.run_cell(args, bench=BENCH, config=CONFIG, traffic=TRAFFIC)
    print(res, file=sys.stderr)
    tdir = os.path.join(run.DATA_DIR, "trace", CELL)
    for dirpath, _, files in os.walk(tdir):
        for name in files:
            if name.endswith(".xplane.pb"):
                text = trim(trace.load(os.path.join(dirpath, name)))
                with gzip.open(out_path, "wt") as f:
                    f.write(text)
                return 0
    return 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
