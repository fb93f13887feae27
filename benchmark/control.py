"""The control of the comparison that decides `correct`.

    python3 benchmark/control.py --workload <cell> --seeds 1 2 3

For each seed, the cell's input is built as a run builds it, and the
reference computed with the projected value ids cut to 16 bits (the
containment then runs on narrower ids, as a sketch or a narrower
dictionary would) is put in the program's place and compared with the
exact reference by benchmark/check.py.  Prints one JSON line per seed with
the compared numbers; every seed has to come out not correct.  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from benchmark import check, gen, reference, run  # noqa: E402

FOLD_BITS = 16


def table_text(cinds) -> str:
    """A CIND set in the --output spelling."""
    ch = {1: "s", 2: "p", 4: "o"}

    def cap(code, v1, v2):
        conds = [b for b in (1, 2, 4) if code & b]
        vals = [f"{ch[conds[0]]}=<v{v1:09d}>"]
        if v2 != reference.NO_VALUE:
            vals.append(f"{ch[conds[1]]}=<v{v2:09d}>")
        return f"{ch[code >> 3]}[{','.join(vals)}]"

    return "".join(f"{cap(*c[0:3])} < {cap(*c[3:6])} (support={c[6]})\n"
                   for c in sorted(cinds))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    cell = run.find_cell(bench, args.workload)
    config = run.load_json(run.BENCH_DIR, "configs", f"{cell['config']}.json")
    support = run.load_json(run.BENCH_DIR, "traffic",
                            f"{cell['traffic']}.json")["support"]
    graph = run.graph_for(config)
    all_fail = True
    for seed in args.seeds:
        t0 = time.perf_counter()
        triples = gen.relabel(graph, seed)
        want, _ = reference.minimal_cinds(triples, support)
        ctrl, _ = reference.minimal_cinds(triples, support, FOLD_BITS)
        checks = check.compare([table_text(ctrl)], want)
        ok = check.passed(checks)
        all_fail &= not ok
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_correct": ok, "checks": checks,
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0 if all_fail else 1


if __name__ == "__main__":
    sys.exit(main())
