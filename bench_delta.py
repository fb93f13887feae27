"""Benchmark: incremental (--delta) discovery vs a from-scratch re-run.

Grows a planted-CIND workload (utils/synth.generate_planted_cinds — the
CIND-dense generator whose rules never interact, so join lines stay small
and uniform like a wide-schema dataset; the zipf generators' hub values
would let a single touched triple dirty a quarter of the evidence, which
benchmarks the fallback ladder, not incrementality) with 0.1% / 1% / 10%
insert+delete change batches (utils/synth.grow_delta_batches — half
recombinations, half brand-new values, so the dictionary tail and new
buckets are exercised).  For each batch size it measures end-to-end wall
of

  * full     — a from-scratch driver run over the updated dataset, and
  * delta    — the --delta replay of just the batch against a persisted
               base bundle (a fresh copy per size: a delta run advances
               its bundle's generation in place),

asserts the two tables are bit-identical (a speedup over a wrong answer is
worthless), and reports ``delta_speedup`` (full wall / delta wall) and
``frac_passes_rerun`` per size.  The paper's promise is cost proportional
to the change: speedup should fall and frac_passes_rerun rise as the batch
grows.

Prints ONE JSON line (bench.py shape) and appends a provenance-keyed row
to BENCH_HISTORY.jsonl for the regression sentinel.  The row's workload
stamp is distinct from bench.py's, so output digests never cross-compare.

Env: BENCH_DELTA_TRIPLES (default 8000, rounded to whole planted rules),
BENCH_DELTA_MIN_SUPPORT (10), JAX_PLATFORMS=cpu measures the CPU on purpose,
BENCH_HISTORY as in bench.py.
"""

import json
import os
import shutil
import sys
import tempfile
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench import _record_history, require_device  # noqa: E402
from rdfind_tpu.obs import integrity as obs_integrity  # noqa: E402
from rdfind_tpu.obs import sentinel as obs_sentinel  # noqa: E402

FRACS = ((0.001, "d01pct"), (0.01, "d1pct"), (0.1, "d10pct"))


def _timed_run(cfg_kwargs):
    from rdfind_tpu.runtime import driver
    t0 = time.perf_counter()
    res = driver.run(driver.Config(**cfg_kwargs))
    return res, time.perf_counter() - t0


def _run(n: int, min_support: int, backend: str) -> dict:
    from rdfind_tpu.utils import synth

    # One planted rule is ~(ref_size + support) * 4 + spoilers triples;
    # size the rule count to land near the requested n.
    support = min_support + 2
    ref_size = support + max(support // 4, 8)
    per_rule = (ref_size + support) * 4 + 4 * max(2, support // 8)
    n_rules = max(4, n // per_rule)
    triples, _expected = synth.generate_planted_cinds(n_rules, support)
    n = int(triples.shape[0])
    detail = {"backend": backend,
              "provenance": obs_sentinel.provenance(backend=backend),
              "n_triples": n, "n_rules": n_rules,
              "min_support": min_support}
    delta_detail = {}
    headline = None

    with tempfile.TemporaryDirectory() as root:
        base_nt = os.path.join(root, "base.nt")
        synth.write_nt(base_nt, triples)

        # One base run persists the bundle (warm-up for the jit cache too);
        # each batch size replays against its own copy.
        bundle0 = os.path.join(root, "bundle0")
        base_res, base_wall = _timed_run(dict(
            input_paths=[base_nt], min_support=min_support,
            traversal_strategy=0, delta_state=bundle0))
        detail["base_wall_s"] = round(base_wall, 3)
        detail["base_cinds"] = len(base_res.table)

        for frac, key in FRACS:
            ins, dels = synth.grow_delta_batches(triples, frac, seed=7)
            p_ins = os.path.join(root, f"{key}_ins.nt")
            p_del = os.path.join(root, f"{key}_del.nt")
            p_upd = os.path.join(root, f"{key}_upd.nt")
            synth.write_nt(p_ins, ins)
            synth.write_nt(p_del, dels)
            synth.write_nt(p_upd, synth.apply_delta(triples, ins, dels))
            bundle = os.path.join(root, f"bundle_{key}")
            shutil.copytree(bundle0, bundle)

            full_res, full_wall = _timed_run(dict(
                input_paths=[p_upd], min_support=min_support,
                traversal_strategy=0))
            delta_res, delta_wall = _timed_run(dict(
                input_paths=[p_ins], delete_paths=[p_del],
                min_support=min_support, traversal_strategy=0,
                delta_base=bundle))
            if obs_integrity.digest_table(full_res.table) != \
                    obs_integrity.digest_table(delta_res.table):
                raise AssertionError(
                    f"{key}: delta output is not bit-identical to "
                    "from-scratch — refusing to report a speedup")
            st = delta_res.counters.get("stat-delta", {})
            n_passes = max(int(st.get("n_passes", 0)), 1)
            row = {
                "frac": frac,
                "inserts": len(ins), "deletes": len(dels),
                "full_wall_s": round(full_wall, 3),
                "delta_wall_s": round(delta_wall, 3),
                "delta_speedup": round(full_wall / max(delta_wall, 1e-9),
                                       2),
                "path": st.get("path"),
                "passes_rerun": int(st.get("passes_rerun", 0)),
                "frac_passes_rerun": round(
                    int(st.get("passes_rerun", 0)) / n_passes, 4),
                "dirty_row_frac": st.get("dirty_row_frac"),
                "cinds": len(delta_res.table),
            }
            delta_detail[key] = row
            print(f"bench_delta: {key} ({frac:.1%}) full {full_wall:.2f}s "
                  f"vs delta {delta_wall:.2f}s = "
                  f"{row['delta_speedup']}x, "
                  f"{row['passes_rerun']}/{n_passes} passes re-run "
                  f"[{row['path']}]", file=sys.stderr, flush=True)
            if key == "d1pct":
                headline = row["delta_speedup"]
                # Digest + workload stamp for the sentinel's correctness
                # gate (distinct from bench.py's workload by construction).
                detail["output_digest"] = obs_integrity.digest_hex(
                    *obs_integrity.digest_table(delta_res.table))
                detail["workload"] = {"bench": "delta", "n_triples": n,
                                      "min_support": min_support,
                                      "frac": frac, "seed": 42}

    detail["delta"] = delta_detail
    return {
        "metric": "delta_speedup_1pct",
        "value": headline if headline is not None else 0,
        "unit": "x",
        "vs_baseline": headline if headline is not None else 0,
        "detail": detail,
    }


def main():
    n = int(os.environ.get("BENCH_DELTA_TRIPLES", 8_000))
    min_support = int(os.environ.get("BENCH_DELTA_MIN_SUPPORT", 10))
    device = require_device()
    try:
        result = _run(n, min_support, device["platform"])
    except Exception as e:
        tb = traceback.format_exc(limit=3)
        result = {
            "metric": "delta_speedup_1pct", "value": 0, "unit": "x",
            "vs_baseline": 0,
            "detail": {"error": f"{type(e).__name__}: {e}",
                       "traceback": tb.splitlines()[-3:]},
        }
    result["device"] = device
    print(json.dumps(result, default=str))
    _record_history(result)


if __name__ == "__main__":
    main()
