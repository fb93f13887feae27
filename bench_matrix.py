"""Benchmark matrix: BASELINE.md configs 1-2 across traversal strategies.

Fills the BASELINE.md measurement table (the reference's always-reporting
measurement machinery, AbstractFlinkProgram.java:65-77,175-182): one row per
(config, strategy) with wall-clock, pairs/s/chip, and CIND counts.

  Config 1: LUBM-1-shaped synthetic (~100k triples), support >= 10.
            "Unary CINDs only" is reported as the 1/1-family slice of the
            full output (the pipeline has no unary-only mode, like the
            reference, which always mines all four families).
  Config 2: DBpedia-person-slice-shaped synthetic (~2M triples),
            unary+binary, support >= 100.

Usage: python bench_matrix.py [--configs 1,2] [--strategies 0,1,2,3]
                              [--dtypes int8,bf16] [--plane-bits 8,4,2]
                              [--emit 0,1] [--hier off,0,1]
Prints one JSON line per row, then a summary table on stderr.  --dtypes adds
one row per cooc membership dtype (int8 rides the doubled int8 MXU peak and
is exact via int32 accumulation; pass "auto" for the probe-resolved default).
--hier adds a pod-scale exchange axis: "off" (default) keeps the
single-device models; "0"/"1"/"auto" run the SHARDED pipeline with
RDFIND_HIER_EXCHANGE pinned to that value (on a single-host run a 2-host
pod is modeled via --hier-hosts so the ICI/DCN ledger split is
meaningful), and each such row records the exchange byte totals.

CIND-count note: strategies 0/2 emit every CIND; small-to-large (1) and
late-BB (3) emit their raw forms, whose 2/1 and 2/2 families omit
1/x-implied members by construction (the reference's behavior for both) —
so their totals are lower while the 1/1 and 1/2 families match exactly.
"""

import argparse
import json
import os
import sys
import time


CONFIGS = {
    1: dict(n=100_000, min_support=10, seed=101,
            synth=dict(n_predicates=18, n_entities=17_000),
            label="LUBM-1-shaped 100k, support>=10"),
    2: dict(n=2_000_000, min_support=100, seed=202,
            # IID synthetic data cannot sustain exact containment at support
            # 100 (both first cuts found zero CINDs at 2M), so config 2 gets
            # the structural-inclusion overlay real RDF has — see
            # utils/synth.inject_cind_structure.
            synth=dict(n_predicates=64, n_entities=60_000),
            structured=True,
            label="person-slice-shaped 2M, unary+binary, support>=100"),
}


def run_one(config_id: int, strategy: int, dtype: str = "auto",
            plane_bits: str = "auto", fuse: str = "auto",
            emit: str = "auto", hier: str = "off",
            hier_hosts: int = 2) -> dict:
    from rdfind_tpu.models import (allatonce, approximate, late_bb,
                                   small_to_large)
    from rdfind_tpu.ops import cooc
    from rdfind_tpu.utils.synth import generate_triples

    spec = CONFIGS[config_id]
    triples = generate_triples(spec["n"], seed=spec["seed"], **spec["synth"])
    if spec.get("structured"):
        from rdfind_tpu.utils.synth import inject_cind_structure
        triples = inject_cind_structure(triples)

    if dtype not in ("auto", "bf16", "int8"):
        raise ValueError(f"dtype must be auto, bf16 or int8, got {dtype!r}")
    if plane_bits not in ("auto", "2", "4", "8"):
        raise ValueError(f"plane bits must be auto, 2, 4 or 8, "
                         f"got {plane_bits!r}")
    if fuse not in ("auto", "0", "1"):
        raise ValueError(f"fuse must be auto, 0 or 1, got {fuse!r}")
    if emit not in ("auto", "0", "1"):
        raise ValueError(f"emit must be auto, 0 or 1, got {emit!r}")
    if hier not in ("off", "0", "1", "auto"):
        raise ValueError(f"hier must be off, 0, 1 or auto, got {hier!r}")

    hier_extra = {}
    if hier == "off":
        discover = {0: allatonce.discover, 1: small_to_large.discover,
                    2: approximate.discover, 3: late_bb.discover}[strategy]
        run = lambda stats: discover(triples, spec["min_support"],  # noqa: E731
                                     stats=stats)
    else:
        # Pod-scale axis: the sharded pipeline with the two-level exchange
        # pinned to this row's knob (flat vs hierarchical over the same
        # mesh).  env is the knob's contract, saved/restored below.
        from rdfind_tpu.models import sharded
        from rdfind_tpu.parallel import mesh as mesh_mod
        sharded_fn = {0: sharded.discover_sharded,
                      1: sharded.discover_sharded_s2l,
                      2: sharded.discover_sharded_approx,
                      3: sharded.discover_sharded_late_bb}[strategy]
        mesh = mesh_mod.make_mesh()
        run = lambda stats: sharded_fn(triples, spec["min_support"],  # noqa: E731
                                       mesh=mesh, use_fis=True, stats=stats)

    saved = (cooc.COOC_DTYPE, cooc.PLANE_BITS, cooc.FUSE_VERDICT,
             cooc.EMIT_PIPELINE)
    saved_env = {k: os.environ.get(k)
                 for k in ("RDFIND_HIER_EXCHANGE", "RDFIND_HIER_HOSTS")}
    (cooc.COOC_DTYPE, cooc.PLANE_BITS, cooc.FUSE_VERDICT,
     cooc.EMIT_PIPELINE) = (dtype, plane_bits, fuse, emit)
    try:
        if hier != "off":
            os.environ["RDFIND_HIER_EXCHANGE"] = hier
            num_dev = int(mesh.devices.size)
            if (mesh_mod.topology_hosts(num_dev) == 1
                    and num_dev % hier_hosts == 0):
                os.environ["RDFIND_HIER_HOSTS"] = str(hier_hosts)
        stats: dict = {}
        run(stats)  # warm (compile)
        stats = {}
        t0 = time.perf_counter()
        table = run(stats)
        wall = time.perf_counter() - t0
        if hier != "off":
            sites = stats.get("exchange_sites", {})
            hier_extra = {
                "hier": hier,
                "hosts": mesh_mod.topology_hosts(int(mesh.devices.size)),
                "exchange_bytes": sum(e["bytes"] for e in sites.values()),
                "ici_bytes": sum(e["ici_bytes"] for e in sites.values()),
                "dcn_bytes": sum(e["dcn_bytes"] for e in sites.values()),
                "overlap_efficiency": (stats.get("overlap")
                                       or {}).get("overlap_efficiency"),
            }
    finally:
        (cooc.COOC_DTYPE, cooc.PLANE_BITS, cooc.FUSE_VERDICT,
         cooc.EMIT_PIPELINE) = saved
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    total_pairs = int(stats.get("total_pairs", 0))
    return {
        **hier_extra,
        "config": config_id,
        "label": spec["label"],
        "strategy": strategy,
        "cooc_dtype": stats.get("cooc_dtype", dtype),
        "plane_bits": stats.get("plane_bits"),
        "fuse_verdict": fuse,
        # The full knob->decision struct (probes included): one glance says
        # what kernel actually ran in this cell.
        "kernel_resolution": stats.get("kernel_resolution"),
        "n_blocks_skipped": stats.get("n_blocks_skipped"),
        "dense_plan": stats.get("dense_plan"),
        "wall_s": round(wall, 3),
        "total_pairs": total_pairs,
        "pairs_per_sec_per_chip": round(total_pairs / wall, 1) if wall else 0,
        "cinds": len(table),
        "cind_families": table.family_counts(),
        "n_triples": int(len(triples)),
        "min_support": spec["min_support"],
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", default="1,2")
    ap.add_argument("--strategies", default="0,1,2,3")
    ap.add_argument("--dtypes", default="int8,bf16",
                    help="cooc membership dtypes, one row each "
                         "(int8 | bf16 | auto)")
    ap.add_argument("--plane-bits", default="auto",
                    help="containment-kernel plane widths, one row each "
                         "(8 | 4 | 2 | auto; 4 = nibble planes, 2 = crumb "
                         "planes, each engaging natively only where the "
                         "matching MXU probe lowers)")
    ap.add_argument("--fuse", default="auto",
                    help="fused-verdict modes, one row each (0 | 1 | auto)")
    ap.add_argument("--emit", default="auto",
                    help="emit_pipeline K-loop modes for the packed "
                         "containment kernel, one row each (0 | 1 | auto; "
                         "falls back byte-identically off TPU)")
    ap.add_argument("--hier", default="off",
                    help="pod-scale exchange modes, one row each (off = "
                         "single-device models; 0 | 1 | auto = sharded "
                         "pipeline with RDFIND_HIER_EXCHANGE pinned)")
    ap.add_argument("--hier-hosts", type=int, default=2,
                    help="host count modeled on single-host runs for the "
                         "--hier rows' ICI/DCN attribution")
    args = ap.parse_args()

    # No accelerator is an error unless JAX_PLATFORMS=cpu was pinned; every
    # row names the device it ran on.
    from bench import require_device
    from rdfind_tpu.obs import sentinel as obs_sentinel
    from rdfind_tpu.runtime import compile_cache
    device = require_device()
    compile_cache.place()
    backend = device["platform"]
    print(f"device: {device}", file=sys.stderr)
    # Shared row identity (git sha, core count, knob set): resolved once —
    # run_one's own env overrides are per-cell parameters already recorded in
    # the row, not ambient provenance.
    prov = obs_sentinel.provenance(backend=backend)

    rows = []
    for cid in (int(c) for c in args.configs.split(",")):
        for strat in (int(s) for s in args.strategies.split(",")):
            for dtype in args.dtypes.split(","):
                for pb in args.plane_bits.split(","):
                    for fuse in args.fuse.split(","):
                        for emit in args.emit.split(","):
                            for hier in args.hier.split(","):
                                try:
                                    row = run_one(cid, strat,
                                                  dtype=dtype.strip(),
                                                  plane_bits=pb.strip(),
                                                  fuse=fuse.strip(),
                                                  emit=emit.strip(),
                                                  hier=hier.strip(),
                                                  hier_hosts=args.hier_hosts)
                                except Exception as e:  # keep reporting
                                    row = {"config": cid, "strategy": strat,
                                           "cooc_dtype": dtype.strip(),
                                           "plane_bits": pb.strip(),
                                           "fuse_verdict": fuse.strip(),
                                           "emit_pipeline": emit.strip(),
                                           "hier": hier.strip(),
                                           "error":
                                               f"{type(e).__name__}: {e}"}
                                row["device"] = device
                                row["provenance"] = prov
                                rows.append(row)
                                print(json.dumps(row), flush=True)

    print(f"{'cfg':>3} {'strat':>5} {'dtype':>5} {'wall_s':>9} "
          f"{'Mpairs/s':>9} {'cinds':>8}", file=sys.stderr)
    for r in rows:
        if "error" in r:
            print(f"{r['config']:>3} {r['strategy']:>5} "
                  f"{r.get('cooc_dtype', '?'):>5} ERROR {r['error']}",
                  file=sys.stderr)
        else:
            print(f"{r['config']:>3} {r['strategy']:>5} "
                  f"{r['cooc_dtype']:>5} {r['wall_s']:>9.2f} "
                  f"{r['pairs_per_sec_per_chip'] / 1e6:>9.2f} "
                  f"{r['cinds']:>8}", file=sys.stderr)


if __name__ == "__main__":
    main()
