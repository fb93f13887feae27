"""Chip smoke: CIND discovery end to end on a TPU, through the CLI's main.

    python chip_smoke.py             # one chip: planted oracle + real size
    python chip_smoke.py --chips 4   # four chips: --dop 4 vs one device

One process holds the chip and starts no JAX child.  Each phase prints one
JSON line (its seconds, compile seconds and result) to stdout; the last
line is `{"ok": true, "device": {...}}`, printed only when every phase
passed.  Without a TPU the script exits nonzero before any phase: it has no
CPU branch.

Phases on one chip:
  * correctness — planted CINDs (utils/synth.generate_planted_cinds) from
    --seed, run for strategies 0-3 with --clean-implied; each CIND set must
    equal the host oracle's (oracle.discover_cinds_joinline, then
    oracle.minimize_cinds) exactly;
  * real size — a DBpedia-shaped graph of --triples rows (default 20M: the
    ~50M-triple per-chip share of DBpedia-2016 on a v5e-8, BASELINE.md, cut
    to fit the run's time) at --support 1000, strategies 1 and 0 with
    --clean-implied and --use-fis (frequent-condition pruning, as scale
    runs of the reference use it; without it every infrequent capture
    stays a candidate referenced capture).  The two sets must be
    identical, and a seeded sample of the CINDs is re-checked by numpy set
    inclusion over the triples.
After every phase the kernel modes (ops/cooc.resolution_report) are checked:
a kernel dispatched that the resolved policy rules out fails the smoke.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
import traceback

import numpy as np

# Seconds JAX spent tracing, lowering and compiling, summed over the run;
# read before and after each CLI call so compile time is kept apart.
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
_compile_s = [0.0]

# Driver phase names (runtime/driver.py) grouped into what the smoke reports.
INGEST_PHASES = ("read+parse", "intern", "distinct")
COLLECT_PHASES = ("write-output",)


class SmokeFailure(Exception):
    pass


def _on_duration(event: str, secs: float, **_) -> None:
    if event in COMPILE_EVENTS:
        _compile_s[0] += secs


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=str), flush=True)


def run_cli(argv: list[str]):
    """One in-process CLI run -> (RunResult, wall seconds, compile seconds)."""
    from rdfind_tpu.programs import rdfind

    got = []
    c0, t0 = _compile_s[0], time.perf_counter()
    rc = rdfind.main(argv, on_result=got.append)
    wall = time.perf_counter() - t0
    if rc != 0 or not got:
        raise SmokeFailure(f"rdfind main {argv} exited {rc}")
    return got[0], wall, _compile_s[0] - c0


def pretty_set(result) -> set[str]:
    return {c.pretty() for c in result.decoded()}


def oracle_set(triples: np.ndarray, support: int) -> set[str]:
    """The host oracle's minimal CINDs over the same `<v...>` tokens the
    .nt file holds."""
    from rdfind_tpu import oracle
    from rdfind_tpu.data import Cind
    from rdfind_tpu.utils import synth

    tokens = synth.triples_to_tokens(triples)
    raw = oracle.discover_cinds_joinline(tokens, support)
    return {Cind(*c).pretty() for c in oracle.minimize_cinds(raw)}


def compare_with_oracle(workdir: str, triples: np.ndarray, support: int,
                        strategies=(0, 1, 2, 3)) -> dict:
    """The correctness phase's comparison: write `triples` as .nt, run the
    CLI once per strategy with --clean-implied, and compare each CIND set
    with the oracle's.  Returns {"oracle_s", "oracle_cinds", "strategies":
    {id: {"equal", "cinds", "missing", "extra", "seconds", "compile_s"}}}."""
    from rdfind_tpu.utils import synth

    path = os.path.join(workdir, "planted.nt")
    synth.write_nt(path, triples)
    t0 = time.perf_counter()
    want = oracle_set(triples, support)
    out = {"oracle_s": time.perf_counter() - t0, "oracle_cinds": len(want),
           "strategies": {}}
    for s in strategies:
        res, wall, comp = run_cli([path, "--support", str(support),
                                   "--traversal-strategy", str(s),
                                   "--clean-implied"])
        got = pretty_set(res)
        out["strategies"][s] = {
            "equal": got == want, "cinds": len(got),
            "missing": len(want - got), "extra": len(got - want),
            "seconds": wall, "compile_s": comp}
    return out


def _capture_rows(triples: np.ndarray, code: int, v1, v2) -> np.ndarray:
    """Distinct projected values of one capture, by numpy masks."""
    from rdfind_tpu import conditions as cc

    first, second, _ = cc.decode(cc.primary(code))
    mask = triples[:, cc.FIELD_INDEX[first]] == _token_id(v1)
    if second:
        mask &= triples[:, cc.FIELD_INDEX[second]] == _token_id(v2)
    return np.unique(triples[mask, cc.FIELD_INDEX[cc.secondary(code)]])


def _token_id(token: str) -> int:
    return int(str(token)[2:-1])  # "<v000000123>" -> 123


def sample_check(triples: np.ndarray, cinds: list, k: int, seed: int) -> dict:
    """Re-check a seeded sample of decoded CINDs on the host: the dependent
    capture's values are a subset of the referenced capture's, and their
    count is the reported support."""
    rng = np.random.default_rng(seed)
    cinds = sorted(cinds, key=lambda c: c.pretty())
    pick = rng.choice(len(cinds), size=min(k, len(cinds)), replace=False)
    bad = []
    for i in pick:
        c = cinds[i]
        dep = _capture_rows(triples, c.dep_code, c.dep_v1, c.dep_v2)
        ref = _capture_rows(triples, c.ref_code, c.ref_v1, c.ref_v2)
        if len(dep) != c.support or not np.isin(dep, ref).all():
            bad.append(c.pretty())
    return {"checked": len(pick), "bad": bad}


def check_kernels(report: dict, *, fused: bool = False,
                  contains: bool = False) -> list[str]:
    """Faults in the kernel-mode report of a TPU run: knobs must be at
    their defaults, modes must be what the chip kind resolves to, the
    materialized verdict must not run while fusion is on, and the named
    Pallas paths must have been dispatched."""
    from rdfind_tpu.ops import cooc

    faults = [f"{k}={v}" for k, v in report["knobs"].items() if v != "auto"]
    bits = cooc.SUBBYTE_PLANE_BITS.get(report["device_kind"])
    want = {"cooc_dtype": "int8", "plane_bits": bits,
            "kernel_dtype": "int4" if bits == 4 else "int8",
            "plane_elem": "int4" if bits == 4 else "int8",
            "fuse_verdict": True}
    faults += [f"{k}={report[k]} (want {v})" for k, v in want.items()
               if report[k] != v]
    used = report["kernels_used"]
    if used.get("materialized_verdict"):
        faults.append("materialized verdict ran with fuse_verdict on")
    if fused and not used.get("fused_verdict"):
        faults.append("fused verdict kernel never dispatched")
    grid, emit_ = (used.get("packed_contains", 0),
                   used.get("packed_contains_emit", 0))
    if contains and not grid + emit_:
        faults.append("packed containment kernel never dispatched")
    if contains and report["emit_pipeline"] and not emit_:
        faults.append("emit_pipeline selected but its kernel never ran")
    if not report["emit_pipeline"] and emit_:
        faults.append("emit_pipeline kernel ran with emit_pipeline off")
    return faults


def device_memory(devices) -> list[dict]:
    keys = ("peak_bytes_in_use", "bytes_in_use", "bytes_limit")
    return [{k: (d.memory_stats() or {}).get(k) for k in keys}
            for d in devices]


def phase_correctness(workdir: str, seed: int) -> None:
    from rdfind_tpu.ops import cooc, sketch
    from rdfind_tpu.utils import synth

    support = 12
    triples, _ = synth.generate_planted_cinds(8, support, seed=seed)
    res = compare_with_oracle(workdir, triples, support)
    for s, row in res["strategies"].items():
        emit(f"correctness/strategy{s}", n_triples=len(triples),
             oracle_cinds=res["oracle_cinds"], oracle_s=res["oracle_s"],
             **row)
    # The planted sketches are 2048 bits wide, too narrow for the
    # emit_pipeline variant; 4096-bit rows show whether it compiles and
    # agrees with the jnp planes path on this chip.
    t0 = time.perf_counter()
    kernel = sketch.kernel_selfcheck(n_rows=1024, n_bits=4096, repeats=1)
    emit("correctness/containment_kernel", parity=kernel["parity"],
         emit_pipeline=kernel["emit_pipeline"],
         kernel_dtype=kernel["kernel_dtype"],
         seconds=time.perf_counter() - t0)
    report = cooc.resolution_report()
    faults = check_kernels(report, fused=True, contains=True)
    emit("correctness/kernels", report=report, faults=faults)
    if faults or not kernel["parity"] \
            or not all(r["equal"] for r in res["strategies"].values()):
        raise SmokeFailure("correctness phase failed")


def _real_run(path: str, support: int, strategy: int, dop: int,
              out_path: str):
    res, wall, comp = run_cli([path, "--support", str(support),
                               "--traversal-strategy", str(strategy),
                               "--clean-implied", "--use-fis",
                               "--dop", str(dop), "--output", out_path])
    t = res.timings
    row = {"strategy": strategy, "dop": dop, "seconds": wall,
           "compile_s": comp,
           "ingest_s": sum(t.get(k, 0.0) for k in INGEST_PHASES),
           "discover_s": t.get("discover"),
           "collect_s": sum(t.get(k, 0.0) for k in COLLECT_PHASES),
           "timings": t, "cinds": len(res.table),
           "families": res.table.family_counts()}
    return res, row


def generate_real(workdir: str, n: int, seed: int):
    from rdfind_tpu.utils import synth

    t0 = time.perf_counter()
    triples = synth.generate_dbpedia_shaped(n, seed=seed)
    path = os.path.join(workdir, "dbpedia_shaped.nt")
    synth.write_nt(path, triples)
    emit("real/generate", n_triples=n, seed=seed,
         seconds=time.perf_counter() - t0, bytes=os.path.getsize(path))
    return triples, path


def phase_real(workdir: str, n: int, support: int, seed: int) -> None:
    import jax

    from rdfind_tpu.ops import cooc

    triples, path = generate_real(workdir, n, seed)
    sets = {}
    for strategy in (1, 0):
        res, row = _real_run(path, support, strategy, 1,
                             os.path.join(workdir, f"s{strategy}.tsv"))
        cinds = res.decoded()
        sets[strategy] = {c.pretty() for c in cinds}
        check = sample_check(triples.astype(np.int64), cinds, 32,
                             seed + strategy)
        emit(f"real/strategy{strategy}", n_triples=n, support=support,
             **row, sample=check,
             memory=device_memory(jax.devices()[:1])[0])
        if check["bad"]:
            raise SmokeFailure(f"strategy {strategy}: sampled CINDs fail "
                               f"the host inclusion check")
    same = sets[0] == sets[1]
    report = cooc.resolution_report()
    faults = check_kernels(report)
    emit("real/compare", equal=same, cinds=len(sets[1]),
         only_s1=len(sets[1] - sets[0]), only_s0=len(sets[0] - sets[1]),
         report=report, faults=faults)
    if not same or faults or not sets[1]:
        raise SmokeFailure("real-size phase failed")


def phase_four(workdir: str, n: int, support: int, seed: int) -> None:
    import jax

    _, path = generate_real(workdir, n, seed)
    sets = {}
    for dop in (4, 1):
        res, row = _real_run(path, support, 1, dop,
                             os.path.join(workdir, f"dop{dop}.tsv"))
        sets[dop] = pretty_set(res)
        emit(f"four/dop{dop}", n_triples=n, support=support, **row,
             memory=device_memory(jax.devices()))
    mem = device_memory(jax.devices())
    idle = [i for i, m in enumerate(mem) if not m["peak_bytes_in_use"]]
    emit("four/compare", equal=sets[4] == sets[1], cinds=len(sets[4]),
         devices_without_memory=idle)
    if sets[4] != sets[1] or idle:
        raise SmokeFailure("--dop 4 phase failed")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the --dop 4 run and its one-device twin")
    ap.add_argument("--triples", type=int, default=20_000_000)
    ap.add_argument("--support", type=int, default=1000)
    ap.add_argument("--seed", type=int, default=404)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU — JAX reports platform "
              f"{devices[0].platform!r}; this smoke runs only on the chip",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    from rdfind_tpu.io import native
    from rdfind_tpu.runtime import compile_cache

    if not native.available():  # builds native/ on first use
        print("chip_smoke: the native ingest library is unavailable "
              "(make -C native failed, or RDFIND_NATIVE=0)", file=sys.stderr)
        return 2
    cache_dir = compile_cache.place()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    emit("start", device_kind=devices[0].device_kind, count=len(devices),
         compile_cache=cache_dir, jax=jax.__version__)
    t0 = time.perf_counter()
    try:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as wd:
            if args.chips == 4:
                phase_four(wd, args.triples, args.support, args.seed)
            else:
                phase_correctness(wd, args.seed)
                phase_real(wd, args.triples, args.support, args.seed)
    except Exception as e:
        traceback.print_exc()
        emit("failed", error=f"{type(e).__name__}: {e}",
             seconds=time.perf_counter() - t0)
        return 1
    emit("done", seconds=time.perf_counter() - t0,
         compile_s=_compile_s[0], cache_entries=len(os.listdir(cache_dir))
         if os.path.isdir(cache_dir) else 0)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
