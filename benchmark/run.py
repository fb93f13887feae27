"""Run one benchmark cell: CIND discovery through the CLI's main on the chip.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, its traffic and its per-layer metrics are
found by name: `BENCHMARK.json` names the cell's configuration and
traffic, `benchmark/configs/<config>.json` holds the graph and the
discovery flags, `benchmark/traffic/<traffic>.json` the support and the
strategy flags, and `benchmark/metrics/<metric>.py` one reader per
per-layer metric.

One run, in one process that holds the chip:
  set-up   the configuration's graph (built once per checkout, kept under
           benchmark/.data), relabelled and reordered by --seed, written as
           an .nt file; one warm-up discovery, which compiles or loads
           every program from JAX's persistent cache;
  window   back-to-back discoveries with the same argv, each from the .nt
           file to the minimal CIND table written with --output, started
           until --seconds have passed; only whole discoveries count;
  trace    with --trace 1, one more discovery under the JAX profiler, with
           the program's own --trace spans on the same clock;
  check    every discovery's table is compared with the plain reference
           (benchmark/reference.py) over the same triples.

The last line of stdout is one JSON object (correct, attempted, failed,
metrics, device, breakdown with --trace 1, and the compared numbers under
"checks", last); the compared numbers are also the last lines of stderr.
Without a TPU, or with fewer chips than the cell asks for, the run prints
no result and exits 2.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import hashlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

import numpy as np  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
DATA_DIR = os.path.join(BENCH_DIR, ".data")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check, gen, reference  # noqa: E402
from benchmark import trace as trace_mod  # noqa: E402

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class NoChip(Exception):
    pass


@dataclasses.dataclass
class Discovery:
    seconds: float
    timings: dict   # RunResult.timings: driver phase -> seconds
    table: str      # the --output file's text


@dataclasses.dataclass
class Run:
    """What a metric reader may read."""

    cell: dict
    config: dict
    window: list          # [Discovery] of the measured window
    trace: object         # trace.Reduction of the traced discovery, or None
    work: dict            # reference.Captures.work() of the input
    peaks: dict           # benchmark/peaks.py entry of the device kind


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"BENCHMARK.json has no workload {name!r}")


def graph_for(config: dict) -> np.ndarray:
    """The configuration's fixed graph, built once per checkout and kept
    under benchmark/.data, keyed by the generator entry and gen.py."""
    spec = config["generator"]
    with open(gen.__file__, "rb") as f:
        key = hashlib.sha256(json.dumps(spec, sort_keys=True).encode()
                             + f.read()).hexdigest()[:16]
    path = os.path.join(DATA_DIR, f"graph-{config['name']}-{key}.npy")
    if os.path.exists(path):
        return np.load(path)
    g = gen.build_graph(spec)
    os.makedirs(DATA_DIR, exist_ok=True)
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "wb") as f:
        np.save(f, g)
    os.replace(tmp, path)
    return g


def device_info(jax, chips: int, require_tpu: bool) -> tuple:
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devices[0].platform!r}); "
                     f"this benchmark runs only on the chip")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chip(s); JAX finds "
                     f"{len(devices)}")
    return devices, {"platform": devices[0].platform,
                     "kind": devices[0].device_kind, "count": len(devices)}


def discover(argv: list) -> tuple:
    """One discovery through the CLI's main -> (RunResult, seconds)."""
    from rdfind_tpu.programs import rdfind

    got = []
    t0 = time.perf_counter()
    rc = rdfind.main(argv, on_result=got.append)
    seconds = time.perf_counter() - t0
    if rc != 0 or not got:
        raise RuntimeError(f"rdfind main {argv} exited {rc}")
    return got[0], seconds


def record(argv: list, out_path: str) -> Discovery:
    """One discovery and the table it wrote; the table of the one before
    is removed first, so each table compared is this discovery's own."""
    if os.path.exists(out_path):
        os.remove(out_path)
    result, seconds = discover(argv)
    with open(out_path) as f:
        table = f.read()
    return Discovery(seconds, dict(result.timings), table)


def traced_discovery(jax, argv: list, out_path: str, trace_dir: str):
    """One discovery under the JAX profiler (no Python tracer, no HLO
    protos), with the program's stage spans as TraceAnnotations."""
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(os.path.join(trace_dir, "xplane"),
                             profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
            d = record(argv + ["--trace", os.path.join(trace_dir, "spans")],
                       out_path)
    finally:
        jax.profiler.stop_trace()
    for dirpath, _, files in os.walk(trace_dir):
        for name in files:
            if name.endswith(".xplane.pb"):
                return d, trace_mod.reduce(
                    trace_mod.load(os.path.join(dirpath, name)))
    raise RuntimeError(f"the profiler wrote no .xplane.pb under {trace_dir}")


def run_cell(args, *, require_tpu: bool = True, bench: dict | None = None,
             config: dict | None = None, traffic: dict | None = None) -> dict:
    """One run of a cell -> the result line's object.  The tests pass
    `require_tpu=False` with a small `bench`, `config` and `traffic` of
    their own."""
    bench = bench or load_json(ROOT, "BENCHMARK.json")
    cell = find_cell(bench, args.workload)
    config = config or load_json(BENCH_DIR, "configs",
                                 f"{cell['config']}.json")
    traffic = traffic or load_json(BENCH_DIR, "traffic",
                                   f"{cell['traffic']}.json")
    support = traffic["support"]

    import jax

    devices, device = device_info(jax, cell["chips"], require_tpu)
    from rdfind_tpu.io import native
    from rdfind_tpu.runtime import compile_cache

    if not native.available():
        raise NoChip("the native ingest library is unavailable "
                     "(make -C native failed)")
    compile_cache.place()
    # Every program goes into the persistent cache, however fast it
    # compiled, so a run after the first compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = [0, 0.0]  # backend compiles, seconds tracing+compiling

    def on_duration(event: str, secs: float, **_) -> None:
        if event in COMPILE_EVENTS:
            compiles[0] += event == COMPILE_EVENTS[-1]
            compiles[1] += secs

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    t_data = time.perf_counter()
    os.makedirs(DATA_DIR, exist_ok=True)
    triples = gen.relabel(graph_for(config), args.seed)
    nt_path = os.path.join(DATA_DIR, f"{cell['name']}.nt")
    out_path = os.path.join(DATA_DIR, f"{cell['name']}.cinds.txt")
    gen.write_nt(nt_path, triples)
    argv = ([nt_path, "--support", str(support),
             "--projection", config["projections"]]
            + config["flags"] + traffic["flags"] + ["--output", out_path])

    t_warm = time.perf_counter()
    warm = record(argv, out_path)
    t_window = time.perf_counter()
    setup_s = t_window - T_PROCESS
    setup = {"start_s": t_data - T_PROCESS, "data_s": t_warm - t_data,
             "warmup_s": t_window - t_warm, "compile_s": compiles[1],
             "compiles": compiles[0]}
    compiles_before = compiles[0]
    window = []
    while time.perf_counter() - t_window < args.seconds:
        window.append(record(argv, out_path))
    window_s = time.perf_counter() - t_window
    window_compiles = compiles[0] - compiles_before
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:cell["chips"]])
    device["memory_peak_bytes"] = int(peak)

    reduction = traced = None
    if args.trace:
        traced, reduction = traced_discovery(
            jax, argv, out_path, os.path.join(DATA_DIR, "trace",
                                              cell["name"]))
    os.remove(nt_path)

    # The check, once the window has closed and the peak is read.
    t_ref = time.perf_counter()
    want, caps = reference.minimal_cinds(triples, support)
    ref_s = time.perf_counter() - t_ref
    work = caps.work()
    tables = [warm.table] + [d.table for d in window] + (
        [traced.table] if traced else [])
    checks = check.compare(tables, want)

    n = len(triples)
    out = {"correct": check.passed(checks), "attempted": len(tables),
           "failed": checks["tables_wrong"]["value"], "metrics": {},
           "device": device}
    units = {m["name"]: m["unit"] for m in bench["end_to_end"]
             + bench["per_layer"]}
    if not args.trace:
        values = {"triples_per_s": n * len(window) / window_s,
                  "peak_hbm_gib": peak / 2 ** 30,
                  "setup_s": setup_s}
    else:
        from benchmark import peaks

        run = Run(cell, config, window, reduction, work,
                  peaks.peaks_for(device["kind"]))
        values = {}
        for m in bench["per_layer"]:
            if cell["name"] not in m.get("workloads", [cell["name"]]):
                continue
            reader = importlib.import_module(f"benchmark.metrics.{m['name']}")
            v = reader.read(run)
            if v is not None:
                values[m["name"]] = v
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
        out["breakdown"] = {
            "device_ops": trace_mod.top(reduction.module_s),
            "idle_gaps": [[k, v] for k, v in reduction.gaps]}
    out["metrics"] = {k: {"value": v, "unit": units[k]}
                      for k, v in values.items()}
    info = {"setup": setup, "discoveries": len(window),
            "window_s": window_s,
            "discovery_s": [d.seconds for d in window],
            "window_compiles": window_compiles, "reference_s": ref_s,
            "cinds": checks["reference_cinds"], "work": work}
    if traced is not None:
        info["traced_discovery_s"] = traced.seconds
    print(f"benchmark: {json.dumps(info)}", file=sys.stderr)
    out["checks"] = {k: v for k, v in checks.items()
                     if k != "reference_cinds"}
    return out


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        out = run_cell(args)
    except NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    except Exception:
        traceback.print_exc()
        return 1
    print(json.dumps(out), flush=True)
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
