"""Benchmark: CIND-candidate-pairs checked per second per chip.

Workload: synthetic RDF (LUBM/DBpedia-shaped, utils/synth.py), full AllAtOnce
discovery incl. binary captures at min_support=10 — BASELINE.md config-1 analog.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

vs_baseline is measured in-process against the single-core pure-Python oracle
(rdfind_tpu.oracle.discover_cinds_joinline) on a subsample, scaled to pairs/sec —
the honest stand-in for the reference's single-worker throughput, since the repo
ships no Flink cluster numbers (BASELINE.md: "published: none in repo").

Device: every line names the device it measured (`device`: platform,
device_kind, count).  Without an accelerator the bench fails, unless the
caller pinned JAX_PLATFORMS=cpu to measure the CPU on purpose; it never falls
back.  Once the device is known, an error in a measurement still prints a
diagnostic JSON line (never a bare traceback) with value=0, like the
reference's AbstractFlinkProgram.java:65-77,175-182.
"""

import json
import os
import sys
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from rdfind_tpu import obs  # noqa: E402
from rdfind_tpu.obs import integrity as obs_integrity  # noqa: E402
from rdfind_tpu.obs import report as obs_report  # noqa: E402
from rdfind_tpu.obs import sentinel as obs_sentinel  # noqa: E402


def require_device() -> dict:
    """The device this bench measures: {"platform", "device_kind", "count"}.

    Exits nonzero when JAX finds no accelerator, unless the caller pinned
    JAX_PLATFORMS=cpu — a CPU number is only ever measured on purpose."""
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu" \
            and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit("bench: JAX found no accelerator; pin "
                         "JAX_PLATFORMS=cpu to measure the CPU on purpose")
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind, "count": len(devices)}


# Published per-chip peaks, keyed by jax's device_kind (Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s bf16, 394 TOP/s int8, 819 GB/s HBM).
# The roofline denominators; a TPU kind missing here is an error.
TPU_PEAKS = {
    "TPU v5 lite": {"bf16_tflops": 197.0, "int8_tops": 394.0,
                    "hbm_gbps": 819.0},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in TPU_PEAKS:
        raise KeyError(f"bench.TPU_PEAKS has no entry for device_kind "
                       f"{device_kind!r}; add its published peaks")
    return TPU_PEAKS[device_kind]


def _measure_mfu(stats: dict, device: dict) -> dict:
    """Achieved FLOP/s of the dense cooc matmul at this workload's shapes.

    Times the device-only scheduled tile sweep (the jitted cooc_cind_tile, no
    host unpack) on the same DensePlan the bench workload used.  Reports BOTH
    raw MFU (issued FLOPs / time / peak — the MXU's utilization on the work
    actually dispatched, padding included) and occupancy-corrected MFU
    (real FLOPs / time / peak = raw * plan occupancy — the fraction of peak
    spent on the unpadded workload, the honest headline).  Fraction-of-peak
    needs a TPU (peaks from TPU_PEAKS by device_kind); raw FLOP/s and the
    occupancy record are reported everywhere.
    """
    import jax
    import jax.numpy as jnp

    from rdfind_tpu.ops import cooc

    plan = cooc.dense_plan(stats.get("n_lines", 0),
                           stats.get("n_captures", 0))
    if plan is None:
        return {"error": "dense plan does not apply at this workload"}
    l_pad, c_pad, tile = plan.l_pad, plan.c_pad, plan.tile

    rng = np.random.default_rng(5)
    member_h = rng.random((l_pad, c_pad)) < 0.01
    dep_count = jnp.asarray(rng.integers(1, 50, c_pad, np.int32))
    cap_id = jnp.asarray(rng.integers(0, 1 << 20, c_pad, np.int32))

    def time_sweep(dtype):
        # One dtype's matrix lives on device at a time: an int8-sized plan
        # can admit shapes whose bf16 matrix alone busts HBM, so each sweep
        # materializes (and frees) its own matrix and is guarded separately.
        mat = jnp.asarray(member_h, dtype)

        def sweep():
            outs = [cooc.cooc_cind_tile(mat, jnp.int32(lo), dep_count, cap_id,
                                        cap_id, cap_id, jnp.int32(10),
                                        tile=tile)
                    for lo in plan.dep_tile_starts]
            jax.block_until_ready(outs)

        sweep()  # compile
        reps = 3
        t0 = time.perf_counter()
        for _ in range(reps):
            sweep()
        return (time.perf_counter() - t0) / reps

    issued = float(plan.issued_flops)
    out = {"plan": plan.describe(), "l_pad": l_pad, "c_pad": c_pad,
           "tile": tile, "occupancy": round(plan.occupancy, 4)}
    achieved = None
    try:
        dt = time_sweep(jnp.bfloat16)
        achieved = issued / dt
        out["sweep_s"] = round(dt, 4)
        out["achieved_tflops"] = round(achieved / 1e12, 3)
    except Exception as e:  # e.g. bf16 matrix over HBM under an int8 plan
        out["bf16_error"] = f"{type(e).__name__}: {e}"
    achieved8 = None
    try:
        # Same sweep on int8 membership (the default cooc dtype on int8-MXU
        # backends): measures the int8 path at these shapes.
        dt8 = time_sweep(jnp.int8)
        achieved8 = issued / dt8
        out["int8_achieved_tops"] = round(achieved8 / 1e12, 3)
        if achieved is not None:
            out["int8_vs_bf16"] = round(dt / dt8, 3)
    except Exception as e:  # int8 matmul unsupported on some backends
        out["int8_error"] = f"{type(e).__name__}: {e}"
    if device["platform"] == "tpu" and cooc.fuse_verdict_enabled():
        # The fused-verdict kernel at the same shapes (device-only, full
        # K-block schedule): the raw-roofline row the headline run rides.
        try:
            kl = plan.line_block
            nb = l_pad // kl
            bids = jnp.asarray(np.arange(nb, dtype=np.int32))
            nr = jnp.asarray(np.full(1, nb, np.int32))
            mat = jnp.asarray(member_h,
                              jnp.int8 if plan.dtype == "int8"
                              else jnp.bfloat16)

            def fsweep():
                outs = [cooc._fused_cind_tile(
                    mat, jnp.int32(lo), dep_count, cap_id, cap_id, cap_id,
                    jnp.int32(10), bids, nr, tile=tile, interpret=False)
                    for lo in plan.dep_tile_starts]
                jax.block_until_ready(outs)

            fsweep()  # compile
            reps = 3
            t0 = time.perf_counter()
            for _ in range(reps):
                fsweep()
            dtf = (time.perf_counter() - t0) / reps
            out["fused_sweep_s"] = round(dtf, 4)
            out["fused_achieved_tflops"] = round(issued / dtf / 1e12, 3)
            # Against the unfused sweep of the SAME resolved dtype.
            base = achieved8 if (plan.dtype == "int8" and achieved8) \
                else achieved
            if base:
                out["fused_vs_unfused"] = round((issued / base) / dtf, 3)
        except Exception as e:
            out["fused_error"] = f"{type(e).__name__}: {e}"
    if device["platform"] == "tpu":
        peaks = peaks_for(device["device_kind"])
        peak = peaks["bf16_tflops"] * 1e12
        out["chip"] = device["device_kind"]
        out["peak_bf16_tflops"] = peaks["bf16_tflops"]
        if achieved is not None:
            out["bf16_mfu"] = round(achieved / peak, 4)
        if achieved8 is not None and "int8_tops" in peaks:
            out["int8_mfu"] = round(
                achieved8 / (peaks["int8_tops"] * 1e12), 4)
            out["int8_mfu_corrected"] = round(
                out["int8_mfu"] * plan.occupancy, 4)
        # The HEADLINE mfu follows the *resolved* membership dtype, against
        # the matching MXU peak (an int8 run rated against the bf16 peak
        # would understate utilization 2x; int4 nibble planes keep the int8
        # membership element, so int8 is also their honest denominator) —
        # labeled so the artifact says which roofline it is.
        resolved = cooc.resolved_cooc_dtype()
        if resolved == "int8" and achieved8 is not None \
                and "int8_tops" in peaks:
            out["peak_dtype"] = "int8"
            out["peak_tflops"] = peaks["int8_tops"]
            head = achieved8 / (peaks["int8_tops"] * 1e12)
        elif achieved is not None:
            out["peak_dtype"] = "bf16"
            out["peak_tflops"] = peaks["bf16_tflops"]
            head = achieved / peak
        else:
            head = None
        if head is not None:
            out["mfu"] = round(head, 4)
            out["mfu_corrected"] = round(head * plan.occupancy, 4)
    return out


def _bench_kernel_modes(backend: str) -> dict:
    """Per-mode rows for the containment/CIND kernels: plane bits x fused
    verdict, each with an HBM watermark sample (obs/memory.py), so the
    "fused never materializes the cooc counts" claim is a measured number.

    Plane rows rerun the packed-containment selfcheck across the full
    rung-3 grid: {8,4,2}-bit planes x emit_pipeline off/on (sub-byte rows
    only lower natively where the backend probe says so — elsewhere they
    record the emulated parity run; the emit=on row off-TPU records the
    probe refusing and the materialized path running, which is the
    fallback contract under test).  Fused rows run the same dense CIND
    sweep with RDFIND_FUSE_VERDICT off/on, fused FIRST, so a higher HBM
    peak on the materialized row is attributable to the int32 cooc tile
    the fused kernel keeps in VMEM.  On backends without memory stats
    (CPU) the hbm field is None and, off-TPU, the fused row shrinks to a
    tiny interpreted parity check.
    """
    import jax
    import jax.numpy as jnp

    from rdfind_tpu.obs import memory
    from rdfind_tpu.ops import cooc, sketch

    on_tpu = backend == "tpu"
    # by_mode mirrors the rows keyed by mode name: the sentinel's _dig walks
    # dicts only, so per-mode walls are only trackable through this view.
    out = {"modes": [], "by_mode": {}}

    def hbm():
        rec = memory.sample(None, publish=False)
        return None if rec is None else {
            "in_use_bytes": rec["in_use_bytes"],
            "peak_bytes": rec["peak_bytes"],
            "delta_bytes": rec["delta_bytes"]}

    saved_pb, saved_fv = cooc.PLANE_BITS, cooc.FUSE_VERDICT
    saved_em = cooc.EMIT_PIPELINE
    try:
        baseline_hashes: dict = {}
        for pb in ("8", "4", "2"):
            for em in ("0", "1"):
                cooc.PLANE_BITS = pb
                cooc.EMIT_PIPELINE = em
                row = {"mode": f"planes{pb}" + ("-emit" if em == "1" else ""),
                       "kernel_dtype": cooc.resolved_kernel_dtype(),
                       "emit_requested": em == "1"}
                try:
                    n = 2048 if on_tpu else 256
                    row.update(sketch.kernel_selfcheck(
                        n_rows=n, n_bits=4096, backend=backend, repeats=3))
                    # All six rows run the identical logical contraction:
                    # the paired emit row must reproduce its non-emit
                    # sibling bit-for-bit (off-TPU the probe refuses and
                    # the emit row IS the materialized path — the check
                    # then proves the fallback contract instead).
                    if "out_hash" in row:
                        if pb in baseline_hashes:
                            row["outputs_identical"] = (
                                row["out_hash"] == baseline_hashes[pb])
                        else:
                            baseline_hashes[pb] = row["out_hash"]
                except Exception as e:
                    row["error"] = f"{type(e).__name__}: {e}"
                row["hbm"] = hbm()
                out["modes"].append(row)
                out["by_mode"][row["mode"]] = row
        cooc.PLANE_BITS = saved_pb
        cooc.EMIT_PIPELINE = saved_em

        # Fused-verdict rows share one membership matrix; the sweep is the
        # full scheduled dep-tile pass of discover_pairs_dense.
        rng = np.random.default_rng(7)
        n_lines, num_caps = (100_000, 4096) if on_tpu else (300, 200)
        plan = cooc.dense_plan(n_lines, num_caps)
        if plan is None:
            out["fused_error"] = "dense plan does not fit"
            return out
        member = rng.random((plan.l_pad, plan.c_pad)) < 0.01
        dt = jnp.int8 if plan.dtype == "int8" else jnp.bfloat16
        m = jax.block_until_ready(jnp.asarray(member, dt))
        dep_count = member.sum(axis=0).astype(np.int64)
        cap_id = rng.integers(0, 1 << 20, plan.c_pad).astype(np.int64)
        baseline = None
        for fv in ("1", "0"):  # fused first: see docstring
            cooc.FUSE_VERDICT = fv
            mode_plan = cooc.dense_plan(n_lines, num_caps)
            stats: dict = {}
            t0 = time.perf_counter()
            d, r, _ = cooc.discover_pairs_dense(
                m, dep_count, cap_id, cap_id, cap_id, 10,
                num_caps, mode_plan.tile, starts=mode_plan.dep_tile_starts,
                plan=mode_plan, stats=stats)
            wall = time.perf_counter() - t0
            pairs = set(zip(d.tolist(), r.tolist()))
            row = {"mode": "fused" if fv == "1" else "materialized",
                   "wall_s": round(wall, 4),
                   "n_cinds": len(pairs),
                   "n_blocks_skipped": stats.get("n_blocks_skipped"),
                   "hbm": hbm()}
            if baseline is None:
                baseline = pairs
            else:
                row["outputs_identical"] = pairs == baseline
            out["modes"].append(row)
            out["by_mode"][row["mode"]] = row
    finally:
        cooc.PLANE_BITS, cooc.FUSE_VERDICT = saved_pb, saved_fv
        cooc.EMIT_PIPELINE = saved_em
    return out


def _bench_pipelined_passes(min_support: int) -> dict:
    """Multi-pass dispatch proxy: the sharded streaming pair phase under a
    tiny RDFIND_PAIR_ROW_BUDGET (n_pass >= 4 on this workload), pipelined vs
    RDFIND_SYNC_PASSES=1.  Records the dispatch counters (host syncs, sync
    time, overlapped pull time, in-flight depth, cap retries) so the JSON
    artifact PROVES the compute/readback overlap rather than asserting it;
    outputs of the two modes are asserted identical in-process.
    """
    from rdfind_tpu.models import sharded
    from rdfind_tpu.parallel.mesh import make_mesh
    from rdfind_tpu.utils.synth import generate_triples

    # Sized for the CPU fallback (one core proxying the whole mesh): big
    # enough for n_pass >= 4 under the adaptive budget below, small enough
    # that 5 pipeline runs (probe + 2x warm/timed) stay in low minutes.
    # On the real chip, raise BENCH_PIPELINE_TRIPLES for a sharper row.
    n = int(os.environ.get("BENCH_PIPELINE_TRIPLES", 4_000))
    triples = generate_triples(n, seed=43)
    mesh = make_mesh()
    out = {"n_devices": int(mesh.devices.size), "n_triples": n}
    saved = {k: os.environ.get(k)
             for k in ("RDFIND_PAIR_ROW_BUDGET", "RDFIND_SYNC_PASSES")}
    try:
        # Probe pass: measure this workload's planned per-device pair load at
        # n_pass=1, then pick the row budget that yields n_pass ~ 5 (a blind
        # constant would give 1 pass on small workloads or hundreds on big
        # ones — both useless as an overlap proxy).
        os.environ.pop("RDFIND_PAIR_ROW_BUDGET", None)
        probe: dict = {}
        sharded.discover_sharded(triples, min_support, mesh=mesh, stats=probe)
        caps = probe["planned_caps"]
        full_load = (caps["pairs"] * probe["n_pair_passes"]
                     + caps["giant_pairs"] * probe["n_pair_passes"])
        budget = max(1 << 10, full_load // 5)
        os.environ["RDFIND_PAIR_ROW_BUDGET"] = str(budget)
        out["pair_row_budget"] = budget
        rows, tables = {}, {}
        for mode, sync in (("pipelined", ""), ("sync", "1")):
            os.environ["RDFIND_SYNC_PASSES"] = sync
            stats: dict = {}
            sharded.discover_sharded(triples, min_support, mesh=mesh,
                                     stats=stats)  # warm (compile)
            stats = {}
            t0 = time.perf_counter()
            tables[mode] = sharded.discover_sharded(triples, min_support,
                                                    mesh=mesh, stats=stats)
            rows[mode] = {
                "wall_s": round(time.perf_counter() - t0, 3),
                # Dispatch + fault telemetry via the shared obs key groups
                # (obs/metrics.DISPATCH_KEYS/FAULT_KEYS): bench rows, the
                # driver's --debug lines and the tests render the same
                # names by construction.  The ladder + retry counters prove
                # a degraded run degraded, and a clean one didn't, straight
                # from the artifact.
                **obs_report.dispatch_row(stats),
                # The overlap-efficiency row (dispatch.overlap_report):
                # measured wall vs the serial/parallel bounds — sync mode
                # should meter ~0 efficiency, pipelined mode the real win.
                "overlap": stats.get("overlap"),
                "degradations": stats.get("degradations"),
                "ladder_rung": stats.get("ladder_rung"),
                "cinds": len(tables[mode]),
            }
        out.update(rows)
        out["outputs_identical"] = (tables["pipelined"].to_rows()
                                    == tables["sync"].to_rows())
        out["speedup_vs_sync"] = round(
            rows["sync"]["wall_s"] / max(rows["pipelined"]["wall_s"], 1e-9), 3)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return out


def _bench_exchange(min_support: int) -> dict:
    """Flat vs hierarchical exchange on the sharded pipeline: per-site
    ICI/DCN byte split, wall clock, and the DCN reduction factor the
    per-host combiner bought.  On a single-host run the 2-host pod is
    modeled via RDFIND_HIER_HOSTS (the ledger attributes the flat run's
    cross-host share so the comparison is apples-to-apples); a real
    multi-process run measures the actual topology.  Outputs of the two
    modes are asserted identical in-process — the knob only moves bytes.
    """
    from rdfind_tpu.models import sharded
    from rdfind_tpu.parallel.mesh import make_mesh, topology_hosts
    from rdfind_tpu.utils.synth import generate_triples

    n = int(os.environ.get("BENCH_EXCHANGE_TRIPLES", 4_000))
    triples = generate_triples(n, seed=47)
    mesh = make_mesh()
    num_dev = int(mesh.devices.size)
    out = {"n_devices": num_dev, "n_triples": n}
    saved = {k: os.environ.get(k)
             for k in ("RDFIND_HIER_EXCHANGE", "RDFIND_HIER_HOSTS",
                       "RDFIND_COLLECTIVE_TIMING", "RDFIND_LINK_PROBE")}
    try:
        if topology_hosts(num_dev) == 1 and num_dev % 2 == 0:
            os.environ["RDFIND_HIER_HOSTS"] = "2"  # single-host pod proxy
        hosts = topology_hosts(num_dev)
        out["hosts"] = hosts
        if hosts == 1:
            out["error"] = "device count admits no host factorization"
            return out
        site_cols = ("calls", "capacity", "lanes", "bytes", "ici_bytes",
                     "dcn_bytes", "reply_bytes", "hier", "dcn_capacity",
                     "overflow_retries")
        rows, tables = {}, {}
        for mode, knob in (("flat", "0"), ("hier", "1")):
            os.environ["RDFIND_HIER_EXCHANGE"] = knob
            stats: dict = {}
            sharded.discover_sharded(triples, min_support, mesh=mesh,
                                     use_fis=True, stats=stats)  # warm
            stats = {}
            t0 = time.perf_counter()
            tables[mode] = sharded.discover_sharded(triples, min_support,
                                                    mesh=mesh, use_fis=True,
                                                    stats=stats)
            sites = stats["exchange_sites"]
            rows[mode] = {
                "wall_s": round(time.perf_counter() - t0, 3),
                "ici_bytes": sum(e["ici_bytes"] for e in sites.values()),
                "dcn_bytes": sum(e["dcn_bytes"] for e in sites.values()),
                "bytes": sum(e["bytes"] for e in sites.values()),
                "sites": {s: {k: e[k] for k in site_cols}
                          for s, e in sorted(sites.items())},
                **obs_report.dispatch_row(stats),
                "cinds": len(tables[mode]),
            }
        out.update(rows)
        out["outputs_identical"] = (tables["flat"].to_rows()
                                    == tables["hier"].to_rows())
        out["dcn_reduction"] = round(
            rows["flat"]["dcn_bytes"] / max(rows["hier"]["dcn_bytes"], 1), 3)
        # Per-site collective timing (hier mode, timers + link probe armed):
        # device-synchronized wall per dispatch, achieved GB/s and
        # utilization of the probed per-hop peaks.  The per-hop achieved
        # rates follow from attributing each dispatch's wall to its hops in
        # proportion to their ideal transfer times: achieved_hop =
        # peak_hop * link_util.
        from rdfind_tpu.obs import metrics as obs_metrics

        os.environ["RDFIND_COLLECTIVE_TIMING"] = "1"
        os.environ["RDFIND_LINK_PROBE"] = "1"
        stats = {}
        timed_tbl = sharded.discover_sharded(triples, min_support, mesh=mesh,
                                             use_fis=True, stats=stats)
        caps = obs_metrics.link_caps()
        t_sites = {}
        for s, e in sorted(stats["exchange_sites"].items()):
            if "wall_ms" not in e:
                continue
            row = {k: e[k] for k in ("wall_ms", "gbps", "link_util",
                                     "timed_calls", "timed_bytes", "ideal_ms")
                   if k in e}
            util = e.get("link_util") or 0.0
            if caps.get("ici_gbps"):
                row["ici_gbps"] = round(caps["ici_gbps"] * util, 3)
            if caps.get("dcn_gbps") and e.get("dcn_bytes"):
                row["dcn_gbps"] = round(caps["dcn_gbps"] * util, 3)
            t_sites[s] = row
        out["timing"] = {
            "link_caps": caps,
            "sites": t_sites,
            # The timers are pure measurement: armed vs unarmed must agree.
            "outputs_identical": timed_tbl.to_rows() == tables["hier"].to_rows(),
        }
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return out


def _kernel_feed_row(n_mesh: int, min_support: int) -> dict:
    """One kernel-feed row: the sharded dense pass on an n_mesh-device mesh
    with the skew meter armed through the metrics exposition gate (NOT
    RDFIND_COLLECTIVE_TIMING — that serializes the executor and would
    destroy the very overlap this row measures).  Reports pairs/s/chip,
    the executor's measured overlap_efficiency, and the kernel-feed stall
    fraction: exchange-wait ms over dense-compute ms, summed across hosts
    from the _SkewMeter phase timers (>= 1.0 means the dense kernels are
    exchange-bound — feeding the MXU is the bottleneck, not the matmul).
    """
    import tempfile

    from rdfind_tpu.models import sharded
    from rdfind_tpu.obs import metrics as obs_metrics
    from rdfind_tpu.parallel.mesh import make_mesh
    from rdfind_tpu.utils.synth import generate_triples

    n = int(os.environ.get("BENCH_KERNEL_FEED_TRIPLES", 4_000))
    triples = generate_triples(n, seed=53)
    mesh = make_mesh(n_mesh)
    row = {"mesh_devices": int(mesh.devices.size), "n_triples": n}
    prev_export = obs_metrics.export_path()
    tmp = tempfile.NamedTemporaryFile(suffix=".prom", delete=False)
    tmp.close()
    obs_metrics.set_export(tmp.name)
    try:
        stats: dict = {}
        sharded.discover_sharded(triples, min_support, mesh=mesh,
                                 stats=stats)  # warm (compile)
        stats = {}
        t0 = time.perf_counter()
        table = sharded.discover_sharded(triples, min_support, mesh=mesh,
                                         stats=stats)
        wall = time.perf_counter() - t0
    finally:
        obs_metrics.set_export(prev_export)
        try:
            os.unlink(tmp.name)
        except OSError:
            pass
    pairs = int(stats.get("total_pairs", 0))
    ov = stats.get("overlap") or {}
    row.update({
        "wall_s": round(wall, 3),
        "total_pairs": pairs,
        "pairs_per_sec_per_chip": round(
            pairs / max(wall, 1e-9) / max(n_mesh, 1), 1),
        "cinds": len(table),
        "overlap_efficiency": ov.get("overlap_efficiency"),
        "kernel_feed_stall_fraction": obs_report.kernel_feed_stall_fraction(
            stats.get("host_skew")),
        "host_skew": stats.get("host_skew"),
        **obs_report.dispatch_row(stats),
    })
    return row


def _bench_kernel_feed(min_support: int) -> dict:
    """Multi-chip kernel-feed rows (rung 3): the sharded dense pass on one
    device and on every device this process holds, asking whether the
    exchange plane can keep the dense kernels fed as chips are added.  Each
    row reports pairs/s/chip, the executor's overlap_efficiency, and the
    kernel-feed stall fraction.  Runs in this process only: a child could
    not reach a chip the parent holds.
    """
    import jax

    avail = int(jax.device_count())
    out = {"n_devices_available": avail, "rows": []}
    for n_mesh in sorted({1, avail}):
        try:
            row = _kernel_feed_row(n_mesh, min_support)
        except Exception as e:
            row = {"mesh_devices": n_mesh,
                   "error": f"{type(e).__name__}: {e}"}
        out["rows"].append(row)
        # Dict view for the sentinel (its _dig walks dicts only).
        out[f"mesh{n_mesh}"] = row
    real = [r for r in out["rows"] if r.get("pairs_per_sec_per_chip")]
    if len(real) >= 2:
        out["scaling_efficiency"] = round(
            real[-1]["pairs_per_sec_per_chip"]
            / real[0]["pairs_per_sec_per_chip"], 3)
    return out


def _bench_ingest() -> dict:
    """Native ingest rows on a generated multi-file workload.

    Builds BENCH_INGEST_FILES plain N-Triples files (one of them gz so the
    gz path is exercised too) and measures four rows, all asserted
    bit-identical:

    * ``serial_legacy`` — 1 thread, every speed rung OFF (scalar scan,
      fread + arena copies, no gz pipeline): the pre-SWAR engine, kept as
      the denominator for ``parse_speedup_vs_legacy``;
    * ``serial`` — 1 thread, rungs at their env-resolved defaults (SWAR +
      mmap zero-copy): the single-thread acceptance row;
    * ``parallel`` — BENCH_INGEST_THREADS (default: auto = physical cores
      clamped to affinity) workers;
    * ``parallel_forced`` — only when auto resolves to 1 (1-core box): 2
      workers, so the parallel engine is still exercised and its
      determinism recorded, clearly labeled as oversubscribed.

    `n_cores` is recorded so a 1-core proxy row cannot be mistaken for a
    parallel-speedup measurement (the parallel acceptance bar needs
    >= 4 cores).
    """
    import gzip
    import tempfile

    from rdfind_tpu.io import native as native_io

    if not native_io.available():
        return {"error": "native ingest unavailable"}
    n_lines = int(os.environ.get("BENCH_INGEST_LINES", 400_000))
    n_files = int(os.environ.get("BENCH_INGEST_FILES", 8))
    threads = int(os.environ.get("BENCH_INGEST_THREADS") or
                  native_io.ingest_threads())
    rng = np.random.default_rng(11)
    out = {"n_cores": os.cpu_count(),
           "n_physical_cores": native_io.physical_cores(),
           "threads": threads, "n_files": n_files, "n_lines": n_lines}
    with tempfile.TemporaryDirectory() as td:
        paths = []
        per_file = max(n_lines // n_files, 1)
        for fi in range(n_files):
            s = rng.integers(0, 60_000, per_file)
            p = rng.integers(0, 240, per_file)
            o = rng.integers(0, 25_000, per_file)
            lines = "".join(
                f"<http://ex/s{a}> <http://ex/p{b}> \"lit {c}\" .\n"
                for a, b, c in zip(s, p, o))
            if fi == n_files - 1:  # one gz member: file-level parallelism only
                path = os.path.join(td, f"f{fi}.nt.gz")
                with gzip.open(path, "wt") as g:
                    g.write(lines)
            else:
                path = os.path.join(td, f"f{fi}.nt")
                with open(path, "w") as f:
                    f.write(lines)
            paths.append(path)
        out["input_bytes"] = sum(os.path.getsize(p) for p in paths)
        legacy_env = {"RDFIND_INGEST_SWAR": "0", "RDFIND_INGEST_MMAP": "0",
                      "RDFIND_INGEST_GZ_PIPELINE": "0"}
        saved = {k: os.environ.get(k) for k in legacy_env}
        os.environ.update(legacy_env)
        try:
            st: dict = {}
            ids_l, d_l = native_io.ingest_files(paths, threads=1, stats=st)
            out["serial_legacy"] = st
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        modes = [("serial", 1), ("parallel", threads)]
        if threads <= 1:
            modes.append(("parallel_forced", 2))
        results = {}
        for mode, t in modes:
            st = {}
            ids, d = native_io.ingest_files(paths, threads=t, stats=st)
            results[mode] = (ids, d)
            out[mode] = st
        ids_s, d_s = results["serial"]
        out["outputs_identical"] = bool(
            np.array_equal(ids_s, ids_l)
            and list(d_s.values) == list(d_l.values)
            and all(np.array_equal(ids_s, ids_m)
                    and list(d_s.values) == list(d_m.values)
                    for mode, (ids_m, d_m) in results.items()
                    if mode != "serial"))
        out["speedup_vs_serial"] = round(
            out["parallel"]["triples_per_sec"]
            / max(out["serial"]["triples_per_sec"], 1e-9), 3)
        out["parse_speedup_vs_legacy"] = round(
            out["serial_legacy"]["parse_ms"]
            / max(out["serial"]["parse_ms"], 1e-9), 3)
    return out


def _run(n: int, min_support: int, device: dict) -> dict:
    backend = device["platform"]

    from rdfind_tpu import oracle
    from rdfind_tpu.models import allatonce
    from rdfind_tpu.utils.synth import generate_triples

    triples = generate_triples(n, seed=42)

    # Warm-up (compile cache) on the same shapes, then measure.
    stats = {}
    allatonce.discover(triples, min_support, stats=stats)
    t0 = time.perf_counter()
    table = allatonce.discover(triples, min_support, stats=stats)
    elapsed = time.perf_counter() - t0
    pairs_per_sec = stats["total_pairs"] / elapsed

    # Oracle baseline: the single-core pure-Python joinline oracle on the SAME
    # workload (like-for-like; the r2 subsample extrapolation understated the
    # oracle's superlinear pair load).  ~15 s at the 200k default.
    all_t = [tuple(int(x) for x in row) for row in triples]
    t0 = time.perf_counter()
    oracle.discover_cinds_joinline(all_t, min_support)
    oracle_elapsed = time.perf_counter() - t0
    oracle_pairs_per_sec = stats["total_pairs"] / oracle_elapsed

    detail = {
        "backend": backend,
        # Row identity for the regression sentinel: git sha, core count and
        # the resolved RDFIND_* knob set this run measured under.
        "provenance": obs_sentinel.provenance(backend=backend),
        "n_triples": n, "min_support": min_support,
        "wall_s": round(elapsed, 3), "total_pairs": stats["total_pairs"],
        "n_lines": stats["n_lines"], "max_line": stats["max_line"],
        "cinds": len(table),
        "pair_backend": stats.get("pair_backend"),
        # Occupancy-corrected roofline inputs: the resolved membership dtype
        # and the dense plan's real/issued-FLOP record for THIS workload.
        "cooc_dtype": stats.get("cooc_dtype"),
        "dense_plan": stats.get("dense_plan"),
        # Degradation ledger of the headline run (None on a fault-free run).
        "degradations": stats.get("degradations"),
        "oracle_wall_s": round(oracle_elapsed, 3),
        "oracle_pairs_per_sec": round(oracle_pairs_per_sec, 1),
        # Integrity plane: the headline run's output digest plus the
        # workload it was computed over.  The sentinel compares digests only
        # between rows with the same workload (and provenance key), so a
        # digest change there is a correctness — not perf — regression.
        "output_digest": obs_integrity.digest_hex(
            *obs_integrity.digest_table(table)),
        "workload": {"n_triples": n, "min_support": min_support,
                     "seed": 42},
    }

    # The DEFAULT strategy (SmallToLarge, id 1) on the same workload, so the
    # default path always has a recorded number too (best-effort).
    try:
        from rdfind_tpu.models import small_to_large
        s2l_stats: dict = {}
        small_to_large.discover(triples, min_support, stats=s2l_stats)  # warm
        s2l_stats.clear()
        t0 = time.perf_counter()
        s2l_table = small_to_large.discover(triples, min_support,
                                            stats=s2l_stats)
        s2l_wall = time.perf_counter() - t0
        detail["s2l"] = {
            "wall_s": round(s2l_wall, 3),
            "total_pairs": int(s2l_stats.get("total_pairs", 0)),
            "pairs_per_sec": round(
                s2l_stats.get("total_pairs", 0) / s2l_wall, 1),
            "cinds": len(s2l_table),
        }
    except Exception as e:
        detail["s2l"] = {"error": f"{type(e).__name__}: {e}"}

    # Strategy 2 on the same workload: the sketch round + dense-matmul
    # verification (r4 rework; was the chunked host loop, the strategy's
    # TPU-matrix laggard at 32.5 s on this workload's config-1 sibling).
    try:
        from rdfind_tpu.models import approximate
        ap_stats: dict = {}
        approximate.discover(triples, min_support, stats=ap_stats)  # warm
        ap_stats.clear()
        t0 = time.perf_counter()
        ap_table = approximate.discover(triples, min_support, stats=ap_stats)
        ap_wall = time.perf_counter() - t0
        detail["approx"] = {
            "wall_s": round(ap_wall, 3),
            "total_pairs": int(ap_stats.get("total_pairs", 0)),
            "pairs_per_sec": round(
                ap_stats.get("total_pairs", 0) / ap_wall, 1),
            "pair_backend": ap_stats.get("pair_backend"),
            "cinds": len(ap_table),
        }
    except Exception as e:
        detail["approx"] = {"error": f"{type(e).__name__}: {e}"}

    # Roofline: achieved FLOP/s of the dense cooc matmul vs chip peak
    # (VERDICT r3: pairs/s alone cannot show how much headroom remains).
    try:
        detail["mfu"] = _measure_mfu(stats, device)
    except Exception as e:
        detail["mfu"] = {"error": f"{type(e).__name__}: {e}"}

    # Pipelined pass executor vs forced-sync on a multi-pass streaming
    # workload (dispatch-overlap telemetry).
    try:
        detail["pipelined_passes"] = _bench_pipelined_passes(min_support)
    except Exception as e:
        detail["pipelined_passes"] = {"error": f"{type(e).__name__}: {e}"}

    # Flat vs hierarchical exchange on the 2-host pod proxy (per-site
    # ICI/DCN split + the combiner's DCN reduction; a multi-process run
    # measures the real topology instead).
    try:
        detail["exchange"] = _bench_exchange(min_support)
    except Exception as e:
        detail["exchange"] = {"error": f"{type(e).__name__}: {e}"}

    # Multi-chip kernel-feed rows (rung 3): the sharded dense pass at mesh
    # 1 and every local device — pairs/s/chip, overlap efficiency, and how
    # long the dense kernels starved on exchange (stall fraction).
    try:
        detail["kernel_feed"] = _bench_kernel_feed(min_support)
    except Exception as e:
        detail["kernel_feed"] = {"error": f"{type(e).__name__}: {e}"}

    # Parallel native ingest vs the serial engine (front-door throughput:
    # triples/s, bytes/s, per-phase ms, identical-output check).
    try:
        detail["ingest"] = _bench_ingest()
    except Exception as e:
        detail["ingest"] = {"error": f"{type(e).__name__}: {e}"}

    # Data-plane snapshot of the headline workload: the log2 join-line and
    # capture-support distributions (obs/datastats.py), which bench rows
    # never recorded before — one extra discover with RDFIND_DATASTATS
    # forced on, so the measured walls above stay on the disabled path.
    try:
        ds_stats: dict = {}
        prev_ds = os.environ.get("RDFIND_DATASTATS")
        os.environ["RDFIND_DATASTATS"] = "1"
        try:
            allatonce.discover(triples, min_support, stats=ds_stats)
        finally:
            if prev_ds is None:
                os.environ.pop("RDFIND_DATASTATS", None)
            else:
                os.environ["RDFIND_DATASTATS"] = prev_ds
        detail["datastats"] = {
            k: ds_stats[k] for k in ("datastats_lines", "datastats_captures",
                                     "datastats_block_skip") if k in ds_stats}
    except Exception as e:
        detail["datastats"] = {"error": f"{type(e).__name__}: {e}"}

    # Unified obs snapshot (ISSUE 5): the metrics-registry mirror of every
    # stats key the process published (dispatch + exchange + ingest + fault
    # telemetry, accumulated across the rows above) plus the current device
    # memory watermarks — ONE schema for every BENCH_* artifact going
    # forward.
    try:
        detail["obs"] = obs.snapshot()
    except Exception as e:
        detail["obs"] = {"error": f"{type(e).__name__}: {e}"}

    # Collective-watchdog cost row: the disabled guard is on every dispatch
    # of every run, so its per-hit cost is a standing tax — sample it here
    # (micro-loop over the real guard path) next to the counters the
    # headline run accumulated, so the <2% overhead budget asserted in
    # tests/test_watchdog.py stays visible in every BENCH_* artifact.
    try:
        from rdfind_tpu.runtime import watchdog
        reps = 20000
        t0 = time.perf_counter()
        for _ in range(reps):
            with watchdog.collective("pairs", nbytes=1024):
                pass
        per_guard_us = (time.perf_counter() - t0) / reps * 1e6
        detail["watchdog"] = {
            "disabled_per_guard_us": round(per_guard_us, 3),
            **watchdog.snapshot(),
        }
    except Exception as e:
        detail["watchdog"] = {"error": f"{type(e).__name__}: {e}"}

    # Pallas packed-bitset kernel vs jnp planes path, on this backend.
    try:
        from rdfind_tpu.ops import sketch
        pk = sketch.kernel_selfcheck(n_rows=1024, n_bits=4096,
                                     backend=backend)
        if backend == "tpu" and "pallas_ms" in pk:
            # Fraction-of-peak for the containment kernel: the same logical
            # contraction as a dense bf16 matmul is 2*D*R*bits FLOPs, so
            # effective FLOP/s = that work over the packed kernel's time.
            peaks = peaks_for(device["device_kind"])
            eq_flops = 2.0 * pk["n_rows"] * pk["n_rows"] * pk["bits"]
            eff = eq_flops / (pk["pallas_ms"] / 1e3)
            pk["equiv_dense_tflops"] = round(eff / 1e12, 3)
            pk["peak_fraction"] = round(
                eff / (peaks["bf16_tflops"] * 1e12), 4)
            pk["hbm_peak_fraction"] = round(
                pk["pallas_gbps"] / peaks["hbm_gbps"], 4)
            # Roofline row at a launch-amortized shape: the 1024-row probe is
            # dispatch-bound; 8192 rows move ~600 MB/call, enough to read the
            # kernel's real HBM bandwidth (VERDICT r4 item 7).
            try:
                big = sketch.kernel_selfcheck(n_rows=8192, n_bits=4096,
                                              backend=backend, repeats=3)
                pk["roofline_8k"] = {
                    k: big[k] for k in ("pallas_ms", "pallas_kernel_ms",
                                        "jnp_ms", "speedup",
                                        "hbm_bytes_model", "pallas_gbps")
                    if k in big}
                if "pallas_gbps" in big:
                    pk["roofline_8k"]["hbm_peak_fraction"] = round(
                        big["pallas_gbps"] / peaks["hbm_gbps"], 4)
            except Exception as e:
                pk["roofline_8k"] = {"error": f"{type(e).__name__}: {e}"}
        # Per-mode rows: plane bits x fused verdict, each with an HBM
        # watermark sample (rung-2 acceptance: the fused row's peak must
        # undercut the materialized row's by the cooc tile it never writes).
        try:
            km = _bench_kernel_modes(backend)
            pk["modes"] = km["modes"]
            pk["modes_by_name"] = km["by_mode"]
        except Exception as e:
            pk["modes"] = {"error": f"{type(e).__name__}: {e}"}
        detail["pallas_vs_jnp"] = pk
    except Exception as e:  # kernel comparison is best-effort
        detail["pallas_vs_jnp"] = {"error": f"{type(e).__name__}: {e}"}

    return {
        "metric": "cind_pairs_checked_per_sec_per_chip",
        "value": round(pairs_per_sec, 1),
        "unit": "pairs/s",
        "vs_baseline": round(pairs_per_sec / oracle_pairs_per_sec, 3),
        "detail": detail,
    }


def _record_history(result: dict) -> None:
    """Append this run to the sentinel's BENCH_HISTORY.jsonl (stderr-only
    reporting: stdout stays the single JSON result line).  BENCH_HISTORY
    overrides the path; "0" disables."""
    dest = os.environ.get("BENCH_HISTORY", "")
    if dest == "0":
        return
    try:
        row = obs_sentinel.append(result, path=dest or None)
        print(f"bench: history row appended (sha={row['sha']}, "
              f"{len(row['metrics'])} metrics)", file=sys.stderr, flush=True)
    except Exception as e:  # history is telemetry, never a bench failure
        print(f"bench: history append failed: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)


def main():
    n = int(os.environ.get("BENCH_TRIPLES", 200_000))
    min_support = int(os.environ.get("BENCH_MIN_SUPPORT", 10))
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    device = require_device()
    from rdfind_tpu.runtime import compile_cache

    compile_cache.place()
    if os.environ.get("BENCH_KERNEL_MODES_ONLY"):
        # Fast standalone artifact for the rung-3 kernel-mode grid (plane
        # bits x emit_pipeline x fused): no oracle, no headline discovery.
        # Same detail shape bench.py embeds under detail.pallas_vs_jnp,
        # promoted to the headline.
        try:
            km = _bench_kernel_modes(device["platform"])
            walls = [r["pallas_ms"] for r in km["modes"]
                     if isinstance(r.get("pallas_ms"), (int, float))]
            result = {
                "metric": "kernel_mode_best_pallas_ms",
                "value": min(walls) if walls else 0,
                "unit": "ms", "vs_baseline": 1.0,
                "detail": {"pallas_vs_jnp": {"modes": km["modes"],
                                             "modes_by_name": km["by_mode"]},
                           "obs": obs.snapshot()},
            }
        except Exception as e:
            result = {"metric": "kernel_mode_best_pallas_ms", "value": 0,
                      "unit": "ms", "vs_baseline": 0,
                      "detail": {"error": f"{type(e).__name__}: {e}"}}
    elif os.environ.get("BENCH_INGEST_ONLY"):
        # Fast standalone artifact for the ingest row (no jax warm-up, no
        # discovery): the same JSON shape bench.py embeds under
        # detail.ingest, promoted to the headline.
        try:
            ing = _bench_ingest()
            value = ing.get("parallel", {}).get("triples_per_sec", 0)
            base = ing.get("serial", {}).get("triples_per_sec", 0)
            result = {
                "metric": "ingest_triples_per_sec",
                "value": value, "unit": "triples/s",
                "vs_baseline": round(value / max(base, 1e-9), 3),
                "detail": {"ingest": ing, "obs": obs.snapshot()},
            }
        except Exception as e:
            result = {"metric": "ingest_triples_per_sec", "value": 0,
                      "unit": "triples/s", "vs_baseline": 0,
                      "detail": {"error": f"{type(e).__name__}: {e}"}}
    else:
        try:
            result = _run(n, min_support, device)
        except Exception as e:
            tb = traceback.format_exc(limit=3)
            result = {
                "metric": "cind_pairs_checked_per_sec_per_chip",
                "value": 0,
                "unit": "pairs/s",
                "vs_baseline": 0,
                "detail": {"error": f"{type(e).__name__}: {e}",
                           "traceback": tb.splitlines()[-3:]},
            }
    result["device"] = device
    print(json.dumps(result))
    _record_history(result)


if __name__ == "__main__":
    main()
