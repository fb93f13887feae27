"""The RDFind job driver: read -> parse -> preprocess -> discover -> sink.

Mirrors the reference's program lifecycle (AbstractProgram.java:112-139: prepare,
execute, statistics, cleanup) and its plan construction (RDFind.createFlinkPlan,
programs/RDFind.scala:196-580), with Flink stages replaced by host ingest + the
jitted device pipelines.  Per-phase wall-clock is recorded like JobMeasurement
(AbstractFlinkProgram.java:65-77,203-247), including the machine-readable CSV line.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import signal
import sys
import threading
import time

import numpy as np

from ..data import CindTable
from ..dictionary import BlobDictionary, Dictionary, intern_triples
from ..io import native, ntriples, prefixes, reader
from ..models import allatonce, approximate, late_bb, sharded, small_to_large
from ..obs import memory as obs_memory
from ..obs import (compiles, console, flightrec, integrity, metrics, report,
                   tracer)
from ..parallel.mesh import make_mesh
from . import checkpoint, serving


@dataclasses.dataclass
class Config:
    """Mirrors the reference's Parameters (programs/RDFind.scala:639-721); flags
    that are meaningless off-JVM (e.g. -jar) are dropped, flags whose machinery is
    built-in here (e.g. --find-frequent-captures: always on, exact) are accepted and
    noted in the CLI help."""

    input_paths: list[str] = dataclasses.field(default_factory=list)
    prefix_paths: list[str] = dataclasses.field(default_factory=list)
    min_support: int = 10
    traversal_strategy: int = 1
    projections: str = "spo"
    use_frequent_item_set: bool = False
    use_association_rules: bool = False
    clean_implied: bool = False
    distinct_triples: bool = False
    asciify_triples: bool = False
    tabs: bool = False
    only_read: bool = False
    only_join: bool = False
    output_file: str | None = None
    ar_output_file: str | None = None
    collect_result: bool = False
    debug_level: int = 0
    counter_level: int = 0
    n_devices: int = 1  # degree of parallelism (the reference's -dop)
    retry_on_preempt: int = 0  # in-driver preemption supervisor retry budget
    native_ingest: bool = True  # C++ fused read+parse+intern when applicable
    checkpoint_dir: str | None = None  # stage-boundary checkpoints (resume)
    explicit_threshold: int = -1  # != -1: half-approximate 1/1 (strategy 1)
    sbf_bits: int = -1  # count-min counter bits (-1 = sized to min_support)
    balanced_11: bool = False  # halve 1/1 emission via pair ownership
    print_plan: bool = False  # dump the logical plan as JSON before executing
    profile_dir: str | None = None  # XLA profiler trace of the whole run
    encoding: str = "utf-8"  # input charset; "auto" sniffs a BOM per file
    file_filter: str | None = None  # regex on input-file basenames
    # Skew-engine policy (sharded runs; the reference's --rebalance-* flags):
    rebalance_strategy: int = 1  # 1 = hash-slice, 2 = range-slice ownership
    rebalance_threshold: float = 1.0  # scales the avg-load split factor
    rebalance_max_load: float = 10_000.0 * 10_000.0  # absolute split trigger
    merge_window_size: int = -1  # pair-merge window (chunked backend; -1 auto)
    combinable_join: bool = True  # False: ship raw join candidates (ablation)
    collector: str | None = None  # "host:port" remote result sink (RMI analog)
    find_only_fcs: int = 0  # >=1: stop after frequent-condition mining
    create_join_histogram: bool = False  # print join-line size histogram
    sharded_ingest: bool = False  # each host parses only its file subset
    interning: str = "auto"  # sharded-ingest dictionary: partitioned|replicated
    trace_dir: str | None = None  # obs: host span trace + heartbeat directory
    metrics_file: str | None = None  # obs: Prometheus text exposition file
    console_port: int | None = None  # obs: live HTTP console (0 = ephemeral)
    # Incremental discovery (runtime/delta.py): --delta runs a change batch
    # against a persisted base bundle; --delta-state makes a full run write
    # one; --deletes names the delete batch files for a delta run.
    delta_base: str | None = None
    delta_state: str | None = None
    delete_paths: list[str] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class RunResult:
    table: CindTable
    dictionary: Dictionary | None
    triples: np.ndarray | None
    counters: dict
    timings: dict  # phase -> seconds

    def decoded(self):
        return self.table.decoded(self.dictionary)


class _Phases:
    def __init__(self):
        self.timings = {}

    def run(self, name, fn):
        t0 = time.perf_counter()
        # Registry-only position gauge: the console's /progress reads it
        # live; never written into a legacy stats dict.
        metrics.gauge_set(None, "run_stage", name)
        with tracer.span(name, cat=tracer.CAT_STAGE):
            out = fn()
        self.timings[name] = time.perf_counter() - t0
        metrics.observe(f"stage_{name}_ms", self.timings[name] * 1e3)
        if tracer.enabled() or metrics.export_requested():
            # Stage-boundary HBM watermark (the coarse lane; the pass
            # executor samples per pass) + a fresh exposition snapshot so a
            # scraper sees progress mid-run, not only at exit.
            obs_memory.sample(None, label=f"stage {name}")
            metrics.flush_export()
        return out


def _load_prefix_trie(cfg: Config):
    """(trie, url_of) from the --prefixes files (shared by the replicated
    shorten-urls phase and the sharded-ingest per-host transform)."""
    ppaths = reader.resolve_path_patterns(cfg.prefix_paths)
    pairs = []
    for _, line in reader.iter_lines(ppaths):
        p = prefixes.parse_prefix_line(line)
        if p is not None:
            pairs.append(p)
    return prefixes.build_prefix_trie(pairs), dict(pairs)


def _resolve_inputs(cfg: Config):
    """Input paths + quad-format sniff (shared by the native and Python paths).
    Empty inputs are legal only for delete-only delta runs (the CLI enforces
    that), so the sniff just defaults to triples then."""
    paths = (reader.resolve_path_patterns(cfg.input_paths, cfg.file_filter)
             if cfg.input_paths else [])
    is_nq = bool(paths) and paths[0].endswith((".nq", ".nq.gz"))
    return paths, is_nq


def load_triples(cfg: Config, phases: _Phases, counters: dict):
    """Host ingest: files -> list of (s, p, o) string tokens."""
    paths, is_nq = _resolve_inputs(cfg)

    def parse_all():
        out = []
        for _, line in reader.iter_lines(paths, encoding=cfg.encoding):
            t = (ntriples.parse_tab_line(line) if cfg.tabs
                 else ntriples.parse_line(line, expect_quad=is_nq))
            if t is not None:
                out.append(t)
        return out

    triples = phases.run("read+parse", parse_all)
    counters["input-triples"] = len(triples)

    if cfg.asciify_triples:
        triples = phases.run("asciify", lambda: [
            tuple(prefixes.asciify(v) for v in t) for t in triples])

    if cfg.prefix_paths:
        def shorten():
            trie, url_of = _load_prefix_trie(cfg)
            return [tuple(prefixes.shorten_term(v, trie, url_of) for v in t)
                    for t in triples]

        triples = phases.run("shorten-urls", shorten)

    return triples


def _checkpoint_payloads(cfg: Config, use_native: bool):
    """(ingest_payload, discover_payload): everything feeding each stage."""
    paths, is_nq = _resolve_inputs(cfg)
    ingest_payload = dict(
        inputs=checkpoint.input_signature(paths), is_nq=is_nq, tabs=cfg.tabs,
        asciify=cfg.asciify_triples, encoding=cfg.encoding,
        prefixes=(checkpoint.input_signature(
            reader.resolve_path_patterns(cfg.prefix_paths))
            if cfg.prefix_paths else []),
        distinct=cfg.distinct_triples,
        # The two ingest implementations agree on valid UTF-8 but are allowed
        # to differ on degenerate inputs; a checkpoint from one must not
        # satisfy a run explicitly requesting the other.
        native=use_native)
    # The mesh size is deliberately NOT fingerprinted (elastic resume): the
    # CIND output is bit-identical across device counts by the sharded
    # pipelines' contract, so a discover checkpoint from a mesh-8 run must
    # satisfy the mesh-2 run that resumes it.
    discover_payload = dict(
        ingest=ingest_payload, min_support=cfg.min_support,
        strategy=cfg.traversal_strategy, projections=cfg.projections,
        use_fis=cfg.use_frequent_item_set, use_ars=cfg.use_association_rules,
        clean_implied=cfg.clean_implied)
    if _half_approx_active(cfg):
        # Only fingerprint the knobs when they actually reach the strategy —
        # a no-effect flag must not invalidate an identical-output checkpoint.
        discover_payload.update(explicit_threshold=cfg.explicit_threshold,
                                sbf_bits=cfg.sbf_bits)
    # balanced_11 is output-neutral, so it never enters the fingerprint.
    return ingest_payload, discover_payload


def _checkpoint_fps(cfg: Config, use_native: bool):
    """(ingest_fp, discover_fp): digests of everything feeding each stage."""
    ingest_payload, discover_payload = _checkpoint_payloads(cfg, use_native)
    return (checkpoint.fingerprint(ingest_payload),
            checkpoint.fingerprint(discover_payload))


def _join_histogram(ids: np.ndarray, projections: str):
    """(line_size, occurrence_count) pairs over the unfiltered join, using the
    same device emission as the real pipelines."""
    import jax.numpy as jnp

    from ..ops import frequency, segments
    from ..ops.emission import emit_join_candidates

    n = ids.shape[0]
    if n == 0:
        return []
    cap = segments.pow2_capacity(n)
    padded = np.pad(np.asarray(ids, np.int32), ((0, cap - n), (0, 0)),
                    constant_values=np.iinfo(np.int32).max)
    t = jnp.asarray(padded)
    valid = jnp.arange(cap, dtype=jnp.int32) < n
    cands = emit_join_candidates(t, frequency.no_filter(valid), projections)
    cols, v, _, n_rows = segments.masked_unique(
        [cands.join_val, cands.code, cands.v1, cands.v2], cands.valid)
    jv = np.asarray(cols[0])[: int(n_rows)]
    _, line_sizes = np.unique(jv, return_counts=True)
    sizes, times = np.unique(line_sizes, return_counts=True)
    return list(zip(sizes.tolist(), times.tolist()))



def _is_primary() -> bool:
    """True on the host that owns sinks/reports (process 0; SPMD convention:
    every host computes, one host writes)."""
    import jax
    try:
        return jax.process_index() == 0
    except Exception:
        return True

def _skew_from_cfg(cfg: Config) -> "sharded.SkewPolicy":
    """The one cfg -> SkewPolicy mapping (defaults compare equal to
    sharded.DEFAULT_SKEW, so 'did the user change anything' is a != check
    rather than re-spelled default literals)."""
    return sharded.SkewPolicy(
        strategy=cfg.rebalance_strategy,
        factor=sharded.REBALANCE_FACTOR * cfg.rebalance_threshold,
        max_load=cfg.rebalance_max_load)


def _half_approx_active(cfg: Config) -> bool:
    """Whether --explicit-threshold actually selects the single-device
    half-approximate 1/1 round: default strategy, single device.  Sharded
    runs have their own two-round count-min mode — env-gated
    (RDFIND_SHARDED_HALF_APPROX, resolved inside models/sharded), not
    flag-gated, because its output is bit-identical and so never part of
    the run's logical configuration."""
    return (cfg.explicit_threshold != -1 and cfg.traversal_strategy == 1
            and cfg.n_devices == 1)


# Logical stages of each traversal strategy, for --print-plan (the analog of
# the reference's Flink execution-plan JSON dump, programs/RDFind.scala:75-81).
_STRATEGY_PLANS = {
    0: ["emit-join-candidates", "group-by-join-value",
        "pair-phase (co-occurrence matmul / chunked counts)",
        "intersect-refsets", "support-filter", "split-cind-sets"],
    1: ["emit-join-candidates", "group-by-join-value",
        "overlap-1/1", "cind-1/1",
        "generate-1/2", "extract-1/2",
        "generate-2/1", "extract-2/1", "infer-2/1 (from 1/1)",
        "generate-2/2", "prune-2/2-vs-1/2", "extract-2/2",
        "union-families"],
    2: ["emit-join-candidates", "group-by-join-value",
        "round-1: bloom refset sketches + containment matmul",
        "round-2: exact re-verification of sketch candidates",
        "support-filter", "split-cind-sets"],
    3: ["emit-join-candidates", "group-by-join-value",
        "round-1: half-approximate unary-dependent CINDs",
        "round-2: binary dependents pruned by round-1 CINDs",
        "union-rounds", "split-cind-sets"],
}


def describe_plan(cfg: Config) -> dict:
    """A JSON-able description of the stages this config will execute."""
    if cfg.sharded_ingest:
        mode = ("replicated dictionary exchange" if cfg.interning == "replicated"
                else "hash-partitioned interning")
        pre = [f"sharded-ingest (per-host parse+intern, {mode}, "
               "per-device row donation)"]
        pre = ([ "asciify (per-host, during parse)"] if cfg.asciify_triples
               else []) + \
              (["shorten-urls (per-host, during parse)"] if cfg.prefix_paths
               else []) + pre
        if cfg.distinct_triples:
            pre.append("distinct (hash-owner row dedup)")
    else:
        pre = ["read+parse"]
        if cfg.asciify_triples:
            pre.append("asciify")
        if cfg.prefix_paths:
            pre.append("shorten-urls")
        pre.append("intern")
        if cfg.distinct_triples:
            pre.append("distinct")
    discover = list(_STRATEGY_PLANS.get(cfg.traversal_strategy, ["unknown"]))
    if cfg.use_frequent_item_set:
        discover.insert(0, "frequent-item-sets (condition-support filter)")
    if cfg.use_association_rules and cfg.use_frequent_item_set:
        discover.insert(1, "association-rules (emission suppression + filter)")
    if _half_approx_active(cfg):
        for i, s in enumerate(discover):
            if s == "overlap-1/1":
                discover[i] = ("overlap-1/1 (half-approximate: explicit top-K "
                               "+ count-min spill, two-round)")
    if cfg.clean_implied:
        discover.append("remove-implied-cinds")
    sinks = []
    if cfg.output_file:
        sinks.append(f"write-output -> {cfg.output_file}")
    if cfg.ar_output_file:
        sinks.append(f"write-ar-output -> {cfg.ar_output_file}")
    if cfg.collector:
        sinks.append(f"collect-remote -> {cfg.collector}")
    if cfg.collect_result:
        sinks.append("collect-result (stdout)")
    return {
        "strategy": cfg.traversal_strategy,
        "n_devices": cfg.n_devices,
        "backend": "sharded-mesh" if cfg.n_devices > 1 else "single-device",
        "min_support": cfg.min_support,
        "projections": cfg.projections,
        "stages": {"ingest": pre, "discover": discover, "sinks": sinks},
    }


def _trivial_cind_mask(table: CindTable) -> np.ndarray:
    """True where a CIND is trivially implied by its own dependent capture:
    same projection and the referenced condition is a value-matching sub-
    condition of the dependent one (Condition.implies semantics,
    data/Condition.scala:35-43).  These must never appear in the output; the
    reference counts them at DEBUG_LEVEL_SANITY (RDFind.scala:497-504)."""
    from .. import conditions as cc

    dep = np.asarray(table.dep_code)
    ref = np.asarray(table.ref_code)
    same_proj = cc.secondary(dep) == cc.secondary(ref)
    sub = cc.is_subcode(cc.primary(ref), cc.primary(dep))
    d1, d2, _ = cc.decode(dep)
    r1, r2, _ = cc.decode(ref)
    dv1 = np.asarray(table.dep_v1)
    dv2 = np.asarray(table.dep_v2)
    rv1 = np.asarray(table.ref_v1)
    rv2 = np.asarray(table.ref_v2)

    def dep_val(field):  # dependent's condition value on a single-bit field
        return np.where(field == d1, dv1, np.where(field == d2, dv2, -1))

    v_ok = np.where(r1 != 0, dep_val(r1) == rv1, True) & np.where(
        r2 != 0, dep_val(r2) == rv2, True)
    return same_proj & sub & v_ok


def _all_hosts_agree(flag: bool) -> bool:
    """True iff `flag` is True on EVERY host (one tiny DCN allgather,
    deadman-armed: a peer that never votes becomes a recoverable
    preemption instead of an indefinite block)."""
    import jax

    from . import watchdog

    if jax.process_count() == 1:
        return flag
    from jax.experimental import multihost_utils

    with watchdog.collective("allgather", 4 * jax.process_count()):
        hits = np.asarray(multihost_utils.process_allgather(
            np.asarray([flag], np.int32))).reshape(-1)
    return bool(hits.min())


def _run_sharded_ingest(cfg: Config, phases: _Phases,
                        counters: dict) -> RunResult:
    """Multi-host sharded ingest + preshard discovery (each host parses only
    its file subset; no host materializes the full triple table)."""
    from . import multihost_ingest

    stats: dict = {}
    paths, is_nq = _resolve_inputs(cfg)
    mesh = make_mesh(cfg.n_devices if cfg.n_devices > 1 else None)

    # Token-local preprocessing (asciify, URL shortening) runs on each host's
    # own shard during parse — same order as the replicated path's phases.
    transform = None
    if cfg.asciify_triples or cfg.prefix_paths:
        steps = []
        if cfg.asciify_triples:
            steps.append(prefixes.asciify)
        if cfg.prefix_paths:
            trie, url_of = _load_prefix_trie(cfg)
            steps.append(lambda v: prefixes.shorten_term(v, trie, url_of))

        def transform(v, _steps=tuple(steps)):
            for f in _steps:
                v = f(v)
            return v

    ckpt = discover_fp = progress = None
    ingest_fp = ""
    if cfg.checkpoint_dir:
        import jax

        # Per-host ingest cache + an all-hosts-agree discover checkpoint.
        # The fingerprints extend the replicated payloads with the sharded
        # layout knobs (host count and interning change the artifacts).
        native_eff = multihost_ingest.native_parse_eligible(
            cfg.native_ingest, transform, cfg.encoding)
        ingest_payload, discover_payload = _checkpoint_payloads(cfg,
                                                               native_eff)
        # The cached artifact is the PRE-dedup local parse (dedupe_preshard
        # runs after ingest on every run), so --distinct-triples must not
        # invalidate it; discovery output still depends on it, so `distinct`
        # stays in the discover payload's embedded copy.
        cache_payload = {k: v for k, v in ingest_payload.items()
                         if k != "distinct"}
        # The host count shapes the ingest ARTIFACTS (per-host file subsets,
        # per-host dictionary shards) but not the discover OUTPUT — so it
        # fingerprints the ingest cache only.  Keeping it out of the discover
        # fingerprint lets a preempted N-host run resume its committed work
        # on a different host count (elastic resume).
        ingest_extra = dict(sharded=True, num_hosts=jax.process_count(),
                            interning=cfg.interning)
        discover_extra = dict(sharded=True, interning=cfg.interning)
        ckpt = checkpoint.CheckpointStore(cfg.checkpoint_dir)
        ingest_fp = checkpoint.fingerprint({**cache_payload, **ingest_extra})
        discover_fp = checkpoint.fingerprint({**discover_payload,
                                              **discover_extra})
        progress = checkpoint.ProgressStore(ckpt, discover_fp)

    def ingest():
        hit: list = []
        out = multihost_ingest.sharded_ingest(
            paths, mesh, tabs=cfg.tabs, expect_quad=is_nq,
            encoding=cfg.encoding, use_native=cfg.native_ingest,
            partition_dictionary={"auto": None, "partitioned": True,
                                  "replicated": False}[cfg.interning],
            transform=transform, cache=ckpt, cache_fp=ingest_fp,
            cache_hit=hit, stats=stats)
        # The counter means "the run skipped parsing" — only true when EVERY
        # host hit its cache (some hosts re-parsing is a partial resume the
        # primary's report must not overstate).
        if hit and _all_hosts_agree(hit[0]):
            counters["resumed-ingest"] = 1
        return out

    g_triples, g_valid, dictionary, total = phases.run("sharded-ingest",
                                                       ingest)
    counters["input-triples"] = total
    counters["distinct-values"] = len(dictionary)
    _ingest_counters(counters, stats)

    if cfg.only_read:
        # The read-only probe (replicated-path parity; note the sharded ingest
        # interns as it parses, so "read" includes interning here).
        _report(cfg, counters, phases.timings)
        return RunResult(CindTable.empty(), dictionary, None, counters,
                         phases.timings)

    if cfg.distinct_triples:
        def dedupe():
            out = sharded.dedupe_preshard(g_triples, g_valid, mesh)
            counters["distinct-triples"] = out[2]
            return out[:2]
        g_triples, g_valid = phases.run("distinct", dedupe)

    if cfg.create_join_histogram:
        # Distributed join-line size histogram (RDFind.scala:448-452): an
        # extra pass over the preshard, like the reference's extra job.
        def histogram():
            hist = sharded.join_histogram_sharded(
                g_triples, g_valid, cfg.projections, mesh)
            if _is_primary():
                for size, times in hist:
                    print(f"Join size {size} encountered {times}x")
        phases.run("join-histogram", histogram)

    if cfg.only_join:
        # Replicated-path parity: stop before discovery (RDFind's join-only
        # measurement probe).
        _report(cfg, counters, phases.timings)
        return RunResult(CindTable.empty(), dictionary, None, counters,
                         phases.timings)

    if cfg.find_only_fcs >= 1:
        # Distributed frequent-condition report over the preshard (level
        # semantics as in the replicated path: >= 1 unary, >= 2 adds binary).
        def mine_fcs():
            n_unary, n_binary = sharded.count_fcs_sharded(
                g_triples, g_valid, cfg.min_support, mesh,
                include_binary=cfg.find_only_fcs >= 2)
            counters["frequent-single-conditions"] = n_unary
            if n_binary is not None:
                counters["frequent-double-conditions"] = n_binary
                if cfg.use_association_rules and cfg.use_frequent_item_set:
                    rules = sharded.mine_ars_sharded(
                        g_triples, g_valid, cfg.min_support, mesh)
                    counters["association-rules"] = len(rules[0])
        phases.run("frequent-conditions", mine_fcs)
        _report(cfg, counters, phases.timings)
        return RunResult(CindTable.empty(), dictionary, None, counters,
                         phases.timings)

    if (cfg.use_association_rules and not cfg.use_frequent_item_set
            and _is_primary()):
        # Parity with the replicated path's note (RDFind.scala:290-296).
        print("note: --use-ars has no effect without --use-fis "
              "(association rules are mined from the frequent-item sets)",
              file=sys.stderr)

    skew = _skew_from_cfg(cfg)
    # Strategy dispatch over the preshard — all four families run natively on
    # the pre-built global arrays (the reference's default strategy is fully
    # distributed too, plan/SmallToLargeTraversalStrategy.scala:38-171).
    discover_fn = {
        0: sharded.discover_sharded,
        1: sharded.discover_sharded_s2l,
        2: sharded.discover_sharded_approx,
        3: sharded.discover_sharded_late_bb,
    }.get(cfg.traversal_strategy)
    if discover_fn is None:
        raise ValueError(
            f"unknown traversal strategy {cfg.traversal_strategy}")
    table = None
    if ckpt is not None:
        import jax

        # Per-host stage file: hosts sharing one checkpoint dir must not race
        # on a common tmp path, and hosts with private dirs must each hold a
        # copy for the all-hosts-agree resume below.
        discover_stage = f"discover-host{jax.process_index()}"
        stored = ckpt.load(discover_stage, discover_fp)
        # Discovery is collective: resume ONLY when every host hit, or the
        # misses would enter the collectives alone and deadlock.
        hit = _all_hosts_agree(stored is not None)
        if hit:
            table = phases.run("resume-discover",
                               lambda: checkpoint.decode_cinds(stored))
            metrics.restore(stats, checkpoint.decode_stats(stored))
            counters["resumed-discover"] = 1
    if table is None:
        table = phases.run("discover", lambda: discover_fn(
            None, cfg.min_support, mesh=mesh, skew=skew,
            combine=cfg.combinable_join, projections=cfg.projections,
            use_fis=cfg.use_frequent_item_set,
            use_ars=cfg.use_association_rules,
            clean_implied=cfg.clean_implied, stats=stats,
            progress=progress, preshard=(g_triples, g_valid)))
        if ckpt is not None:
            def save_discover():
                arrays = checkpoint.encode_cinds(table)
                arrays.update(checkpoint.encode_stats(stats))
                _safe_save(ckpt, discover_stage, discover_fp, arrays,
                           counters)
                progress.cleanup()  # per-pass snapshots are now superseded
            phases.run("checkpoint-discover", save_discover)
    counters["cind-counter"] = len(table)
    if (cfg.ar_output_file and cfg.use_frequent_item_set
            and "association_rules" not in stats):
        # --ar-output without --use-ars: rules were not mined during
        # discovery; mine them over the preshard (no host triple table).
        metrics.struct_set(stats, "association_rules", phases.run(
            "mine-ars", lambda: sharded.mine_ars_sharded(
                g_triples, g_valid, cfg.min_support, mesh)))
    counters.update({f"stat-{k}": v for k, v in stats.items()})
    if isinstance(dictionary, multihost_ingest.PartitionedDictionary):
        # Hash-partitioned interning: no host holds the union, so decoding the
        # final CINDs is a collective every host joins (the strings needed are
        # the output's condition values plus any mined rule values — tiny
        # next to the dictionary).
        rules = stats.get("association_rules")
        extra = (np.concatenate([rules[2], rules[3]])
                 if rules is not None else None)
        dictionary = phases.run(
            "resolve-dictionary",
            lambda: dictionary.resolve_table(table, extra_ids=extra))
    _emit_sinks(cfg, phases, counters, table, dictionary, stats, None)
    _report(cfg, counters, phases.timings)
    return RunResult(table, dictionary, None, counters, phases.timings)


@contextlib.contextmanager
def _flush_progress_on_signal(enabled: bool):
    """SIGTERM/SIGINT (the preemption notice on TPU VMs) flush every live
    mid-discover ProgressStore before the process dies, so the successor run
    resumes from the last committed pass instead of the last stage boundary.
    When the flight recorder is armed, the handler also dumps its ring —
    the post-mortem for runs flying without the jsonl tracer.

    Installed only on the main thread, and only when there is work to do
    (checkpointed runs, or an armed flight recorder); the previous handlers
    are restored on exit and re-invoked after the flush.
    """
    if ((not enabled and not flightrec.enabled())
            or threading.current_thread() is not threading.main_thread()):
        yield
        return
    installed = {}

    def handler(signum, frame):
        flightrec.dump(reason=f"signal {signum}")
        if enabled:
            checkpoint.flush_all_progress()
        signal.signal(signum, installed[signum])
        if signum == signal.SIGINT:
            raise KeyboardInterrupt
        os.kill(os.getpid(), signum)  # re-deliver to the restored handler

    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            installed[sig] = signal.signal(sig, handler)
        except (ValueError, OSError):  # exotic embedding; best effort
            pass
    try:
        yield
    finally:
        for sig, prev in installed.items():
            try:
                signal.signal(sig, prev)
            except (ValueError, OSError):
                pass


def _safe_save(ckpt: "checkpoint.CheckpointStore", stage: str, fp: str,
               arrays: dict, counters: dict) -> None:
    """A failed checkpoint write must never fail an otherwise-complete run —
    it only costs the NEXT run its resume (counted + warned, never raised)."""
    try:
        ckpt.save(stage, fp, arrays)
    except Exception as e:
        counters["checkpoint-errors"] = counters.get("checkpoint-errors",
                                                     0) + 1
        print(f"warning: checkpoint stage {stage} not written ({e}); "
              f"the next run will recompute it", file=sys.stderr)


def run(cfg: Config) -> RunResult:
    with _obs_session(cfg):
        with _flush_progress_on_signal(bool(cfg.checkpoint_dir)):
            return _run_supervised(cfg)


def _retry_budget(cfg: Config) -> int:
    """--retry-on-preempt, with RDFIND_RETRY_ON_PREEMPT as the env fallback
    (orchestrators set the env; the flag wins when both are given)."""
    if cfg.retry_on_preempt > 0:
        return cfg.retry_on_preempt
    try:
        return max(0, int(os.environ.get("RDFIND_RETRY_ON_PREEMPT", "0")
                          or 0))
    except ValueError:
        return 0


def _run_supervised(cfg: Config) -> RunResult:
    """The in-driver preemption supervisor: a preempted attempt flushes its
    progress snapshots (already done by the raising site / signal handler),
    backs off with the fault ladder's jittered schedule, re-probes the
    visible device set, and re-enters the run — which resumes from the
    (possibly re-sharded) snapshots instead of starting over.  A zero budget
    keeps the historical behavior: Preempted propagates to the CLI's exit-75
    path for an external orchestrator to restart us."""
    from . import faults

    from . import watchdog

    budget = _retry_budget(cfg)
    attempt = 0
    while True:
        try:
            with tracer.span("run", cat=tracer.CAT_RUN,
                             strategy=cfg.traversal_strategy,
                             n_devices=cfg.n_devices, attempt=attempt):
                out = _run_profiled(cfg)
            if attempt:
                out.counters["supervisor-attempts"] = attempt
                metrics.struct_update(None, "elastic_resume",
                                      supervisor_attempts=attempt)
                # The recovery window is over: the re-entered attempt
                # finished, so tpu_watch stops reporting RECOVERING.
                tracer.set_status(recovering=False)
                if cfg.counter_level >= 1:
                    # The counter report already printed inside the attempt,
                    # before this counter existed.
                    print(f"supervisor-attempts: {attempt}", file=sys.stderr)
            watchdog.publish(None)
            return out
        except (faults.Preempted, faults.FallbackRequired) as e:
            attempt += 1
            if attempt > budget:
                raise
            # Belt and braces: the raising site flushes before Preempted
            # propagates, but a FallbackRequired that escaped the discover
            # entry point may not have.
            checkpoint.flush_all_progress()
            # A watchdog-converted wedge leaves its fire state + peer
            # marker behind; clear both so the re-entered attempt's first
            # collective is not insta-aborted, and stamp the heartbeat so
            # tpu_watch --status reports RECOVERING while we re-enter.
            watchdog.clear_fired()
            watchdog.clear_markers()
            tracer.set_status(recovering=True)
            tracer.heartbeat_now()
            metrics.counter_add(None, "preempt_supervisor_retries")
            metrics.struct_update(None, "elastic_resume",
                                  supervisor_attempts=attempt)
            delay_ms = faults._backoff_ms(attempt - 1)
            tracer.instant("preempt_retry", cat=tracer.CAT_RUN,
                           attempt=attempt, budget=budget,
                           backoff_ms=delay_ms, reason=str(e))
            print(f"rdfind: preempted ({e}); supervisor retry "
                  f"{attempt}/{budget} after {delay_ms} ms",
                  file=sys.stderr, flush=True)
            time.sleep(delay_ms / 1e3)
            # Re-probe the device set: a restart after real preemption can
            # come back with less capacity; the snapshots re-shard on load.
            import jax
            try:
                avail = len(jax.devices())
            except Exception:
                avail = cfg.n_devices
            if cfg.n_devices > avail > 0:
                print(f"rdfind: device set shrank to {avail}; resuming "
                      f"re-sharded", file=sys.stderr, flush=True)
                cfg = dataclasses.replace(cfg, n_devices=avail)


@contextlib.contextmanager
def _obs_session(cfg: Config):
    """Arm the obs layer for one driver run (span tracing + heartbeat when
    --trace/RDFIND_TRACE names a directory, Prometheus exposition when
    --metrics-file/RDFIND_METRICS_FILE names a file), and tear it down —
    exporting the merged Chrome trace on the primary host — no matter how
    the run ends.  The live console (--console-port/RDFIND_CONSOLE_PORT)
    arms here too: one per-host HTTP server for the run's duration, port 0
    binding an ephemeral port printed to stderr.  With no knob set this is
    a no-op and the run pays only the disabled-path checks."""
    trace_dir = cfg.trace_dir or os.environ.get("RDFIND_TRACE") or None
    metrics_file = (cfg.metrics_file
                    or os.environ.get("RDFIND_METRICS_FILE") or None)
    console_port = (cfg.console_port if cfg.console_port is not None
                    else console.env_port())
    obs_memory.reset()
    compiles.install()
    compiles.reset()  # one run, one compile record
    flightrec.configure()  # re-read RDFIND_FLIGHTREC at every run start
    flightrec.reset()  # one run, one ring (dumps are per-incident anyway)
    if metrics_file:
        metrics.set_export(metrics_file)
    if trace_dir:
        tracer.start(trace_dir)
    console_started = False
    if console_port is not None:
        bound = console.start(console_port, obs_dir=trace_dir)
        if bound is None:
            print(f"warning: run console could not bind port {console_port};"
                  f" continuing without it", file=sys.stderr)
        else:
            console_started = True
            print(f"rdfind: run console on http://{console.DEFAULT_HOST}:"
                  f"{bound}/ (/metrics /status /progress /datastats "
                  f"/flightrec)", file=sys.stderr, flush=True)
    try:
        yield
    finally:
        if console_started:
            console.stop()
        if metrics_file:
            try:
                metrics.flush_export()
            finally:
                metrics.set_export(None)
        if trace_dir:
            tracer.stop()
            if _is_primary():
                # Best-effort merge: on a shared filesystem this folds every
                # host's lane in; per-host dirs still get a loadable
                # single-lane trace (obs/report.py re-merges offline).
                try:
                    report.export_chrome_trace(trace_dir)
                except OSError as e:
                    print(f"warning: trace export failed ({e}); the raw "
                          f"event files remain in {trace_dir}",
                          file=sys.stderr)


def _run_profiled(cfg: Config) -> RunResult:
    if cfg.profile_dir:
        # Device-level observability the reference cannot offer (its tracing
        # stops at per-plan wall clocks, AbstractFlinkProgram.java:65-77):
        # one XLA profiler trace over the whole run — per-op device timings,
        # HLO, memory — viewable in TensorBoard / xprof.
        import jax

        with jax.profiler.trace(cfg.profile_dir):
            return _run(cfg)
    return _run(cfg)


def _run(cfg: Config) -> RunResult:
    phases = _Phases()
    counters: dict = {}
    stats: dict = {}

    if cfg.print_plan and _is_primary():
        import json as _json
        print(_json.dumps(describe_plan(cfg), indent=2))

    if cfg.delta_base:
        # Incremental discovery: the change batch replays against the
        # persisted base bundle host-side (runtime/delta.py); it emits
        # through the same _emit_sinks/_report as a full run.
        from . import delta
        return delta.run_delta(cfg, phases, counters, stats)
    tracer.set_status(mode="full")

    if cfg.sharded_ingest:
        if cfg.delta_state:
            print("note: --delta-state is not supported with "
                  "--sharded-ingest yet; no delta bundle written",
                  file=sys.stderr)
        return _run_sharded_ingest(cfg, phases, counters)

    # Native fused ingest (read+parse+intern in one C++ pass) whenever the
    # string-level preprocessing options that need raw tokens are off.
    use_native = (cfg.native_ingest and native.available()
                  and not cfg.asciify_triples and not cfg.prefix_paths
                  and not cfg.only_read
                  and reader.is_utf8(cfg.encoding))  # native parser is UTF-8-only

    ckpt = ingest_fp = discover_fp = progress = None
    if cfg.checkpoint_dir and not cfg.only_read:
        ckpt = checkpoint.CheckpointStore(cfg.checkpoint_dir)
        ingest_fp, discover_fp = _checkpoint_fps(cfg, use_native)
        # Mid-discover per-pass checkpoints (sharded runs): a preempted
        # discover resumes from its last committed pass, not from ingest.
        progress = checkpoint.ProgressStore(ckpt, discover_fp)

    ids = dictionary = None
    if ckpt is not None:
        stored = ckpt.load("ingest", ingest_fp)
        if stored is not None:
            ids, dictionary = phases.run(
                "resume-ingest", lambda: checkpoint.decode_ingest(stored))
            counters["input-triples"] = int(stored["input_triples"])
            if "distinct_triples" in stored:
                counters["distinct-triples"] = int(stored["distinct_triples"])
            counters["resumed-ingest"] = 1

    if ids is None:
        if use_native:
            paths, is_nq = _resolve_inputs(cfg)
            ingest_stats: dict = {}
            ids, dictionary = phases.run(
                "read+parse", lambda: native.ingest_files(
                    paths, tabs=cfg.tabs, expect_quad=is_nq,
                    stats=ingest_stats))
            if ingest_stats:
                metrics.struct_set(stats, "ingest", ingest_stats)
                _ingest_counters(counters, stats)
            counters["input-triples"] = ids.shape[0]
            phases.timings["intern"] = 0.0  # folded into the native pass
        else:
            raw = load_triples(cfg, phases, counters)
            if cfg.only_read:
                _report(cfg, counters, phases.timings)
                return RunResult(CindTable.empty(), None, None, counters,
                                 phases.timings)
            ids, dictionary = phases.run(
                "intern", lambda: intern_triples(np.asarray(raw, dtype=object)))
            del raw
        if cfg.distinct_triples:
            ids = phases.run("distinct", lambda: np.unique(ids, axis=0))
            counters["distinct-triples"] = ids.shape[0]
        if ckpt is not None:
            def save_ingest():
                arrays = checkpoint.encode_ingest(ids, dictionary)
                # Counter state rides along so resumed runs report identically.
                arrays["input_triples"] = np.int64(counters["input-triples"])
                if "distinct-triples" in counters:
                    arrays["distinct_triples"] = np.int64(
                        counters["distinct-triples"])
                _safe_save(ckpt, "ingest", ingest_fp, arrays, counters)
            phases.run("checkpoint-ingest", save_ingest)
    counters["distinct-values"] = len(dictionary)

    if cfg.create_join_histogram:
        # Join-line size histogram (RDFind.scala:448-452): an extra pass over
        # the join, exactly like the reference's extra map/groupBy/collect
        # job.  Runs before the --do-only-join return, as in the reference.
        def histogram():
            hist = _join_histogram(ids, cfg.projections)
            if _is_primary():
                for size, times in hist:
                    print(f"Join size {size} encountered {times}x")
        phases.run("join-histogram", histogram)

    if cfg.only_join:
        _report(cfg, counters, phases.timings)
        return RunResult(CindTable.empty(), dictionary, ids, counters, phases.timings)

    if cfg.find_only_fcs >= 1:
        # Stop after the frequent-condition plan (RDFind.scala:298-306):
        # level >= 1 emits the single-condition filters and returns; level >= 2
        # additionally emits the double-condition filters (+ ARs here, which
        # ride the binary counts).  Device segment-count ops, same code as the
        # real pipeline's frequency prefilter.
        def mine_fcs():
            from ..ops import frequency as freq_ops
            n_unary, n_binary = freq_ops.count_frequent_conditions(
                ids, cfg.min_support, include_binary=cfg.find_only_fcs >= 2)
            counters["frequent-single-conditions"] = n_unary
            if n_binary is not None:
                counters["frequent-double-conditions"] = n_binary
                if cfg.use_association_rules and cfg.use_frequent_item_set:
                    rules = freq_ops.mine_association_rules(
                        ids, cfg.min_support)
                    counters["association-rules"] = len(rules[0])
        phases.run("frequent-conditions", mine_fcs)
        _report(cfg, counters, phases.timings)
        return RunResult(CindTable.empty(), dictionary, ids, counters,
                         phases.timings)

    use_ars = cfg.use_association_rules and cfg.use_frequent_item_set
    if cfg.use_association_rules and not cfg.use_frequent_item_set:
        # Like the reference: ARs are mined from the frequent-item sets, so without
        # --use-fis the AR broadcast is empty (RDFind.scala:290-296).
        print("note: --use-ars has no effect without --use-fis "
              "(association rules are mined from the frequent-item sets)",
              file=sys.stderr)

    def discover():
        if cfg.n_devices > 1:
            # Distributed strategy dispatch, all four ids native on the mesh
            # (the reference's distributed-by-construction contract,
            # plan/TraversalStrategy.scala:28-33): 0 = sharded AllAtOnce,
            # 1 = sharded SmallToLarge (default), 2 = sharded Approximate
            # AllAtOnce, 3 = sharded LateBB (raw output drops 1/x-implied 2/x
            # CINDs, like its single-device form).
            mesh = make_mesh(cfg.n_devices)
            strategy = cfg.traversal_strategy
            skew = _skew_from_cfg(cfg)
            if cfg.merge_window_size > 0:
                print("note: --merge-window-size only affects the "
                      "single-device chunked backend; the sharded run sizes "
                      "its merge buffers from measured loads", file=sys.stderr)
            if cfg.explicit_threshold != -1:
                print("note: --explicit-threshold (spectral half-approximate "
                      "1/1) configures the single-device chunked backend "
                      "only; sharded runs bound 1/1 memory via planned "
                      "capacities + dep-slice streaming passes "
                      "(RDFIND_PAIR_ROW_BUDGET), and their distributed "
                      "two-round count-min cut is the env knob "
                      "RDFIND_SHARDED_HALF_APPROX=1 (bit-identical output; "
                      "see the README design note)", file=sys.stderr)
            if cfg.balanced_11:
                print("note: --balanced-overlap-candidates is single-device "
                      "only; the sharded 1/1 already splits emission across "
                      "devices (giant-line slicing), so rotation ownership "
                      "adds nothing there", file=sys.stderr)
            if strategy == 2:
                return sharded.discover_sharded_approx(
                    ids, cfg.min_support, mesh=mesh, skew=skew, combine=cfg.combinable_join,
                    progress=progress, projections=cfg.projections,
                    use_fis=cfg.use_frequent_item_set, use_ars=use_ars,
                    clean_implied=cfg.clean_implied, stats=stats)
            if strategy == 3:
                return sharded.discover_sharded_late_bb(
                    ids, cfg.min_support, mesh=mesh, skew=skew, combine=cfg.combinable_join,
                    progress=progress, projections=cfg.projections,
                    use_fis=cfg.use_frequent_item_set, use_ars=use_ars,
                    clean_implied=cfg.clean_implied, stats=stats)
            if strategy == 1:
                return sharded.discover_sharded_s2l(
                    ids, cfg.min_support, mesh=mesh, skew=skew, combine=cfg.combinable_join,
                    progress=progress, projections=cfg.projections,
                    use_fis=cfg.use_frequent_item_set, use_ars=use_ars,
                    clean_implied=cfg.clean_implied, stats=stats)
            if strategy != 0:
                raise ValueError(f"unknown traversal strategy {strategy}")
            return sharded.discover_sharded(
                ids, cfg.min_support, mesh=mesh, skew=skew, combine=cfg.combinable_join,
                progress=progress, projections=cfg.projections,
                use_fis=cfg.use_frequent_item_set, use_ars=use_ars,
                clean_implied=cfg.clean_implied, stats=stats)
        try:
            skew_nondefault = _skew_from_cfg(cfg) != sharded.DEFAULT_SKEW
            if skew_nondefault or not cfg.combinable_join:
                print("note: --rebalance-*/--no-combinable-join only affect "
                      "sharded runs (--dop > 1)", file=sys.stderr)
        except ValueError as e:
            # Invalid values never reach the skew engine on a single device,
            # but a --dop > 1 rerun would reject them — say so.
            print(f"note: invalid rebalance settings ignored on this "
                  f"single-device run; a sharded run (--dop > 1) would "
                  f"reject them ({e})", file=sys.stderr)
        # Strategy dispatch (TraversalStrategy registry, RDFind.scala:50-56).
        strategy = STRATEGIES.get(cfg.traversal_strategy)
        if strategy is None:
            raise ValueError(f"unknown traversal strategy {cfg.traversal_strategy}")
        kwargs = {}
        if cfg.explicit_threshold != -1:
            # The half-approximate 1/1 round belongs to the default strategy
            # (reference gates it on this same flag).
            if not _half_approx_active(cfg):
                print("note: --explicit-threshold only affects the "
                      "small-to-large strategy (1)", file=sys.stderr)
            else:
                kwargs = dict(explicit_threshold=cfg.explicit_threshold,
                              sbf_bits=cfg.sbf_bits)
        if cfg.balanced_11:
            if cfg.traversal_strategy != 1:
                print("note: --balanced-overlap-candidates only affects the "
                      "small-to-large strategy (1)", file=sys.stderr)
            else:
                kwargs["balanced_11"] = True
        if cfg.merge_window_size > 0:
            # The reference's --merge-window-size caps the k-way merge window
            # (BulkMergeDependencies.scala:96-104); here it caps the pair
            # budget of one chunk in the chunked backend.
            kwargs["pair_chunk_budget"] = cfg.merge_window_size
        return strategy(
            ids, cfg.min_support, projections=cfg.projections,
            use_frequent_condition_filter=cfg.use_frequent_item_set,
            use_association_rules=use_ars,
            clean_implied=cfg.clean_implied, stats=stats, **kwargs)

    table = None
    if ckpt is not None:
        stored = ckpt.load("discover", discover_fp)
        if stored is not None:
            table = phases.run("resume-discover",
                               lambda: checkpoint.decode_cinds(stored))
            metrics.restore(stats, checkpoint.decode_stats(stored))
            counters["resumed-discover"] = 1
    if table is None:
        table = phases.run("discover", discover)
        if ckpt is not None:
            def save_discover():
                arrays = checkpoint.encode_cinds(table)
                arrays.update(checkpoint.encode_stats(stats))
                _safe_save(ckpt, "discover", discover_fp, arrays, counters)
                progress.cleanup()  # per-pass snapshots are now superseded
            phases.run("checkpoint-discover", save_discover)
    counters["cind-counter"] = len(table)
    base_meta: dict = {}
    if cfg.delta_state and _is_primary():
        # Persist the base bundle (generation 0) the incremental runs load.
        from . import delta
        base_meta = phases.run("delta-state", lambda: delta.write_base_bundle(
            cfg, ids, dictionary, table, stats, phases.timings)) or {}
    if _is_primary() and (cfg.delta_state or serving.env_index_dir()):
        # The servable artifact: generation-0 mmap index next to the bundle
        # (and/or into RDFIND_SERVE_INDEX) for runtime/serving readers.  The
        # bundle's commit stamp + batch identity ride into the index meta so
        # the serving freshness plane measures gen 0 the same way as gen N
        # (None values are stripped; created_unix backstops the stamp).
        phases.run("serve-index", lambda: serving.emit_index(
            [cfg.delta_state] if cfg.delta_state else [],
            dictionary, table, generation=0, base_output_digest=None,
            strategy=cfg.traversal_strategy, min_support=cfg.min_support,
            stats=stats,
            extra={"bundle_commit_unix": base_meta.get("commit_unix"),
                   "batch": base_meta.get("batch")}))
    counters.update({f"stat-{k}": v for k, v in stats.items()})
    _emit_sinks(cfg, phases, counters, table, dictionary, stats, ids)

    _report(cfg, counters, phases.timings)
    return RunResult(table, dictionary, ids, counters, phases.timings)


def _ingest_counters(counters: dict, stats: dict) -> None:
    """Headline ingest telemetry -> counters (so -c reports it even on the
    only-read/only-join probes, which return before the sink stage)."""
    ing = stats.get("ingest")
    if not ing:
        return
    for k in ("n_threads", "n_units", "triples_per_sec", "bytes_per_sec",
              "queue_stalls"):
        if k in ing:
            counters[f"ingest-{k.replace('_', '-')}"] = ing[k]


def _emit_sinks(cfg: Config, phases: _Phases, counters: dict, table,
                dictionary, stats: dict, ids) -> None:
    """Debug reports + every result sink; shared by the replicated and the
    sharded-ingest paths so they can never diverge.  All stats rendering
    goes through the ONE obs formatter (obs/report.format_debug_lines), so
    the driver, bench.py and the tests share key names by construction."""
    if cfg.debug_level >= 1 and _is_primary():
        for line in report.format_debug_lines(stats):
            print(line, file=sys.stderr)
    if cfg.debug_level >= 1 and len(table) and _is_primary():
        # Per-family CIND counts (TraversalStrategy.scala:101-107).
        fams = table.family_counts()
        print("CIND families: " + ", ".join(
            f"{k[0]}/{k[1]}: {v}" for k, v in fams.items()), file=sys.stderr)
        counters.update({f"cinds-{k}": v for k, v in fams.items()})

    if cfg.debug_level >= 2 and len(table):
        # DEBUG_LEVEL_SANITY: trivial CINDs in the output indicate a pipeline
        # bug (the reference's check, RDFind.scala:497-504).
        n_trivial = int(np.count_nonzero(_trivial_cind_mask(table)))
        counters["sanity-trivial-cinds"] = n_trivial
        if n_trivial:
            print(f"SANITY VIOLATION: {n_trivial} trivial CINDs in output",
                  file=sys.stderr)

    if cfg.ar_output_file and not cfg.use_frequent_item_set:
        # Reference parity: without --use-fis there are no frequent-item sets to
        # mine rules from (RDFind.scala:290-296) -- write nothing.
        print("note: --ar-output requires --use-fis; no rules written",
              file=sys.stderr)
    if cfg.ar_output_file and cfg.use_frequent_item_set and _is_primary():
        def write_ars():
            mined = stats.get("association_rules")
            if mined is None:
                from ..ops import frequency as freq_ops
                mined = freq_ops.mine_association_rules(ids, cfg.min_support)
                # (ids is always present here: the sharded-ingest path
                # pre-mines rules into stats before _emit_sinks.)
            ants, cons, avs, cvs, sups = mined
            counters["association-rules"] = len(ants)
            from .. import conditions as cc
            with open(cfg.ar_output_file, "w") as f:
                for i in range(len(ants)):
                    # AssociationRule.toString format (data/AssociationRule.scala).
                    ant = cc.pretty(int(ants[i]), dictionary.value(int(avs[i])))
                    con = cc.pretty(int(cons[i]), dictionary.value(int(cvs[i])))
                    f.write(f"{ant} -> {con} (support={int(sups[i])},"
                            f"confidence=100.00%)\n")
        phases.run("write-ar-output", write_ars)

    if cfg.output_file and _is_primary():
        def write():
            cinds = table.decoded(dictionary)
            with open(cfg.output_file, "w") as f:
                for c in sorted(cinds, key=lambda c: c.pretty()):
                    f.write(c.pretty() + "\n")
        phases.run("write-output", write)

    if cfg.collector and _is_primary():
        # Remote result channel (the reference's RMI collector,
        # RemoteCollectorUtils.java:38-99, as TCP JSON lines).  A dead
        # collector must not destroy an otherwise-complete run: the results
        # are already computed (and possibly written to --output).
        def send_remote():
            from .collector import RemoteSink
            try:
                # ValueError here == malformed host:port.  Only the sink
                # construction is shielded so a decoding bug in the results
                # themselves still fails loudly instead of masquerading as a
                # networking warning.
                sink = RemoteSink(cfg.collector)
            except (OSError, ValueError) as e:
                counters["collector-errors"] = 1
                print(f"warning: remote collector {cfg.collector} "
                      f"unreachable ({e}); results NOT streamed",
                      file=sys.stderr)
                return
            try:
                with sink:
                    for c in table.decoded(dictionary):
                        sink.send_cind(c.pretty())
            except OSError as e:  # stream dropped mid-send
                counters["collector-errors"] = 1
                print(f"warning: remote collector {cfg.collector} dropped "
                      f"the stream ({e}); results may be truncated",
                      file=sys.stderr)
        phases.run("collect-remote", send_remote)
    if (cfg.collect_result or cfg.debug_level >= 3) and _is_primary():
        for c in table.decoded(dictionary):
            print(c.pretty())

    if integrity.enabled() and _is_primary():
        # Integrity plane: fold the counters into stats["integrity"] and
        # emit the run certificate — input signature -> per-stage digests ->
        # output digest, provenance-keyed like BENCH_HISTORY rows — when a
        # destination (RDFIND_CERT or a live trace dir) is configured.
        summary = integrity.summarize(stats)
        stages = dict(stats.get("integrity_stages") or {})
        stages.setdefault("output", integrity.digest_hex(
            *integrity.digest_table(table)))
        counters["output-digest"] = stages["output"]
        dest = integrity.certificate_path()
        if dest:
            def write_cert():
                from ..obs import sentinel as obs_sentinel
                paths, _ = _resolve_inputs(cfg)
                if cfg.delete_paths:
                    paths = list(paths) + reader.resolve_path_patterns(
                        cfg.delete_paths)
                extra = {"summary": summary, "n_cinds": len(table)}
                delta_info = stats.get("delta") or {}
                if delta_info.get("base_output_digest"):
                    # Chain the incremental run onto its base: a verifier
                    # walks base_output_digest links back to generation 0.
                    extra["base_output_digest"] = \
                        delta_info["base_output_digest"]
                    extra["generation"] = delta_info.get("new_generation")
                cert = integrity.run_certificate(
                    input_signature=checkpoint.input_signature(paths),
                    stages=stages, output_digest=stages["output"],
                    provenance=obs_sentinel.provenance(),
                    extra=extra)
                integrity.write_certificate(dest, cert)
            phases.run("write-certificate", write_cert)

    # Whether the dictionary stayed the native ingest's value blob, and how
    # many values the sinks decoded from it one at a time.
    lazy = isinstance(dictionary, BlobDictionary)
    counters["dictionary-lazy"] = int(lazy)
    counters["dictionary-decoded"] = dictionary.decoded if lazy else 0


def _report(cfg: Config, counters: dict, timings: dict) -> None:
    """Post-run statistics, incl. the CSV line (AbstractFlinkProgram.java:149-182)."""
    counters.update(compiles.counters())
    try:
        import resource
        counters["peak-rss-mb"] = (
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss // 1024)
    except Exception:
        pass
    if not _is_primary():
        if cfg.counter_level >= 1 and "peak-rss-mb" in counters:
            # Worker hosts report their own memory high-water (the scale
            # artifact needs every host's bound, not just host 0's).
            print(f"peak-rss-mb: {counters['peak-rss-mb']}", file=sys.stderr)
        return
    if cfg.counter_level >= 1:
        for line in report.format_counter_lines(counters):
            print(line, file=sys.stderr)
    if cfg.debug_level >= 1 or cfg.counter_level >= 1:
        for line in report.format_timing_lines(timings, counters):
            print(line, file=sys.stderr)


# Strategy ids follow the reference (RDFind.scala:50-56): 0 = all-at-once,
# 1 = small-to-large (default), 2 = approximate all-at-once, 3 = late-BB.
STRATEGIES = {
    0: allatonce.discover,
    1: small_to_large.discover,
    2: approximate.discover,
    3: late_bb.discover,
}
