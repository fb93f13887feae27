"""The RDFind CLI: discover CINDs in RDF datasets on TPU.

Flag surface mirrors the reference's Parameters (programs/RDFind.scala:639-721).
Flags whose machinery is built-in or obsolete here are accepted for compatibility
and noted in help:
  --find-frequent-captures  exact capture-support pruning is always on;
  --hash-dictionary/--apply-hash/--hash-*  subsumed by exact string interning;
  --no-bulk-merge/--no-combinable-join  merge is always combiner-style.

Real behavior flags beyond the basics:
  --explicit-threshold/--sbf-bytes select and tune the half-approximate 1/1
      overlap round of the default strategy (models/small_to_large.py), as in
      the reference (SmallToLargeTraversalStrategy.scala:322-326);
  --balanced-overlap-candidates halves the 1/1 emission via rotation ownership
      (the reference's ring-distance relation, AbstractExtractBalancedUnary
      UnaryOverlapCandidates.scala:64-120).
"""

from __future__ import annotations

import argparse
import os
import sys


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="rdfind-tpu",
        description="Discover Conditional Inclusion Dependencies in RDF datasets "
                    "(TPU-native rebuild of stratosphere/rdfind).")
    p.add_argument("inputs", nargs="*",
                   help="input .nt/.nq[.gz] files or globs (for --delta "
                        "runs these are the INSERT batch; may be empty for "
                        "a delete-only batch)")
    p.add_argument("--delta", default=None, metavar="BASE_DIR",
                   dest="delta_base",
                   help="incremental run: apply the insert (positional) and "
                        "--deletes batches against the base bundle in "
                        "BASE_DIR (written by a full run with "
                        "--delta-state); output is bit-identical to a "
                        "from-scratch run on the updated dataset, and the "
                        "bundle advances one generation in place")
    p.add_argument("--delta-state", default=None, metavar="DIR",
                   help="full run: persist the delta base bundle (interned "
                        "dictionary + per-bucket join-line rows + per-pass "
                        "digests + the definitional CIND set) into DIR for "
                        "later --delta runs")
    p.add_argument("--deletes", nargs="*", default=[], metavar="FILE",
                   help="delete batch files for a --delta run (same formats "
                        "as the inputs; each line retracts one matching "
                        "triple)")
    p.add_argument("--prefixes", nargs="*", default=[],
                   help="nt-prefix files for URL shortening")
    p.add_argument("--support", type=int, default=10,
                   help="minimum support for CINDs (default 10)")
    p.add_argument("--traversal-strategy", type=int, default=1,
                   help="0=all-at-once 1=small-to-large 2=approx 3=late-bb")
    p.add_argument("--projection", default="spo",
                   help="fields to project captures on (subset of 'spo')")
    p.add_argument("--use-fis", action="store_true",
                   help="mine + use frequent item sets for pruning")
    p.add_argument("--use-ars", action="store_true",
                   help="mine + use association rules")
    p.add_argument("--clean-implied", action="store_true",
                   help="remove implied CINDs (minimality cleanup)")
    p.add_argument("--distinct-triples", action="store_true")
    p.add_argument("--asciify-triples", action="store_true")
    p.add_argument("--tabs", action="store_true", help="tab-separated input")
    p.add_argument("--only-read", action="store_true")
    p.add_argument("--do-only-join", action="store_true", dest="only_join")
    p.add_argument("--output", default=None, help="CIND output file")
    p.add_argument("--ar-output", default=None, help="association-rule output file")
    p.add_argument("--collect-result", action="store_true",
                   help="print CINDs to stdout")
    p.add_argument("--collector", default=None, metavar="HOST:PORT",
                   help="stream CINDs to a remote collector (JSON lines over "
                        "TCP; the reference's RMI result channel)")
    p.add_argument("--debug-level", type=int, default=0,
                   help="1: phase timings; 2: + sanity checks (trivial-CIND "
                        "count); 3: + print every CIND")
    p.add_argument("--print-plan", action="store_true",
                   help="dump the logical plan as JSON before executing")
    p.add_argument("--profile-dir", default=None, metavar="DIR",
                   help="write an XLA profiler trace of the run (per-op "
                        "device timings; open with TensorBoard)")
    p.add_argument("--trace", default=None, metavar="DIR", dest="trace_dir",
                   help="obs: record host span traces (run/stage/pass/"
                        "dispatch/pull/exchange/checkpoint) + a heartbeat "
                        "file into DIR; a merged Chrome-trace JSON "
                        "(DIR/trace.json, per-host lanes) is written at run "
                        "end — open in Perfetto (ui.perfetto.dev).  Pairs "
                        "with --profile-dir: host spans emit matching "
                        "jax.profiler.TraceAnnotations")
    p.add_argument("--metrics-file", default=None, metavar="FILE",
                   help="obs: write the metrics registry as Prometheus text "
                        "exposition to FILE (atomically refreshed at every "
                        "stage boundary and at run end)")
    p.add_argument("--console-port", type=int, default=None, metavar="PORT",
                   help="obs: serve the live run console on this port "
                        "(loopback HTTP: /metrics /status /progress "
                        "/datastats /flightrec; 0 binds an ephemeral port, "
                        "printed to stderr; RDFIND_CONSOLE_PORT is the env "
                        "form)")
    p.add_argument("--counters", type=int, default=0, dest="counter_level")
    p.add_argument("--dop", type=int, default=1,
                   help="degree of parallelism = number of devices in the mesh")
    p.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                   help="multi-host run: process 0's coordinator address "
                        "(every host runs the same command with its own "
                        "--host-index)")
    p.add_argument("--num-hosts", type=int, default=1,
                   help="multi-host run: total number of host processes")
    p.add_argument("--host-index", type=int, default=0,
                   help="multi-host run: this process's index in [0, "
                        "num-hosts)")
    # Accepted-for-compatibility (behavior built-in or subsumed; a note is
    # printed when set so no flag is a *silent* no-op):
    for flag in ("--find-frequent-captures", "--no-bulk-merge",
                 "--rebalance-join", "--apply-hash",
                 "--hash-dictionary", "--only-read-compat",
                 "--any-binary-captures"):
        p.add_argument(flag, action="store_true", help=argparse.SUPPRESS)
    p.add_argument("--no-combinable-join", action="store_true",
                   help="plan ablation: ship raw join candidates instead of "
                        "combiner-deduped ones (sharded runs; same output)")
    p.add_argument("--balanced-overlap-candidates", action="store_true",
                   dest="balanced_11",
                   help="halve the 1/1 overlap emission via pair ownership "
                        "(strategy 1, single-device chunked backend; sharded "
                        "runs split emission via giant-line slicing instead)")
    p.add_argument("--rebalance-strategy", type=int, default=1,
                   choices=(1, 2),
                   help="split-line dependent ownership: 1 = hash-slice, "
                        "2 = contiguous range-slice (sharded runs)")
    p.add_argument("--rebalance-max-load", type=float, default=10000.0 * 10000,
                   help="absolute quadratic load above which a join line "
                        "always splits across devices (sharded runs)")
    p.add_argument("--merge-window-size", type=int, default=-1,
                   help="pair-merge window: max pairs materialized per chunk "
                        "in the chunked backend (-1 = auto)")
    p.add_argument("--create-join-histogram", action="store_true",
                   help="print a join-line size histogram "
                        "('Join size N encountered Mx')")
    p.add_argument("--find-only-fcs", type=int, default=0,
                   help="1: stop after frequent-condition mining, reporting "
                        "unary counts; 2: also mine binary (double) "
                        "conditions and association rules")
    for flag, dv in (("--rebalance-split", 1), ("--hash-bytes", -1),
                     ("--frequent-condition-strategy", 0)):
        p.add_argument(flag, type=int, default=dv, help=argparse.SUPPRESS)
    p.add_argument("--explicit-threshold", type=int, default=-1,
                   help="half-approximate 1/1 round: max exact per-dependent "
                        "counters (strategy 1, single-device chunked backend "
                        "only; -1 = exact overlaps).  Sharded runs bound 1/1 "
                        "memory via planned capacities + dep-slice streaming "
                        "(RDFIND_PAIR_ROW_BUDGET); their distributed "
                        "two-round count-min cut is "
                        "RDFIND_SHARDED_HALF_APPROX=1 (bit-identical "
                        "output)")
    p.add_argument("--sbf-bytes", type=int, default=-1, dest="sbf_bits",
                   help="bits per spectral (count-min) counter for the "
                        "half-approximate round (-1 = sized to support)")
    p.add_argument("--rebalance-threshold", type=float, default=1.0,
                   help="scales the average-load factor above which a join "
                        "line splits (sharded runs; default 1.0)")
    p.add_argument("--hash-function", default="MD5", help=argparse.SUPPRESS)
    p.add_argument("--encoding", default="utf-8",
                   help="input charset; 'auto' sniffs a BOM per file "
                        "(default utf-8)")
    p.add_argument("--file-filter", default=None,
                   help="regex on input-file basenames (the reference's "
                        "file-filtered directory scan)")
    p.add_argument("--sharded-ingest", action="store_true",
                   help="each host parses only its file subset and donates "
                        "rows to its own devices (multi-host; no host holds "
                        "the full triple table; all four traversal "
                        "strategies run on the presharded arrays)")
    p.add_argument("--interning", choices=("auto", "partitioned",
                                           "replicated"), default="auto",
                   help="sharded-ingest dictionary mode: partitioned = each "
                        "host stores only its value-hash range (multi-host "
                        "default; decode is a collective), replicated = "
                        "every host holds the union; auto picks partitioned "
                        "when multi-host")
    p.add_argument("--no-native-ingest", action="store_true",
                   help="force the pure-Python ingest path")
    p.add_argument("--checkpoint-dir", default=None,
                   help="directory for stage-boundary AND mid-discover "
                        "per-pass checkpoints; re-runs with unchanged "
                        "inputs/flags resume from them (a preempted sharded "
                        "discover replays only its unfinished passes).  "
                        "Fault-tolerance knobs ride the environment: "
                        "RDFIND_STRICT=1 fail-fast, RDFIND_FAULTS=... "
                        "deterministic fault injection (see README, 'Fault "
                        "tolerance & resume')")
    p.add_argument("--retry-on-preempt", type=int, default=0, metavar="N",
                   help="in-driver preemption supervisor: on Preempted (or "
                        "an escaped fallback request), back off and re-enter "
                        "the run up to N times, resuming from the "
                        "mesh-portable progress snapshots — even on a "
                        "shrunken device set.  0 (default) keeps the "
                        "historical exit-75 behavior for an external "
                        "orchestrator; RDFIND_RETRY_ON_PREEMPT is the env "
                        "form (the flag wins)")
    return p


def main(argv=None, on_result=None) -> int:
    """Run the CLI; returns the exit code.  `on_result`, when given, is
    called with the driver's RunResult (in-process callers such as
    chip_smoke.py read the CIND table and phase timings from it)."""
    parser = build_parser()
    args = parser.parse_args(argv)
    if not args.projection or not set(args.projection) <= set("spo"):
        # Otherwise typo'd fields are silently dropped (zero or partial
        # output) — a long-standing footgun.
        parser.error(f"--projection {args.projection!r} must be a non-empty "
                     f"subset of 'spo'")
    if args.deletes and not args.delta_base:
        parser.error("--deletes only applies to incremental runs; pass "
                     "--delta BASE_DIR")
    if not args.inputs and not (args.delta_base and args.deletes):
        parser.error("no input files (positional inputs may only be empty "
                     "for a delete-only --delta run with --deletes)")
    if args.delta_base:
        for flag, bad in (("--sharded-ingest", args.sharded_ingest),
                          ("--only-read", args.only_read),
                          ("--do-only-join", args.only_join),
                          ("--find-only-fcs", args.find_only_fcs),
                          ("--checkpoint-dir", args.checkpoint_dir)):
            if bad:
                parser.error(f"{flag} is not supported with --delta "
                             f"(the delta engine replays host-side against "
                             f"the base bundle)")
        if args.delta_state:
            print("note: --delta-state is ignored with --delta (the run "
                  "advances the base bundle in place)", file=sys.stderr)
            args.delta_state = None
    if args.dop > 1 and args.coordinator is None and \
            "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        # Allow --dop on CPU-only hosts (the minicluster analog): request fake
        # host devices before the JAX backend initializes.  No effect if a real
        # multi-chip platform provides enough devices.
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   f" --xla_force_host_platform_device_count={args.dop}"
                                   ).strip()
    if args.coordinator is None and (args.num_hosts != 1
                                     or args.host_index != 0):
        parser.error("--num-hosts/--host-index require --coordinator "
                     "(without it this would run a full independent "
                     "single-host job)")
    from ..runtime import compile_cache

    compile_cache.place()
    if args.coordinator:
        # Join the multi-host runtime before anything touches the backend;
        # the mesh then spans every host's devices and --dop defaults to all
        # of them.
        # ensure_distributed = initialize_multihost + bounded retry with
        # jittered backoff around the rendezvous (gloo wedges on loaded
        # boxes) + the collective watchdog's deadman.
        from ..parallel.mesh import ensure_distributed
        ensure_distributed(args.coordinator, args.num_hosts, args.host_index)
        import jax
        if args.dop == 1:
            args.dop = jax.device_count()
        elif args.dop != jax.device_count():
            # A mesh over a device subset would exclude whole processes and
            # deadlock the collectives.
            parser.error(
                f"--dop {args.dop} does not span the multi-host runtime "
                f"({jax.device_count()} devices across {args.num_hosts} "
                f"hosts); omit --dop or pass the global device count")
    from ..runtime import driver  # deferred: must follow XLA_FLAGS setup

    cfg = driver.Config(
        input_paths=args.inputs,
        prefix_paths=args.prefixes,
        min_support=args.support,
        traversal_strategy=args.traversal_strategy,
        projections=args.projection,
        use_frequent_item_set=args.use_fis,
        use_association_rules=args.use_ars,
        clean_implied=args.clean_implied,
        distinct_triples=args.distinct_triples,
        asciify_triples=args.asciify_triples,
        tabs=args.tabs,
        only_read=args.only_read,
        only_join=args.only_join,
        output_file=args.output,
        ar_output_file=args.ar_output,
        collect_result=args.collect_result,
        debug_level=args.debug_level,
        counter_level=args.counter_level,
        n_devices=args.dop,
        retry_on_preempt=args.retry_on_preempt,
        native_ingest=not args.no_native_ingest,
        checkpoint_dir=args.checkpoint_dir,
        explicit_threshold=args.explicit_threshold,
        sbf_bits=args.sbf_bits,
        balanced_11=args.balanced_11,
        print_plan=args.print_plan,
        profile_dir=args.profile_dir,
        encoding=args.encoding,
        file_filter=args.file_filter,
        rebalance_strategy=args.rebalance_strategy,
        rebalance_threshold=args.rebalance_threshold,
        rebalance_max_load=args.rebalance_max_load,
        merge_window_size=args.merge_window_size,
        combinable_join=not args.no_combinable_join,
        collector=args.collector,
        find_only_fcs=args.find_only_fcs,
        create_join_histogram=args.create_join_histogram,
        sharded_ingest=args.sharded_ingest,
        interning=args.interning,
        trace_dir=args.trace_dir,
        metrics_file=args.metrics_file,
        console_port=args.console_port,
        delta_base=args.delta_base,
        delta_state=args.delta_state,
        delete_paths=args.deletes,
    )
    # Un-silence the remaining compatibility no-ops (the reference's
    # JVM-dataflow levers that the TPU design subsumes).
    for name, why in (
            ("no_bulk_merge", "merging is always windowed segment-sum here"),
            ("frequent_condition_strategy",
             "frequency uses exact segment counts; both reference strategies "
             "produce identical verdicts"),
            ("rebalance_split",
             "split lines always fan out to every device in the mesh"),
            ("hash_bytes", "hash dictionary subsumed by exact interning"),
            ("apply_hash", "hash dictionary subsumed by exact interning"),
            ("hash_dictionary", "hash dictionary subsumed by exact interning"),
            ("hash_function", "hash dictionary subsumed by exact interning"),
            ("find_frequent_captures",
             "exact capture-support pruning is always on"),
            ("rebalance_join",
             "the skew engine is always on for sharded runs; tune it with "
             "--rebalance-threshold/--rebalance-max-load"),
            ("only_read_compat", "use --only-read"),
            ("any_binary_captures",
             "binary condition frequencies are computed exactly in the same "
             "pass as unary ones; there is no pre-pass to skip")):
        v = getattr(args, name, None)
        default = {"rebalance_split": 1, "frequent_condition_strategy": 0,
                   "hash_bytes": -1, "hash_function": "MD5"}.get(name, False)
        if v not in (default, None):
            print(f"note: --{name.replace('_', '-')} has no effect ({why})",
                  file=sys.stderr)
    from ..runtime import faults

    from ..runtime import delta as delta_rt

    try:
        result = driver.run(cfg)
        if on_result is not None:
            on_result(result)
    except delta_rt.DeltaBaseError as e:
        # Clean miss, never a wrong incremental answer: name the failure
        # and tell the caller how to rebuild.
        print(f"rdfind: delta base unusable ({e}); re-run a full build "
              f"with --delta-state to rebuild the bundle", file=sys.stderr)
        return 66  # EX_NOINPUT: the base bundle cannot serve this run
    except faults.Preempted as e:
        # Injected (or test-driven) preemption: in-flight progress was
        # flushed before the raise; the same command resumes the run.
        print(f"rdfind: preempted ({e}); re-run with the same "
              f"--checkpoint-dir to resume from the last committed pass "
              f"(or pass --retry-on-preempt N to let the driver retry "
              f"in-process)", file=sys.stderr)
        return 75  # EX_TEMPFAIL: transient, retry the same invocation
    if not (cfg.output_file or cfg.collect_result):
        print(f"Detected {len(result.table)} CINDs.")
    return 0


if __name__ == "__main__":
    sys.exit(main())
