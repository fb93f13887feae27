"""Multi-device discovery: AllAtOnce and SmallToLarge sharded over a 1-D mesh.

The reference scales by hash-partitioning every operator over Flink task managers
(SURVEY.md §2h); here the same dataflow runs as jitted shard_map programs over a
jax.sharding.Mesh with bucket exchanges riding ICI/DCN:

  triples (data-parallel shards)
    -> [optional] distributed frequency filter     [count exchanges, see below]
    -> emit join candidates, local dedupe          [device-local]
    -> exchange A: route by hash(join value)       [all_to_all]
    -> join-line dedupe at the value owner         [device-local]
    -> exchange B: route (capture, 1) by hash(capture); owner counts support
    -> skew split: oversized join lines -> all devices, sliced  [all_gather]
    -> pair emission + local pair counts           [device-local, quadratic part]
    -> exchange C: route pair partials by hash(dependent capture)
    -> merge counts, sorted-join against support, CIND test   [device-local]

Stats-driven capacity planning (the reference's load-aware placement,
LoadBasedPartitioner.scala:13-52 + AssignJoinLineRebalancing.scala:28-64 by
*measured* load): before any exchange runs, a cheap planning program measures the
actual per-(source, destination) bucket loads — distinct-key histograms for the
count exchanges, the join-value histogram for exchange A — and the line-building
program measures the capture-hash histogram (exchange B), the post-split pair
totals, and the giant-row counts.  Capacities are set to the measured maxima plus
headroom instead of the old "everything lands on one device" worst cases, so
per-device buffers scale O(N/D + skew), not O(N).  Overflow is still psum-counted
at every site and the host retries with grown capacities — planning is the fast
path, retry is the safety net.

Distributed frequency filter (the reference's broadcast Bloom-filter pruning,
FrequentConditionPlanner.scala:201-283 + CreateJoinPartners.scala:48-76, exact
here): per-row global condition counts come from exchange.global_row_counts —
local distinct keys carry combiner-summed multiplicities to their hash owner and
the sums ride the reply collective back to the asking rows.  Association-rule
verdicts are then pure per-row comparisons (binary count == unary count), so AR
suppression at emission needs no rule broadcast at all.

Sharded SmallToLarge (the reference's *default* strategy, SmallToLargeTraversal
Strategy.scala:38-171): the host drives the exact same lattice logic as the
single-device strategy (small_to_large._run_lattice — candidate generation is
host-side numpy over the small capture table, like the reference's driver-side
plan construction), while each level's quadratic verification runs sharded: the
level's (dep?, ref?) flags per capture are broadcast as a replicated flag table
(the analog of the reference's broadcast candidate Bloom filters,
SmallToLargeTraversalStrategy.scala:381-401), sorted-joined onto the
device-resident join-line rows, and only flagged rows enter the skew-aware pair
phase.  Join-line rows stay value-bucketed on device across all four levels —
they are built once (exchange A/B) and never leave HBM.

Skew engine (the reference's join-line rebalancing, SURVEY.md §5 "long-context
analog"): a join line shared by m captures costs m(m-1) pairs, so one hot value
can swamp its owner device.  Like the reference — which annotates sizes
(AnnotateJoinLineSizes.scala:19-41), computes the global average quadratic load
(RDFind.scala:421-424), replicates oversized lines (AssignJoinLineRebalancing
.scala:48-64) and lets each replica process a hash-slice of dependent captures
(CreateDependencyCandidates.scala:136-154) — lines whose load exceeds
max(avg*factor, floor) are pulled out of the local pair path, all_gather'ed (XLA
lowers this to a ring of ICI ppermutes), and every device emits pairs only for the
dependents it owns by hash, i.e. ~1/D of each giant line's rows against the full
line.  An absolute backstop (load > cap_pairs/4) also splits when the whole
distribution is heavy, so the local pair budget never has to absorb one huge line.

Captures travel as raw (code, v1, v2) key triples — no global capture interning is
needed, because every grouping is a hash-bucketed sort on the owning device.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import hashlib
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .. import conditions as cc
from ..data import CindTable
from ..ops import frequency, hashing, minimality, pairs, segments
from ..ops.emission import emit_join_candidates
from ..obs import datastats, forecast, integrity
from ..obs import memory as obs_memory
from ..obs import metrics, tracer
from ..parallel import exchange
from ..parallel.mesh import (AXIS, allgather_host_values,
                             dcn_chunks as env_dcn_chunks, hier_spec,
                             host_gather, host_gather_many, make_global,
                             make_mesh, maybe_link_probe, shard_map,
                             topology_hosts)
from ..runtime import dispatch, faults, watchdog
from . import sharded_dense

SENTINEL = segments.SENTINEL


def _masked_counts(valid, inverse, num_segments):
    """Multiplicity of each distinct row produced by masked_unique."""
    w = valid.astype(jnp.int32)
    ids = jnp.clip(inverse, 0, num_segments - 1)
    return jax.ops.segment_sum(w, ids, num_segments=num_segments)


# Split lines whose quadratic load exceeds `rebalance_factor` times the global
# average (the reference's default-ish aggressiveness), but never bother below
# _MIN_SPLIT_LOAD pairs — replication overhead would beat the win.
REBALANCE_FACTOR = 8.0
_MIN_SPLIT_LOAD = 256


@dataclasses.dataclass(frozen=True)
class SkewPolicy:
    """Tunable skew-engine policy (the reference's --rebalance-* flags,
    programs/RDFind.scala:689-698 + AssignJoinLineRebalancing.scala:48-64).

    strategy  -- how a split line's dependents are owned across devices:
                 1 = hash-slice (CreateDependencyCandidates.scala:141-147),
                 2 = contiguous range-slice (:148-154);
    factor    -- a line splits when its quadratic load exceeds
                 factor * global average (--rebalance-threshold scales this);
    max_load  -- absolute load above which a line always splits
                 (--rebalance-max-load, reference default 10000*10000).

    Frozen (hashable) so it can ride jit static_argnames: each distinct policy
    compiles once.
    """

    strategy: int = 1
    factor: float = REBALANCE_FACTOR
    max_load: float = 10_000.0 * 10_000.0

    def __post_init__(self):
        if self.strategy not in (1, 2):
            raise ValueError(
                f"rebalance strategy must be 1 (hash-slice) or 2 "
                f"(range-slice), got {self.strategy}")

    def split_threshold(self, avg_load, cap_pairs: int | None = None):
        """The giant-split load threshold; the ONE definition shared by the
        capacity planner, the hot-line report, and the pair phase (drift
        between copies would desynchronize their load models)."""
        t = jnp.minimum(
            jnp.maximum(avg_load * self.factor, jnp.float32(_MIN_SPLIT_LOAD)),
            jnp.float32(self.max_load))
        if cap_pairs is not None:  # absolute pair-budget backstop
            # cap_pairs may be a traced f32 scalar (the multipass pair phase
            # passes the FULL budget cap_p * n_pass, since per-line emission
            # is dep-sliced ~1/n_pass per pass — a per-pass backstop would
            # reclassify mid-size lines as giant vs the backstop-free plan).
            t = jnp.minimum(t, jnp.asarray(cap_pairs, jnp.float32) // 4)
        return t


DEFAULT_SKEW = SkewPolicy()

# Hash seeds shared between the planning histograms and the real exchanges —
# planning is only exact because both sides bucket identically.
_SEED_VALUE = 1     # exchange A: join value
_SEED_CAPTURE = 2   # exchange B + exchange C: capture key
_SEED_GIANT = 5     # giant-line dependent ownership
_SEED_PASS = 7      # dep-slice selection for bounded-memory pair passes
_SEED_UNARY = 11    # +f, f in 0..2: frequency count exchanges
_SEED_BINARY = 17   # +k, k in 0..2
_SEED_HA = 23       # count-min pair keys for the sharded half-approx rounds
# (The integrity-plane digest lanes use obs/integrity.SEED_A/SEED_B — same
# mixer, so they must stay clear of every routing seed above.)


def _freq_key_sets(triples):
    """The 6 key sets of the frequency filter, with their exchange seeds."""
    sets = [([triples[:, f]], _SEED_UNARY + f) for f in range(3)]
    sets += [([triples[:, a], triples[:, b]], _SEED_BINARY + k)
             for k, (a, b) in enumerate(frequency._FIELD_PAIRS)]
    return sets


def _distributed_frequency(triples, valid_t, min_support, cap_freq,
                           find_ar_implied, *, cap_freq_dcn=0, hier=None,
                           dcn_chunks=1):
    """frequency.triple_frequencies with GLOBAL counts (inside shard_map).

    Six count exchanges (3 unary fields + 3 field pairs) against the keys' hash
    owners; all verdicts are then local per-row comparisons.  Returns
    (TripleFrequency, overflow): on overflow > 0 the verdicts are unusable and
    the caller must retry with a larger cap_freq (hierarchical mode folds the
    DCN-budget shortfall into the same counter — the retry grows both caps).
    """
    counts = []
    ovf = jnp.int32(0)
    for key_cols, seed in _freq_key_sets(triples):
        c, o = exchange.global_row_counts(key_cols, valid_t, AXIS, cap_freq,
                                          seed=seed, hier=hier,
                                          dcn_capacity=cap_freq_dcn,
                                          dcn_chunks=dcn_chunks)
        counts.append(c)
        ovf = ovf + o
    unary_cnt, binary_cnt = counts[:3], counts[3:]
    unary_ok = jnp.stack([c >= min_support for c in unary_cnt], axis=1)
    binary_ok = jnp.stack([c >= min_support for c in binary_cnt], axis=1)
    if find_ar_implied:
        ar = jnp.stack([
            (binary_cnt[k] == unary_cnt[a]) | (binary_cnt[k] == unary_cnt[b])
            for k, (a, b) in enumerate(frequency._FIELD_PAIRS)
        ], axis=1) & binary_ok
    else:
        ar = jnp.zeros_like(binary_ok)
    return frequency.TripleFrequency(unary_ok=unary_ok, binary_ok=binary_ok,
                                     binary_ar_implied=ar), ovf


# ---------------------------------------------------------------------------
# Capacity planning (P1): measure bucket loads before any exchange runs.
# ---------------------------------------------------------------------------


def _bucket_max(cols, valid, seed):
    """Global max over (src, dst) of this device's valid-row count per bucket."""
    num_dev = jax.lax.psum(1, AXIS)
    b = jnp.where(valid, hashing.bucket_of(cols, num_dev, seed=seed), num_dev)
    hist = jax.ops.segment_sum(valid.astype(jnp.int32), b,
                               num_segments=num_dev + 1)
    return jax.lax.pmax(hist[:num_dev].max(), AXIS)


def _combined_bucket_max(bucket_ix, cols, valid, seed, hier):
    """Global max per (relay, final destination) of HOST-combined distinct
    rows — the exact DCN-hop load of a route_combined at this site.

    Members of one host all_gather their rows (intra-host collective only),
    dedupe them host-wide, and histogram by the same bucket hash the real
    exchange uses; hist[t] is then precisely the combined-row count the
    relay (src_host, t % local) will slot for target t.  Exact for the same
    reason _bucket_max is: planner and exchange share seeds.  `bucket_ix`
    selects the hash columns (exchange A hashes join value only).

    Planning-only cost: the gather transiently holds `local` x the measured
    buffer per device.
    """
    num_dev = jax.lax.psum(1, AXIS)
    intra, _ = exchange.hier_groups(hier)
    g_cols = [jax.lax.all_gather(c, AXIS, tiled=True,
                                 axis_index_groups=intra) for c in cols]
    g_valid = jax.lax.all_gather(valid, AXIS, tiled=True,
                                 axis_index_groups=intra)
    u_cols, u_valid, _, _ = segments.masked_unique(g_cols, g_valid)
    b = jnp.where(u_valid, hashing.bucket_of([u_cols[i] for i in bucket_ix],
                                             num_dev, seed=seed), num_dev)
    hist = jax.ops.segment_sum(u_valid.astype(jnp.int32), b,
                               num_segments=num_dev + 1)
    return jax.lax.pmax(hist[:num_dev].max(), AXIS)


def _plan_device(triples, n_valid, *, projections, use_fis, combine=True,
                 hier=None):
    """Measured capacity needs for the frequency exchanges and exchange A
    (+ their DCN-hop budgets when hierarchical; zero lanes otherwise)."""
    t = triples.shape[0]
    valid_t = jnp.arange(t, dtype=jnp.int32) < n_valid[0]

    cap_f = jnp.int32(0)
    cap_fd = jnp.int32(0)
    if use_fis:
        for key_cols, seed in _freq_key_sets(triples):
            u_cols, u_valid, _, _ = segments.masked_unique(key_cols, valid_t)
            cap_f = jnp.maximum(cap_f, _bucket_max(u_cols, u_valid, seed))
            if hier is not None:
                cap_fd = jnp.maximum(cap_fd, _combined_bucket_max(
                    range(len(u_cols)), u_cols, u_valid, seed, hier))

    # Exchange A load: unfiltered emission is an upper bound on the filtered one.
    cands = emit_join_candidates(triples, frequency.no_filter(valid_t),
                                 projections)
    if combine:
        cols, valid, _, _ = segments.masked_unique(
            [cands.join_val, cands.code, cands.v1, cands.v2], cands.valid)
    else:
        cols = [cands.join_val, cands.code, cands.v1, cands.v2]
        valid = cands.valid
    cap_a = _bucket_max([cols[0]], valid, _SEED_VALUE)
    cap_ad = (_combined_bucket_max((0,), cols, valid, _SEED_VALUE, hier)
              if hier is not None else jnp.int32(0))
    return (jnp.full(1, cap_f, jnp.int32), jnp.full(1, cap_a, jnp.int32),
            jnp.full(1, cap_fd, jnp.int32), jnp.full(1, cap_ad, jnp.int32))


@functools.partial(jax.jit,
                   static_argnames=("mesh", "projections", "use_fis",
                                    "combine", "hier"))
def _plan_step(triples, n_valid, *, mesh, projections, use_fis, combine=True,
               hier=None):
    fn = functools.partial(_plan_device, projections=projections,
                           use_fis=use_fis, combine=combine, hier=hier)
    return shard_map(fn, mesh=mesh, in_specs=(P(AXIS, None), P(AXIS)),
                     out_specs=P(AXIS), check_vma=False)(triples, n_valid)


# ---------------------------------------------------------------------------
# Line building (P2): emission -> exchange A -> join-line dedupe + downstream
# load measurement.
# ---------------------------------------------------------------------------


def _lines_device(triples, n_valid, min_support, *, projections, use_fis,
                  use_ars, cap_freq, cap_exchange_a, skew=DEFAULT_SKEW,
                  combine=True, cap_freq_dcn=0, cap_exchange_a_dcn=0,
                  hier=None, dcn_chunks=1):
    t = triples.shape[0]
    valid_t = jnp.arange(t, dtype=jnp.int32) < n_valid[0]
    num_dev = jax.lax.psum(1, AXIS)

    if use_fis:
        freq, ovf_f = _distributed_frequency(triples, valid_t, min_support,
                                             cap_freq, use_ars,
                                             cap_freq_dcn=cap_freq_dcn,
                                             hier=hier, dcn_chunks=dcn_chunks)
    else:
        freq, ovf_f = frequency.no_filter(valid_t), jnp.int32(0)

    # Emission + local dedupe (combiner side of the join, cf.
    # UnionJoinCandidates).  combine=False ships raw candidate rows instead
    # (the reference's --no-combinable-join ablation, RDFind.scala:336-345 /
    # UnionConditions path) — same output, more exchange volume.
    cands = emit_join_candidates(triples, freq, projections)
    if combine:
        cols, valid, _, _ = segments.masked_unique(
            [cands.join_val, cands.code, cands.v1, cands.v2], cands.valid)
    else:
        cols = [cands.join_val, cands.code, cands.v1, cands.v2]
        valid = cands.valid

    # Exchange A: co-locate equal join values.  Hierarchical mode lifts the
    # local dedupe to a per-host dedupe at the relay (route_combined with no
    # weight lane): only host-distinct candidate rows cross DCN, and the
    # owner's masked_unique below sees the same distinct row set either way.
    bucket = hashing.bucket_of([cols[0]], num_dev, seed=_SEED_VALUE)
    if hier is None:
        cols, valid, ovf_a = exchange.bucket_exchange(cols, valid, bucket,
                                                      AXIS, cap_exchange_a)
    else:
        cols, _, valid, (ovf_a1, ovf_a2), _ = exchange.route_combined(
            cols, None, valid, bucket, AXIS, cap_exchange_a,
            cap_exchange_a_dcn, hier, dcn_chunks=dcn_chunks)
        ovf_a = ovf_a1 + ovf_a2

    # Join lines: distinct (value, capture), sorted by value at the owner.
    cols, valid, _, n_rows = segments.masked_unique(cols, valid)
    jv, code, v1, v2 = cols

    # --- Downstream load measurement (the planning half of the skew engine).
    cap_b = _bucket_max([code, v1, v2], valid, _SEED_CAPTURE)
    cap_bd = (_combined_bucket_max((0, 1, 2), [code, v1, v2], valid,
                                   _SEED_CAPTURE, hier)
              if hier is not None else jnp.int32(0))
    pos, length, _, _ = pairs.line_layout(jv, n_rows)
    is_start = valid & (pos == 0)
    len_f = length.astype(jnp.float32)
    load_f = len_f * (len_f - 1.0)
    total_load = jax.lax.psum(jnp.where(is_start, load_f, 0.0).sum(), AXIS)
    total_lines = jax.lax.psum(is_start.sum(), AXIS)
    avg_load = total_load / jnp.maximum(total_lines, 1).astype(jnp.float32)
    # No cap_pairs backstop here (it is what we are planning); the real pair
    # phase may split a few more lines, which only lowers the normal budget.
    thresh = skew.split_threshold(avg_load)
    is_giant = valid & (load_f > thresh)
    norm_pairs = jnp.where(valid & ~is_giant, length - 1, 0)
    cap_p = jax.lax.pmax(pairs.saturating_cumsum(norm_pairs)[-1], AXIS)
    cap_g = jax.lax.pmax(is_giant.sum(), AXIS)
    giant_load = jax.lax.psum(jnp.where(is_start & is_giant, load_f, 0.0).sum(),
                              AXIS)
    # Each device owns ~1/D of every giant line's dependents.
    g_share = jnp.minimum(giant_load / num_dev, jnp.float32(pairs.SAT))

    overflow = jnp.stack([ovf_f, ovf_a])
    plan = jnp.stack([cap_b, cap_bd, cap_p, cap_g, g_share.astype(jnp.int32)])
    return (jv, code, v1, v2, jnp.full(1, n_rows, jnp.int32), plan, overflow)


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "projections", "use_fis", "use_ars", "cap_freq",
                     "cap_exchange_a", "skew", "combine", "cap_freq_dcn",
                     "cap_exchange_a_dcn", "hier", "dcn_chunks"))
def _lines_step(triples, n_valid, min_support, *, mesh, projections, use_fis,
                use_ars, cap_freq, cap_exchange_a, skew=DEFAULT_SKEW,
                combine=True, cap_freq_dcn=0, cap_exchange_a_dcn=0, hier=None,
                dcn_chunks=1):
    fn = functools.partial(_lines_device, projections=projections,
                           use_fis=use_fis, use_ars=use_ars, cap_freq=cap_freq,
                           cap_exchange_a=cap_exchange_a, skew=skew,
                           combine=combine, cap_freq_dcn=cap_freq_dcn,
                           cap_exchange_a_dcn=cap_exchange_a_dcn, hier=hier,
                           dcn_chunks=dcn_chunks)
    return shard_map(fn, mesh=mesh,
                     in_specs=(P(AXIS, None), P(AXIS), P()),
                     out_specs=P(AXIS), check_vma=False)(
        triples, n_valid, min_support)


# ---------------------------------------------------------------------------
# Load-aware placement (P2b): greedy least-loaded reassignment of hot lines.
#
# Exchange A places lines purely by hash(join value); several mid-sized hot
# lines (above average but below the giant-split threshold) can land on one
# device and skew the quadratic pair work.  The reference assigns every line
# greedily to the least-loaded bin by size² priority
# (operators/LoadBasedPartitioner.scala:13-52); here hash stays the base
# placement and only the measured hot tail is greedily reassigned: each device
# reports its heaviest above-average lines + its base load, the host computes
# the greedy placement, and only lines whose owner changes move (whole lines —
# nothing downstream depends on which device owns a line: exchange B/C route
# by capture hash and level flags are replicated).
# ---------------------------------------------------------------------------

_HOT_FACTOR = 2.0   # a line is "hot" when its load exceeds avg * _HOT_FACTOR
_CAP_HOT = 256      # heaviest hot lines reported per device
_REBALANCE_MIN_GAIN = 0.9  # move only if the planned max drops below 90%


def _hotlines_device(jv, n_rows, *, skew=DEFAULT_SKEW, cap_pairs=None):
    """Heaviest above-average lines (jv, length) + base load of this device.

    Lines above the giant-split threshold are excluded from both the report
    and the load model: the split engine already spreads their pair work
    across every device, so moving them is pure cost and counting their full
    load at one bin would distort the greedy placement.
    """
    n = jv.shape[0]
    valid = jnp.arange(n, dtype=jnp.int32) < n_rows[0]
    pos, length, _, _ = pairs.line_layout(jv, n_rows[0])
    is_start = valid & (pos == 0)
    len_f = length.astype(jnp.float32)
    load_f = len_f * (len_f - 1.0)
    total_load = jax.lax.psum(jnp.where(is_start, load_f, 0.0).sum(), AXIS)
    total_lines = jax.lax.psum(is_start.sum(), AXIS)
    avg_load = total_load / jnp.maximum(total_lines, 1).astype(jnp.float32)
    giant_thresh = skew.split_threshold(avg_load, cap_pairs)
    movable = is_start & (load_f <= giant_thresh)
    hot = movable & (load_f > avg_load * _HOT_FACTOR)
    order = jnp.argsort(jnp.where(hot, -load_f, jnp.inf))[:min(_CAP_HOT, n)]
    hot_jv = jnp.where(hot[order], jv[order], SENTINEL)
    hot_len = jnp.where(hot[order], length[order], 0)
    # Report the device's total movable load; the host subtracts the reported
    # lines' loads itself.  (Subtracting all hot lines here would lose the
    # load of hot lines beyond the _CAP_HOT report cap and skew the model.)
    dev_load = jnp.where(movable, load_f, 0.0).sum()
    return hot_jv, hot_len, jnp.full(1, dev_load, jnp.float32)


@functools.partial(jax.jit, static_argnames=("mesh", "skew", "cap_pairs"))
def _hotlines_step(jv, n_rows, *, mesh, skew=DEFAULT_SKEW, cap_pairs=None):
    fn = functools.partial(_hotlines_device, skew=skew, cap_pairs=cap_pairs)
    return shard_map(fn, mesh=mesh, in_specs=(P(AXIS),) * 2,
                     out_specs=P(AXIS), check_vma=False)(jv, n_rows)


def _rebalance_device(jv, code, v1, v2, n_rows, moved_jv, moved_dest, *,
                      cap_move, hier=None, dcn_chunks=1):
    """Ship rows of reassigned lines to their new owners; keep the rest.

    Destinations are data-driven (the host's greedy placement), not a hash of
    the row — so hierarchical mode uses the slot-preserving two-hop route,
    never the combiner (rows are globally unique; nothing would merge).
    """
    n = jv.shape[0]
    valid = jnp.arange(n, dtype=jnp.int32) < n_rows[0]
    my_idx = jax.lax.axis_index(AXIS)
    h = moved_jv.shape[0]
    i = jnp.clip(jnp.searchsorted(moved_jv, jv), 0, h - 1)
    match = valid & (moved_jv[i] == jv)
    dest = jnp.where(match, moved_dest[i], my_idx)
    moving = match & (dest != my_idx)
    stay = valid & ~moving
    mcols, mvalid, ovf = exchange.bucket_exchange([jv, code, v1, v2], moving,
                                                  dest, AXIS, cap_move,
                                                  hier=hier,
                                                  dcn_chunks=dcn_chunks)
    cols_all = [jnp.concatenate([a, b])
                for a, b in zip([jv, code, v1, v2], mcols)]
    valid_all = jnp.concatenate([stay, mvalid])
    cols, _, _, n2 = segments.masked_unique(cols_all, valid_all)
    return (*cols, jnp.full(1, n2, jnp.int32), jnp.full(1, ovf, jnp.int32))


@functools.partial(jax.jit, static_argnames=("mesh", "cap_move", "hier",
                                             "dcn_chunks"))
def _rebalance_step(jv, code, v1, v2, n_rows, moved_jv, moved_dest, *, mesh,
                    cap_move, hier=None, dcn_chunks=1):
    fn = functools.partial(_rebalance_device, cap_move=cap_move, hier=hier,
                           dcn_chunks=dcn_chunks)
    return shard_map(fn, mesh=mesh,
                     in_specs=(P(AXIS),) * 5 + (P(), P()),
                     out_specs=P(AXIS), check_vma=False)(
        jv, code, v1, v2, n_rows, moved_jv, moved_dest)


# ---------------------------------------------------------------------------
# Capture table (P3): exchange B support counting at the capture owner.
# ---------------------------------------------------------------------------


def _captures_device(jv, code, v1, v2, n_rows, *, cap_exchange_b,
                     cap_exchange_b_dcn=0, hier=None, dcn_chunks=1):
    num_dev = jax.lax.psum(1, AXIS)
    n = jv.shape[0]
    valid = jnp.arange(n, dtype=jnp.int32) < n_rows[0]
    cap_bucket = hashing.bucket_of([code, v1, v2], num_dev, seed=_SEED_CAPTURE)
    if hier is None:
        ccols, cvalid, ovf_b = exchange.bucket_exchange([code, v1, v2], valid,
                                                        cap_bucket, AXIS,
                                                        cap_exchange_b)
        cw = cvalid.astype(jnp.int32)
    else:
        # Hierarchical: pre-sum each host's duplicate captures before the DCN
        # hop (weight = row multiplicity); the owner then sums received
        # multiplicities instead of counting raw rows — same totals.
        ccols, cw, cvalid, (ovf_b1, ovf_b2), _ = exchange.route_combined(
            [code, v1, v2], jnp.ones(n, jnp.int32), valid, cap_bucket, AXIS,
            cap_exchange_b, cap_exchange_b_dcn, hier, dcn_chunks=dcn_chunks)
        ovf_b = ovf_b1 + ovf_b2
    tbl_cols, tbl_valid, tbl_inv, n_caps = segments.masked_unique(ccols, cvalid)
    m = tbl_cols[0].shape[0]
    tbl_counts = jax.ops.segment_sum(jnp.where(cvalid, cw, 0),
                                     jnp.clip(tbl_inv, 0, m - 1),
                                     num_segments=m)
    return (tbl_cols[0], tbl_cols[1], tbl_cols[2], tbl_counts,
            jnp.full(1, n_caps, jnp.int32), jnp.full(1, ovf_b, jnp.int32))


@functools.partial(jax.jit, static_argnames=("mesh", "cap_exchange_b",
                                             "cap_exchange_b_dcn", "hier",
                                             "dcn_chunks"))
def _captures_step(jv, code, v1, v2, n_rows, *, mesh, cap_exchange_b,
                   cap_exchange_b_dcn=0, hier=None, dcn_chunks=1):
    fn = functools.partial(_captures_device, cap_exchange_b=cap_exchange_b,
                           cap_exchange_b_dcn=cap_exchange_b_dcn, hier=hier,
                           dcn_chunks=dcn_chunks)
    return shard_map(fn, mesh=mesh,
                     in_specs=(P(AXIS),) * 5,
                     out_specs=P(AXIS), check_vma=False)(
        jv, code, v1, v2, n_rows)


# ---------------------------------------------------------------------------
# Pair phase (shared): skew-aware masked pair counting + exchange C merge.
# ---------------------------------------------------------------------------


def _emit_local_pairs(jv, code, v1, v2, n_rows, dep_f, ref_f, *, cap_pairs,
                      cap_giant, cap_giant_pairs, skew=DEFAULT_SKEW,
                      pass_idx=None, n_pass=None):
    """Pair emission + device-local pre-count of one dep-slice pass.

    The first half of the pair phase: skew stats, giant-line split/gather,
    masked pair emission, and the local masked_unique pre-count — everything
    BEFORE any cross-device pair exchange.  This is also the sharded
    two-round's "bounded explicit window per device": the deduped
    (pair key, partial count) rows, bounded by cap_pairs/cap_giant_pairs,
    that the round-1 count-min build folds into a partial table without the
    pairs ever leaving the device.

    Returns (pcols(6), pvalid2, pcnt, (ovf_p, ovf_g, ovf_gp),
    n_giant_lines, n_giant_pairs, n_pairs_total).
    """
    num_dev = jax.lax.psum(1, AXIS)
    my_idx = jax.lax.axis_index(AXIS)
    n = jv.shape[0]
    valid = jnp.arange(n, dtype=jnp.int32) < n_rows
    dep_f = dep_f & valid
    ref_f = ref_f & valid
    if n_pass is not None:
        dep_f = dep_f & (hashing.bucket_of([code, v1, v2], n_pass,
                                           seed=_SEED_PASS) == pass_idx)

    # Skew stats: per-line quadratic load + global average (f32: loads overflow
    # int32 long before they overflow the threshold math's precision needs).
    pos, length, start_idx, _ = pairs.line_layout(jv, n_rows)
    is_start = valid & (pos == 0)
    len_f = length.astype(jnp.float32)
    load_f = len_f * (len_f - 1.0)
    total_load = jax.lax.psum(jnp.where(is_start, load_f, 0.0).sum(), AXIS)
    total_lines = jax.lax.psum(is_start.sum(), AXIS)
    avg_load = total_load / jnp.maximum(total_lines, 1).astype(jnp.float32)
    full_budget = (jnp.float32(cap_pairs) if n_pass is None
                   else jnp.float32(cap_pairs) * n_pass.astype(jnp.float32))
    thresh = skew.split_threshold(avg_load, full_budget)
    is_giant = valid & (load_f > thresh)
    n_giant_lines = jax.lax.psum((is_start & is_giant).sum(), AXIS)

    # Pair emission for normal lines (giant rows get length 1 => no pairs).
    # Only dep-flagged rows emit: S2L levels and dep-slice passes allocate
    # buffer slots proportional to their actual work, not the full quadratic.
    length_n = jnp.where(is_giant, 1, length)
    total_norm = pairs.saturating_cumsum(
        jnp.where(dep_f, length_n - 1, 0))[-1]
    ovf_p = jax.lax.psum(jnp.maximum(total_norm - cap_pairs, 0), AXIS)
    row, partner, pvalid = pairs.emit_pair_indices(pos, length_n, start_idx,
                                                   cap_pairs, emit=dep_f)
    pvalid = pvalid & dep_f[row] & ref_f[partner]

    # Giant lines: extract whole lines, all_gather, process an owned dep slice.
    # Giant rows are a subset of the line rows, so the giant buffer never needs
    # to exceed the row buffer (also guards slicing below: c[:cap] must not
    # clamp shorter than g_valid's arange).  Flags ride along packed in one lane.
    cap_giant = min(cap_giant, n)
    flag = dep_f.astype(jnp.int32) * 2 + ref_f.astype(jnp.int32)
    g_cols, n_g = segments.compact([jv, code, v1, v2, flag], is_giant)
    ovf_g = jax.lax.psum(jnp.maximum(n_g - cap_giant, 0), AXIS)
    g_valid = jnp.arange(cap_giant, dtype=jnp.int32) < n_g
    gg = [jax.lax.all_gather(c[:cap_giant], AXIS, tiled=True) for c in g_cols]
    gg_valid = jax.lax.all_gather(g_valid, AXIS, tiled=True)
    # Regroup gathered rows by line (jv is globally unique per line, so sorting by
    # it alone re-forms whole lines; in-line order is irrelevant to rotations).
    permg = segments.lexsort([jnp.where(gg_valid, gg[0], SENTINEL)])
    jv_g, code_g, v1_g, v2_g, flag_g = (c[permg] for c in gg)
    gv = gg_valid[permg]
    dep_fg = gv & (flag_g >= 2)
    ref_fg = gv & (flag_g % 2 == 1)
    posg, leng, startg, _ = pairs.line_layout(jv_g, gv.sum())
    if skew.strategy == 2:
        # Contiguous range-slice of each line's rows (the reference's split
        # strategy 2, CreateDependencyCandidates.scala:148-154): device d owns
        # positions [d*block, (d+1)*block) with block = ceil(len/D).  Division
        # by the block size (not posg * num_dev, which would overflow int32 on
        # giant lines) keeps everything in 32 bits.
        block = jnp.maximum(-(-leng // num_dev), 1)
        own = dep_fg & (posg // block == my_idx)
    else:
        # Hash-slice (split strategy 1, :141-147).
        own = dep_fg & (hashing.bucket_of([code_g, v1_g, v2_g], num_dev,
                                          seed=_SEED_GIANT) == my_idx)
    (posd, lend, startd, dc, dv1, dv2), n_own = segments.compact(
        [posg, leng, startg, code_g, v1_g, v2_g], own)
    lend = jnp.where(jnp.arange(lend.shape[0], dtype=jnp.int32) < n_own, lend, 1)
    total_g = pairs.saturating_cumsum(lend - 1)[-1]
    ovf_gp = jax.lax.psum(jnp.maximum(total_g - cap_giant_pairs, 0), AXIS)
    growp, gpart, gpvalid = pairs.emit_pair_indices(posd, lend, startd,
                                                    cap_giant_pairs)
    gpvalid = gpvalid & ref_fg[gpart]
    n_giant_pairs = jax.lax.psum(total_g, AXIS)
    n_pairs_total = jax.lax.psum(total_norm, AXIS) + n_giant_pairs

    # Local partial counts over the combined (normal + giant-slice) stream.
    pair_cols = [jnp.concatenate([a[row], b[growp]])
                 for a, b in ((code, dc), (v1, dv1), (v2, dv2))]
    pair_cols += [jnp.concatenate([a[partner], b[gpart]])
                  for a, b in ((code, code_g), (v1, v1_g), (v2, v2_g))]
    pvalid_all = jnp.concatenate([pvalid, gpvalid])
    pcols, pvalid2, pinv, _ = segments.masked_unique(pair_cols, pvalid_all)
    pcnt = _masked_counts(pvalid_all, pinv, pcols[0].shape[0])
    return (pcols, pvalid2, pcnt, (ovf_p, ovf_g, ovf_gp),
            n_giant_lines, n_giant_pairs, n_pairs_total)


def _ha_pair_keys(pcols):
    """32-bit count-min key of one (dep capture, ref capture) pair row.

    Pure function of the six key columns, so the same pair produces the same
    key on every device and in every pass — the property the round-2 cut's
    soundness argument leans on.
    """
    return hashing.hash_cols(pcols, seed=_SEED_HA).astype(jnp.int32)


def _pair_phase(jv, code, v1, v2, n_rows, dep_f, ref_f, *, cap_pairs,
                cap_exchange_c, cap_giant, cap_giant_pairs,
                skew=DEFAULT_SKEW, pass_idx=None, n_pass=None,
                cap_exchange_c_dcn=0, hier=None, dcn_chunks=1, ha_cut=None):
    """Skew-aware masked pair counting over value-sorted line rows.

    Emits all ordered co-occurrence pairs whose dependent row is dep-flagged and
    partner row is ref-flagged (AllAtOnce passes all-valid flags; SmallToLarge
    passes the level's candidate flags), splitting oversized lines across the
    mesh, then routes pair partials to the dependent capture's owner (seed 2)
    and merges counts there.

    pass_idx/n_pass (traced int32 scalars) select one dep-slice PASS: only
    rows whose capture hashes to pass_idx (mod n_pass) emit pairs, so pair
    buffers, the exchange, and the merge all shrink by ~n_pass while the
    resident join lines are reread in place.  Slices partition the dependent
    captures, so per-pass outputs concatenate with no cross-pass merge.
    This is the bounded-memory analog of the reference's windowed merge
    under heap pressure (BulkMergeDependencies.scala:96-104) — multi-pass
    streaming over resident data instead of Flink's disk spill.  Emission
    masking (ops/pairs.emit_pair_indices `emit`) means non-emitting rows
    take zero buffer slots; n_pairs_total counts EMITTED pairs.

    ha_cut, when set to (table, bits, num_hashes, thresh), applies the
    round-2 candidate cut of the sharded half-approximate 1/1 BEFORE
    exchange C: pair rows whose count-min upper bound falls below thresh are
    dropped from the exchange.  Sound because the all-reduced table
    upper-bounds min(true global cooc, cap) per pair and thresh is clamped
    to min(min_support, cap) by the caller — a pair meeting min_support can
    never estimate below thresh — and because the same pair hashes to the
    same key on every device (`_ha_pair_keys`), so all of a pair's partial
    rows survive or die together: no partial-sum corruption at the merge,
    and cut pairs have true cooc < min_support, which the downstream CIND
    test discards anyway.  Output is therefore bit-identical with the cut
    on or off; only exchange C traffic and merge width shrink.

    Returns (ucols(6), uvalid, cooc, (ovf_p, ovf_c, ovf_g, ovf_gp, ovf_cd),
    n_giant_lines, n_giant_pairs, n_pairs_total, n_ha_cut).  ovf_cd is
    exchange C's inter-host (DCN) hop overflow; always 0 on the flat path.
    n_ha_cut counts sketch-cut pair rows (0 when ha_cut is None).
    """
    from ..ops import sketch
    num_dev = jax.lax.psum(1, AXIS)
    (pcols, pvalid2, pcnt, (ovf_p, ovf_g, ovf_gp),
     n_giant_lines, n_giant_pairs, n_pairs_total) = _emit_local_pairs(
        jv, code, v1, v2, n_rows, dep_f, ref_f, cap_pairs=cap_pairs,
        cap_giant=cap_giant, cap_giant_pairs=cap_giant_pairs, skew=skew,
        pass_idx=pass_idx, n_pass=n_pass)

    n_ha_cut = jnp.int32(0)
    if ha_cut is not None:
        table, ha_bits, ha_hashes, ha_thresh = ha_cut
        est = sketch.count_min_query(table, _ha_pair_keys(pcols),
                                     bits=ha_bits, num_hashes=ha_hashes)
        keep = est >= ha_thresh
        n_ha_cut = jax.lax.psum(jnp.where(pvalid2 & ~keep, 1, 0).sum(), AXIS)
        pvalid2 = pvalid2 & keep

    # Exchange C: co-locate pair partials with the dependent capture's owner.
    # Hierarchical mode sum-combines each host's partial counts per pair key
    # before the DCN hop (pcnt is the combine weight); the owner-side merge
    # below is mode-agnostic — it sums whatever count lane arrives.
    pair_bucket = hashing.bucket_of(pcols[0:3], num_dev, seed=_SEED_CAPTURE)
    if hier is None:
        mcols, mvalid, ovf_c = exchange.bucket_exchange(pcols + [pcnt],
                                                        pvalid2, pair_bucket,
                                                        AXIS, cap_exchange_c)
        mkeys, mcnt_in = mcols[0:6], mcols[6]
        ovf_cd = jnp.int32(0)
    else:
        mkeys, mcnt_in, mvalid, (ovf_c, ovf_cd), _ = exchange.route_combined(
            pcols, pcnt, pvalid2, pair_bucket, AXIS, cap_exchange_c,
            cap_exchange_c_dcn, hier, dcn_chunks=dcn_chunks)

    # Merge partial counts across sources.
    ucols, uvalid, uinv, _ = segments.masked_unique(mkeys, mvalid)
    m = ucols[0].shape[0]
    cooc = jax.ops.segment_sum(jnp.where(mvalid, mcnt_in, 0),
                               jnp.clip(uinv, 0, m - 1), num_segments=m)
    return (ucols, uvalid, cooc, (ovf_p, ovf_c, ovf_g, ovf_gp, ovf_cd),
            n_giant_lines, n_giant_pairs, n_pairs_total, n_ha_cut)


# Packed per-pass control lanes (exchange.pack_counters): 5 overflow counters
# followed by the tail counters.  ONE lane array per pass is the whole
# device->host control surface of the pipelined executor — the host reads it
# in a single async-staged pull instead of 3+ blocking host_gathers.
_TELE_LANES = 11  # [ovf_p, ovf_c, ovf_g, ovf_gp, ovf_cd, n_giant_lines,
#                   n_giant_pairs, n_pairs_total, n_ha_cut, dig_a, dig_b]
_N_OVF = 5
# The integrity digest lanes ride at the END of the tail so every existing
# tail index (datastats' [:3], run_cooc's n_ha_cut at [3]) stays valid; the
# tail tuple persisted per pass in progress snapshots therefore carries the
# digests for free, and snapshots are re-verified on load against them.
_N_TAIL = _TELE_LANES - _N_OVF


def _digest_lanes(cols, valid):
    """The two psum'd integrity-digest lanes over a masked device row set
    (obs/integrity.py): global uint32 wraparound sums, identical on every
    device.  Computed unconditionally — the same compiled program runs with
    the integrity knob on or off (bit-identity; only host-side verification
    is gated)."""
    return (jax.lax.psum(hashing.digest_fold(cols, valid,
                                             seed=integrity.SEED_A), AXIS),
            jax.lax.psum(hashing.digest_fold(cols, valid,
                                             seed=integrity.SEED_B), AXIS))


def _cind_device(jv, code, v1, v2, n_rows, tc, tv1, tv2, tcnt, n_caps,
                 min_support, pass_idx, n_pass, *, cap_pairs, cap_exchange_c,
                 cap_giant, cap_giant_pairs, skew=DEFAULT_SKEW,
                 cap_exchange_c_dcn=0, hier=None, dcn_chunks=1):
    """AllAtOnce finish: all-flag pair phase + support join + CIND test."""
    n = jv.shape[0]
    valid = jnp.arange(n, dtype=jnp.int32) < n_rows[0]
    (ucols, uvalid, cooc, (ovf_p, ovf_c, ovf_g, ovf_gp, ovf_cd),
     n_giant_lines, n_giant_pairs, n_pairs_total, n_ha_cut) = _pair_phase(
        jv, code, v1, v2, n_rows[0], valid, valid, cap_pairs=cap_pairs,
        cap_exchange_c=cap_exchange_c, cap_giant=cap_giant,
        cap_giant_pairs=cap_giant_pairs, skew=skew,
        pass_idx=pass_idx[0], n_pass=n_pass[0],
        cap_exchange_c_dcn=cap_exchange_c_dcn, hier=hier,
        dcn_chunks=dcn_chunks)

    # Support lookup + CIND test (same-device by shared hash _SEED_CAPTURE).
    tbl_valid = jnp.arange(tc.shape[0], dtype=jnp.int32) < n_caps[0]
    dep_count = exchange.sorted_join_counts([tc, tv1, tv2], tcnt, tbl_valid,
                                            ucols[0:3], uvalid)
    is_cind = uvalid & (cooc == dep_count) & (dep_count >= min_support)

    d_code, d_v1, d_v2, r_code, r_v1, _ = ucols
    implied = cc.is_subcode(r_code, d_code) & jnp.where(
        cc.first_subcapture(d_code) == r_code, r_v1 == d_v1, r_v1 == d_v2)
    keep = is_cind & ~implied

    out_cols, n_out = segments.compact(list(ucols) + [dep_count], keep)
    dig_a, dig_b = _digest_lanes(
        out_cols, jnp.arange(out_cols[0].shape[0], dtype=jnp.int32) < n_out)
    tele = exchange.pack_counters([ovf_p, ovf_c, ovf_g, ovf_gp, ovf_cd,
                                   n_giant_lines, n_giant_pairs,
                                   n_pairs_total, n_ha_cut, dig_a, dig_b])
    return (*out_cols, jnp.full(1, n_out, jnp.int32), tele)


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "cap_pairs", "cap_exchange_c", "cap_giant",
                     "cap_giant_pairs", "skew", "cap_exchange_c_dcn", "hier",
                     "dcn_chunks"))
def _cind_step(jv, code, v1, v2, n_rows, tc, tv1, tv2, tcnt, n_caps,
               min_support, pass_idx, n_pass, *, mesh, cap_pairs,
               cap_exchange_c, cap_giant, cap_giant_pairs, skew=DEFAULT_SKEW,
               cap_exchange_c_dcn=0, hier=None, dcn_chunks=1):
    fn = functools.partial(_cind_device, cap_pairs=cap_pairs,
                           cap_exchange_c=cap_exchange_c, cap_giant=cap_giant,
                           cap_giant_pairs=cap_giant_pairs, skew=skew,
                           cap_exchange_c_dcn=cap_exchange_c_dcn, hier=hier,
                           dcn_chunks=dcn_chunks)
    return shard_map(fn, mesh=mesh,
                     in_specs=(P(AXIS),) * 10 + (P(),) * 3,
                     out_specs=P(AXIS), check_vma=False)(
        jv, code, v1, v2, n_rows, tc, tv1, tv2, tcnt, n_caps, min_support,
        pass_idx, n_pass)


# ---------------------------------------------------------------------------
# Host orchestration.
# ---------------------------------------------------------------------------


# Floors for the per-device block and planned capacities: every workload small
# enough to land under a floor shares the same compiled shard_map programs —
# compilation, not compute, dominates small runs (the r2 test suite recompiled
# the whole pipeline per test workload).
T_LOC_FLOOR = 256
CAP_FLOOR = 512

# Per-device pair-stream rows per pass before the pair phase splits into
# dep-slice passes (RDFIND_PAIR_ROW_BUDGET overrides).  2^25 rows cost
# ~150-200 B each through emission + the merge lexsort — a few GB of
# transients, comfortable inside a v5e's 16 GB HBM next to the resident
# lines; hosts proxying many fake devices in one address space set it lower.
PAIR_ROW_BUDGET = 1 << 25

# Sharded half-approximate 1/1 (the distributed two-round count-min cut).
# Depth 2 matches the single-device half-approx round's spectral filter
# economics: two probes halve the collision overestimate per doubling of
# query cost, and the cut is correctness-neutral at any depth.
_HA_HASHES = 2
_HA_DEF_BITS = 1 << 16


def sharded_half_approx_enabled() -> bool:
    """RDFIND_SHARDED_HALF_APPROX: run strategies' pair verification as the
    sharded two-round count-min 1/1 (round 1 builds per-device partial
    sketches + all-reduces them; round 2 cuts sub-support candidates before
    exchange C).  auto/0/1; auto (default) = off until benched on.  Output
    is bit-identical either way — the sketch only prunes candidates the
    support filter would discard."""
    v = os.environ.get("RDFIND_SHARDED_HALF_APPROX", "auto").strip().lower()
    return v in ("1", "on", "true", "yes")


def sharded_ha_bits() -> int:
    """RDFIND_SHARDED_HA_BITS: count-min table width for the sharded
    two-round (power of two, min 32; default 2^16 = 256 KiB of int32 per
    device — one table, independent of mesh size)."""
    v = int(os.environ.get("RDFIND_SHARDED_HA_BITS", _HA_DEF_BITS))
    return max(32, segments.pow2_capacity(max(v, 1)))


def _block_rows(n: int, num_dev: int) -> int:
    """Rows of each device's block of an n-row triple table."""
    return max(T_LOC_FLOOR, segments.pow2_capacity(-(-n // num_dev)))


def _shard_triples(triples, num_dev, t_loc: int | None = None):
    """Contiguous per-device split, padded to a shared power-of-two block.

    `t_loc` overrides the block size (the multi-host ingest agrees on one
    globally so every host's blocks tile the same global array).
    """
    n = triples.shape[0]
    if t_loc is None:
        t_loc = _block_rows(n, num_dev)
    padded = np.full((num_dev * t_loc, 3), np.iinfo(np.int32).max, np.int32)
    n_valid = np.zeros(num_dev, np.int32)
    for dev in range(num_dev):
        lo, hi = dev * t_loc, min((dev + 1) * t_loc, n)
        hi = max(hi, lo)
        take = triples[lo:hi] if lo < n else triples[:0]
        padded[dev * t_loc: dev * t_loc + take.shape[0]] = take
        n_valid[dev] = take.shape[0]
    return padded, n_valid, t_loc


# Largest TOTAL buffer (rows) an int32-indexed scatter/sort can address;
# beyond it the plan must fail loudly, not wrap (a 60k-triple support-5 smoke
# found route()'s flat index overflowing instead).  Exchange/all_gather
# buffers total D * capacity rows per device; local pair buffers total their
# capacity.
MAX_EXCHANGE_ROWS = (1 << 31) - 1


def _check_caps(**total_rows) -> None:
    """Every named buffer's TOTAL rows must stay int32-indexable."""
    for name, rows in total_rows.items():
        if int(rows) > MAX_EXCHANGE_ROWS:
            raise RuntimeError(
                f"planned buffer {name}={int(rows)} rows exceeds the int32 "
                f"indexing budget; this workload's pair volume needs more "
                f"devices, a higher --support, or --use-fis pruning")


def _check_exchange_caps(num_dev: int, **caps) -> None:
    """Planned capacities must keep every (D * capacity) buffer int32-indexable."""
    _check_caps(**{name: num_dev * int(c) for name, c in caps.items()})


def _headroom(measured: int, floor: int = CAP_FLOOR) -> int:
    """Measured load -> planned capacity: +12.5% margin, pow2-bucketed (compiled
    programs are reused across runs whose loads land in the same bucket)."""
    measured = int(measured)
    return segments.pow2_capacity(max(measured + max(measured // 8, floor),
                                      floor))


class _PairCapsExhausted(Exception):
    """Internal ladder signal: a pass exhausted its grow retries (the
    executor escalates to split / fallback; never escapes _run_passes)."""

    def __init__(self, msg: str):
        super().__init__(msg)
        self.msg = msg


# Exchange-site lane counts for the communication ledger
# (exchange.log_exchange): payload columns + validity lane.  Derived from the
# device code above — update together.
_LANES_FREQ = 27        # 6 count exchanges: 3 unary (4 lanes) + 3 binary (5)
_LANES_FREQ_REPLY = 6   # 6 count exchanges x 1 reply lane (route_reply /
#                         route_combined_reply return traffic)
_LANES_EXCHANGE_A = 5   # [jv, code, v1, v2] + validity
_LANES_EXCHANGE_B = 4   # [code, v1, v2] + validity (+1 weight lane when hier)
_LANES_REBALANCE = 5    # [jv, code, v1, v2] + validity
_LANES_EXCHANGE_C = 8   # 6 pair-key cols + count + validity
_LANES_GIANT = 6        # [jv, code, v1, v2, flag] + validity (all_gather)


class _SkewMeter:
    """Straggler/skew attribution across hosts, one sample per committed pass.

    The paper's scalability argument rests on the bucket shuffles staying
    balanced across workers; this is the instrument that says when they
    don't, and WHY.  Each committed pass the executor hands over this host's
    phase breakdown — exchange (dispatch/enqueue + ledger), compute (the
    blocking counters pull: dominated by the head pass's device program),
    pull (the blocks readback), commit (HBM sample + progress snapshot) —
    and the meter exchanges the 5-float vector across hosts on one tiny
    allgather (mesh.allgather_host_values; single-process it is a reshape).
    Per pass it emits trace counter lanes (`host_skew`, `pass_phase_ms`) and
    registry histograms; at attempt end `publish` lands the run-level
    ``host_skew`` struct: skew index (slowest host wall / mean wall), the
    slowest host, and its dominant-phase cause bucket.

    Active only with a live obs consumer or the collective timers armed —
    the disabled path costs one attribute check per pass.
    """

    PHASES = ("exchange", "compute", "pull", "commit")

    def __init__(self, stats, what: str):
        self.active = (tracer.enabled() or metrics.export_requested()
                       or exchange.collective_timing_enabled())
        self.stats = stats
        self.what = what
        self.totals = np.zeros(len(self.PHASES) + 1)
        self.n_committed = 0

    def vec(self, phase_ms: dict) -> list:
        """This host's [4 phases + wall] sample for the commit collective."""
        v = [float(phase_ms.get(ph, 0.0)) for ph in self.PHASES]
        v.append(sum(v))
        return v

    def pass_committed(self, phase_ms: dict) -> None:
        """Standalone form (one allgather); the pass executor instead rides
        the coalesced pass_commit collective via pass_committed_rows."""
        vec = self.vec(phase_ms)
        self.pass_committed_rows(
            vec, allgather_host_values(vec, site="pass_commit"))

    def pass_committed_rows(self, vec: list, m: np.ndarray) -> None:
        """Consume this host's vec + the already-allgathered (hosts, 5)
        matrix — the executor batches the skew sample onto the same
        per-pass collective as the integrity digest agreement, so the two
        consumers cost ONE allgather per committed pass (the gloo
        many-tiny-collectives abort scales with collective count)."""
        self.totals += np.asarray(vec)
        self.n_committed += 1
        walls = m[:, -1]
        slowest = int(walls.argmax())
        skew = float(walls.max() / max(float(walls.mean()), 1e-9))
        tracer.counter("host_skew", skew=round(skew, 3), slowest=slowest)
        tracer.counter("pass_phase_ms",
                       **{ph: round(v, 3)
                          for ph, v in zip(self.PHASES, vec)})
        for ph, v in zip(self.PHASES, vec):
            metrics.observe(f"pass_{ph}_ms", v)

    def publish(self) -> None:
        """The attempt-level host_skew struct (every host calls this the
        same number of times — the allgather is a collective)."""
        if not self.active or not self.n_committed:
            return
        m = allgather_host_values(self.totals.tolist())
        walls = m[:, -1]
        slowest = int(walls.argmax())
        cause = self.PHASES[int(np.argmax(m[slowest, :-1]))]
        metrics.struct_set(self.stats, "host_skew", {
            "n_hosts": int(m.shape[0]),
            "n_passes": int(self.n_committed),
            "skew_index": round(float(walls.max()
                                      / max(float(walls.mean()), 1e-9)), 4),
            "slowest_host": slowest,
            "cause": cause,
            "per_host_ms": [round(float(x), 3) for x in walls],
            "phase_ms": {ph: [round(float(x), 3) for x in m[:, i]]
                         for i, ph in enumerate(self.PHASES)},
        })


def _host_mix32(x: np.ndarray) -> np.ndarray:
    """numpy replica of ops.hashing.mix32 (uint32 wraparound semantics)."""
    with np.errstate(over="ignore"):
        x = np.asarray(x).astype(np.uint32)
        x = (x ^ (x >> np.uint32(16))) * np.uint32(0x85EBCA6B)
        x = (x ^ (x >> np.uint32(13))) * np.uint32(0xC2B2AE35)
        return x ^ (x >> np.uint32(16))


def _host_bucket_of(cols, num_buckets: int, *, seed: int) -> np.ndarray:
    """numpy replica of ops.hashing.bucket_of: the elastic-resume re-shard
    must route reloaded rows to exactly the owners the device exchanges
    would pick, or a resumed run would diverge from an uninterrupted one.
    The replica now lives in ops.hashing.host_bucket_of so the delta engine
    shares the identical routing law; this wrapper keeps the local name."""
    return hashing.host_bucket_of(cols, num_buckets, seed=seed)


def _reshard_pass_rows(cols, num_dev: int):
    """Re-shard one committed pass's host rows for a `num_dev` mesh.

    collect_blocks concatenates per-device blocks in device order, and each
    device's rows leave masked_unique sorted ascending over the 6 key
    columns — so an uninterrupted run's global row order per pass is (owner
    bucket, key lex).  The owner bucket is the exchange-C route,
    bucket_of(key[0:3], num_dev, seed=_SEED_CAPTURE); recomputing it for the
    new mesh and re-sorting reproduces bit-exactly the rows a run AT that
    mesh size would have committed for this pass.
    """
    key = [np.asarray(c) for c in cols[:6]]
    bucket = _host_bucket_of(key[0:3], num_dev, seed=_SEED_CAPTURE)
    # np.lexsort sorts by the LAST key first: bucket is primary, then the
    # 6 key columns major-to-minor — the same order segments.lexsort yields
    # on device within each bucket.
    order = np.lexsort(tuple(reversed(key)) + (bucket,))
    return [np.asarray(c)[order] for c in cols]


class _Pipeline:
    """Planned, retrying execution of the sharded programs (host side).

    Holds the device-resident line rows + capture table and the capacity plan.
    Every stage checks its psum'd overflow counters and retries with grown
    capacities — the plan is the fast path, retry the safety net.
    """

    def __init__(self, mesh, triples, min_support, projections, use_fis,
                 use_ars, max_retries, stats, skew=None, combine=True,
                 preshard=None, progress=None):
        self.mesh = mesh
        self.num_dev = mesh.devices.size
        self.min_support = min_support
        self.max_retries = max_retries
        self.stats = stats
        # The watchdog is process-global; point its fire path's degradation
        # ledger at this run's stats dict.
        watchdog.bind_stats(stats)
        self.skew = skew if skew is not None else DEFAULT_SKEW
        self.combine = combine
        # Hierarchical (two-level ICI/DCN) exchange configuration: None = flat
        # single-hop all_to_all; (hosts, local) factorization otherwise.
        # hosts feeds the ledger's ICI/DCN byte attribution in BOTH modes.
        self.hier = hier_spec(self.num_dev)
        self.hosts = topology_hosts(self.num_dev)
        self.dcn_chunks = env_dcn_chunks()
        # One-shot link-capability probe (RDFIND_LINK_PROBE): tiny all_to_all
        # microbench per hop, cached per topology — the denominator of every
        # link_util the collective timers report.
        maybe_link_probe(mesh)
        # RDFIND_COLLECTIVE_TIMING arms per-dispatch device-synchronized wall
        # clocks (block_until_ready after every exchange dispatch).  That
        # serializes the pipelined executor, so it is a measurement mode, not
        # a flight mode; outputs are bit-identical either way.
        self._timed = exchange.collective_timing_enabled()
        # Preemption-safe per-pass checkpoints (checkpoint.ProgressStore, or
        # None): each _run_passes phase snapshots committed passes through it.
        self.progress = progress
        self._phase_seq = 0
        # Pull-retry telemetry baseline: the pipeline's planning/line pulls
        # run before any DispatchStats exists, so the executor publishes the
        # delta since THIS point (pipeline lifetime, not executor lifetime).
        self._pull_base = faults.pull_stats()
        if preshard is not None:
            # Pre-built global arrays (sharded multi-host ingest:
            # runtime/multihost_ingest.py) — no host ever held the full table.
            self._triples, self._n_valid = preshard
        else:
            padded, n_valid, _ = _shard_triples(triples, self.num_dev)
            self._triples = make_global(padded, mesh)
            self._n_valid = make_global(n_valid, mesh)

        # Data-plane sampling gate (obs/datastats.py): resolved once per
        # pipeline — the per-pass path pays attribute checks only.  The env
        # knob must agree across hosts (same contract as RDFIND_TRACE).
        self._datastats_on = datastats.enabled()
        # Integrity-plane gate (obs/integrity.py): resolved once per
        # pipeline.  The device digest lanes are computed unconditionally —
        # the same compiled program runs with the knob on or off (bit
        # identity); this flag gates only the host-side recompute, verify
        # and publish.  The env knob must agree across hosts (same contract
        # as RDFIND_TRACE).
        self._integrity_on = integrity.enabled()

        # Sharded half-approximate 1/1 (RDFIND_SHARDED_HALF_APPROX): resolved
        # once so every run_cooc level sees one consistent configuration.
        # The cut threshold is clamped to the sketch cap — counters saturate
        # at cap, so a pair meeting min_support > cap still reads >= cap.
        from ..ops import sketch
        self.ha_on = sharded_half_approx_enabled()
        self.ha_bits = sharded_ha_bits()
        self.ha_hashes = _HA_HASHES
        self.ha_thresh = min(int(min_support), sketch.MAX_COUNT_MIN_CAP)

        # P1: measured plan for the pre-exchange capacities.  Hierarchical
        # mode also measures the DCN-hop (host-combined) loads exactly.
        # The raw pre-headroom gathers double as the cap-utilization
        # numerators (datastats): they ARE the measured demand.
        cap_f, cap_a, cap_fd, cap_ad = _plan_step(
            self._triples, self._n_valid, mesh=mesh, projections=projections,
            use_fis=use_fis, combine=combine, hier=self.hier)
        raw_f = int(host_gather(cap_f)[0]) if use_fis else 0
        raw_a = int(host_gather(cap_a)[0])
        self.cap_f = _headroom(raw_f) if use_fis else 1
        self.cap_a = _headroom(raw_a)
        raw_fd = raw_ad = 0
        if self.hier is not None:
            raw_fd = int(host_gather(cap_fd)[0]) if use_fis else 0
            raw_ad = int(host_gather(cap_ad)[0])
            self.cap_f_dcn = _headroom(raw_fd) if use_fis else 1
            self.cap_a_dcn = _headroom(raw_ad)
        else:
            self.cap_f_dcn = 0
            self.cap_a_dcn = 0

        # P2: lines + downstream load measurement (retry on freq/A overflow).
        hier_on = self.hier is not None
        for _ in range(max_retries):
            pend = []
            if use_fis:
                pend.append(exchange.log_exchange(
                    stats, "freq", num_dev=self.num_dev, capacity=self.cap_f,
                    lanes=_LANES_FREQ, reply_lanes=_LANES_FREQ_REPLY,
                    hosts=self.hosts, hier=hier_on,
                    dcn_capacity=self.cap_f_dcn if hier_on else None))
            pend.append(exchange.log_exchange(
                stats, "exchange_a", num_dev=self.num_dev,
                capacity=self.cap_a, lanes=_LANES_EXCHANGE_A,
                hosts=self.hosts, hier=hier_on,
                dcn_capacity=self.cap_a_dcn if hier_on else None))
            t0 = time.perf_counter() if self._timed else 0.0
            with watchdog.collective(
                    "freq", sum(e.get("bytes", 0) for e in pend)):
                out = _lines_step(
                    self._triples, self._n_valid, jnp.int32(min_support),
                    mesh=mesh, projections=projections, use_fis=use_fis,
                    use_ars=use_ars, cap_freq=self.cap_f,
                    cap_exchange_a=self.cap_a,
                    skew=self.skew, combine=self.combine,
                    cap_freq_dcn=self.cap_f_dcn,
                    cap_exchange_a_dcn=self.cap_a_dcn, hier=self.hier,
                    dcn_chunks=self.dcn_chunks)
                if self._timed:
                    jax.block_until_ready(out)
                    exchange.log_dispatch_timing(
                        stats, pend, (time.perf_counter() - t0) * 1e3)
                *line_cols, n_rows, plan, overflow = out
                ovf = host_gather(overflow).reshape(self.num_dev, 2)[0]
            if faults.overflow_injected("overflow@lines"):
                ovf = np.maximum(ovf, 1)
            if int(ovf.sum()) == 0:
                break
            self._count_overflow_retry(
                "line-building",
                site="freq" if int(ovf[0]) > 0 else "exchange_a")
            # Hierarchical overflow counters fold both hops; growing the
            # site's ICI and DCN capacities together keeps the retry monotone.
            if ovf[0] > 0:
                self.cap_f = segments.pow2_capacity(2 * self.cap_f + int(ovf[0]))
                if hier_on:
                    self.cap_f_dcn = segments.pow2_capacity(
                        2 * self.cap_f_dcn + int(ovf[0]))
            if ovf[1] > 0:
                self.cap_a = segments.pow2_capacity(2 * self.cap_a + int(ovf[1]))
                if hier_on:
                    self.cap_a_dcn = segments.pow2_capacity(
                        2 * self.cap_a_dcn + int(ovf[1]))
            _check_exchange_caps(self.num_dev, freq=self.cap_f,
                                 exchange_a=self.cap_a)
        else:
            self._overflow_exhausted(
                "line-building",
                f"freq={int(ovf[0])}, exchange_a={int(ovf[1])}")
        self.lines = line_cols  # jv, code, v1, v2 — device-resident
        self.n_rows = n_rows
        plan = host_gather(plan).reshape(self.num_dev, 5)[0]
        if os.environ.get("RDFIND_DEBUG_PLAN"):
            print(f"debug plan (per-device maxima): lines_b={int(plan[0])} "
                  f"lines_b_dcn={int(plan[1])} pairs={int(plan[2])} "
                  f"giant_rows={int(plan[3])} giant_pairs={int(plan[4])}",
                  file=sys.stderr, flush=True)
        self.cap_b = _headroom(plan[0])
        self.cap_b_dcn = _headroom(plan[1]) if hier_on else 0
        # Bounded-memory streaming: when the measured per-device pair load
        # exceeds the row budget, the pair phase runs as n_pass dep-slice
        # passes over the resident join lines, each with ~1/n_pass the
        # buffers (the windowed-merge intent of BulkMergeDependencies
        # .scala:96-104, as multi-pass streaming instead of disk spill).
        budget = int(os.environ.get("RDFIND_PAIR_ROW_BUDGET",
                                    PAIR_ROW_BUDGET))
        full_load = int(plan[2]) + 2 * int(plan[4])
        self.n_pass = max(1, -(-full_load // budget))
        # Plan maxima stashed for elastic resume: adopting a snapshot's pass
        # count (_adopt_n_pass) re-derives the per-pass caps from these same
        # measured numbers rather than fingerprinting mesh-sized state.
        self._plan_pairs = int(plan[2])
        self._plan_giant_pairs = int(plan[4])
        self.cap_p = _headroom(int(plan[2]) // self.n_pass, floor=1 << 10)
        self.cap_g = _headroom(plan[3])
        self.cap_gp = _headroom(2 * int(plan[4]) // self.n_pass,
                                floor=1 << 10)
        # Exchange C per-(src, dst) capacity: the deduped pair partials are
        # hash-spread over dep-capture owners, so the expected per-destination
        # share is (pairs + giant pairs) / D; overflow retries cover skew.
        self.cap_c = _headroom((self.cap_p + self.cap_gp)
                               // max(self.num_dev, 1), floor=1 << 10)
        # Exchange C's DCN hop carries each host's combined partials: L
        # sources' worth of per-destination share, halved for the expected
        # same-key overlap within a host (heuristic — pair keys are not
        # measurable pre-pass; the overflow ladder owns the tail).
        self.cap_c_dcn = (_headroom(
            self.hier[1] * ((self.cap_p + self.cap_gp)
                            // max(self.num_dev, 1)) // 2,
            floor=1 << 10) if hier_on else 0)
        self._check_pair_caps()
        if stats is not None:
            metrics.gauge_set(stats, "n_pair_passes", self.n_pass)

        # P2b: load-aware placement of the measured hot tail.
        self._maybe_rebalance()

        # P3: capture table (retry on B overflow).
        for _ in range(max_retries):
            pend = [exchange.log_exchange(
                stats, "exchange_b", num_dev=self.num_dev,
                capacity=self.cap_b,
                lanes=_LANES_EXCHANGE_B + (1 if hier_on else 0),
                hosts=self.hosts, hier=hier_on,
                dcn_capacity=self.cap_b_dcn if hier_on else None)]
            t0 = time.perf_counter() if self._timed else 0.0
            with watchdog.collective(
                    "captures", sum(e.get("bytes", 0) for e in pend)):
                out = _captures_step(*self.lines, self.n_rows, mesh=mesh,
                                     cap_exchange_b=self.cap_b,
                                     cap_exchange_b_dcn=self.cap_b_dcn,
                                     hier=self.hier,
                                     dcn_chunks=self.dcn_chunks)
                if self._timed:
                    jax.block_until_ready(out)
                    exchange.log_dispatch_timing(
                        stats, pend, (time.perf_counter() - t0) * 1e3)
                *tbl, n_caps, ovf_b = out
                ovf_b = int(host_gather(ovf_b)[0])
            if faults.overflow_injected("overflow@captures"):
                ovf_b = max(ovf_b, 1)
            if ovf_b == 0:
                break
            self._count_overflow_retry("capture-count", site="exchange_b")
            self.cap_b = segments.pow2_capacity(2 * self.cap_b + ovf_b)
            if hier_on:
                self.cap_b_dcn = segments.pow2_capacity(
                    2 * self.cap_b_dcn + ovf_b)
            _check_caps(exchange_b=self.num_dev * self.cap_b)
        else:
            self._overflow_exhausted("capture-count", f"exchange_b={ovf_b}")
        self.tbl = tbl  # tc, tv1, tv2, tcnt — device-resident, capture-owned
        self.n_caps = n_caps
        # The PLAN-time capacities (deterministic per workload+config, unlike
        # the grown retry caps) — part of every progress fingerprint.  DCN-hop
        # capacities join the dict only in hierarchical mode, so flat runs
        # keep their historical fingerprints (checkpoint compatibility).
        self._planned_caps = dict(
            freq=self.cap_f, exchange_a=self.cap_a, exchange_b=self.cap_b,
            pairs=self.cap_p, exchange_c=self.cap_c, giant_rows=self.cap_g,
            giant_pairs=self.cap_gp)
        if hier_on:
            self._planned_caps.update(
                freq_dcn=self.cap_f_dcn, exchange_a_dcn=self.cap_a_dcn,
                exchange_b_dcn=self.cap_b_dcn, exchange_c_dcn=self.cap_c_dcn)
        if stats is not None:
            metrics.struct_set(stats, "planned_caps",
                               dict(self._planned_caps))
            # The sketch/containment stages (sharded strategies 2/3) contract
            # in the resolved cooc dtype — and, on the packed Pallas kernel,
            # at the resolved plane width (int4 nibble planes double the
            # K-dim per MXU pass); record both for bench/debug parity with
            # the single-chip strategies.
            from ..ops import cooc as cooc_ops
            metrics.gauge_set(stats, "cooc_dtype",
                              cooc_ops.resolved_cooc_dtype())
            metrics.gauge_set(stats, "plane_bits",
                              cooc_ops.resolved_plane_bits())
            metrics.struct_set(stats, "kernel_resolution",
                               cooc_ops.resolution_report())

        # Data plane (obs/datastats.py): the one-shot distribution snapshot
        # (on-device log2 histograms over the resident lines + capture
        # table, O(32) host bytes each) and the plan-time cap-utilization
        # fractions — measured demand vs the headroomed capacities above.
        # Consumer-gated: without a live consumer this costs two flag checks.
        if self._datastats_on and stats is not None:
            used = dict(freq=raw_f, exchange_a=raw_a,
                        exchange_b=int(plan[0]),
                        pairs=int(plan[2]) // self.n_pass,
                        giant_rows=int(plan[3]),
                        giant_pairs=2 * int(plan[4]) // self.n_pass)
            if hier_on:
                used.update(freq_dcn=raw_fd, exchange_a_dcn=raw_ad,
                            exchange_b_dcn=int(plan[1]))
            datastats.publish_cap_utilization(stats, self._planned_caps,
                                              used)
            self._collect_datastats()

        # Integrity plane (obs/integrity.py): digest the resident stage
        # state — the join lines after exchanges A/B + rebalance, and the
        # capture table after exchange C — as four psum'd lanes in one
        # device dispatch, O(4) ints pulled however large the state is.
        if self._integrity_on and stats is not None:
            self._collect_stage_digests()

    def _collect_datastats(self):
        """One device dispatch for the data plane's distribution snapshot:
        the join-line size histogram and giant-line share over the resident
        rows, and the capture support spectrum over the capture table."""
        # "Giant" here is the pair phase's absolute backstop (load >
        # cap_pairs/4): the skew-relative threshold is per-kernel state, but
        # the backstop is the bound every configuration shares.
        prog = _stage_datastats(self.mesh,
                                giant_load=max(int(self.cap_p) // 4, 1))
        hist, chist, sc = prog(self.lines[0], self.n_rows, self.tbl[3],
                               self.n_caps)
        # Replicated P() outputs: one logical copy single-process, stacked
        # per-host copies after a multi-process allgather — either way the
        # first row is the (already psum'd) answer.
        hist = np.asarray(host_gather(hist)).reshape(-1, 32)[0]
        chist = np.asarray(host_gather(chist)).reshape(-1, 32)[0]
        n_lines, max_line, n_giant, n_capt, max_sup = (
            int(x) for x in np.asarray(host_gather(sc)).reshape(-1, 5)[0])
        datastats.publish_line_stats(
            self.stats, hist=datastats.hist_from_bins(hist),
            n_lines=n_lines, max_line=max_line, giant_lines=n_giant,
            source="sharded")
        datastats.publish_capture_spectrum(
            self.stats, hist=datastats.hist_from_bins(chist),
            n_captures=n_capt, max_support=max_sup, source="sharded")

    def _collect_stage_digests(self):
        """One device dispatch for the integrity plane's resident-state
        digests: two order/mesh-invariant lanes each for the join-line rows
        (the exchange A/B commit point) and the capture table (exchange C)."""
        lanes = _stage_digest(self.mesh)(*self.lines, self.n_rows,
                                         *self.tbl, self.n_caps)
        lanes = np.asarray(host_gather(lanes)).reshape(-1, 4)[0]
        la, lb, ca, cb = (int(x) & integrity.MASK32 for x in lanes)
        integrity.publish_stage(self.stats, "lines", la, lb)
        integrity.publish_stage(self.stats, "captures", ca, cb)

    def _host_digest(self, blocks, block_layout):
        """Host replica of one pass's digest lanes over its pulled or
        snapshot-loaded blocks (obs/integrity.py)."""
        if block_layout == "sketch":
            return integrity.digest_sketch_rows(blocks[0], self.ha_bits)
        return integrity.digest_rows(blocks)

    def _verify_snapshot(self, resumed, what, block_layout):
        """Digest-attested resume: recompute each loaded pass's content
        digest (AFTER any re-shard — the digest is order-invariant, so the
        _reshard_pass_rows permutation washes out) against the digest lanes
        persisted in its tail-counter tuple.  A mismatch is a clean miss for
        that pass plus a named `integrity` degradation — never a corrupted
        resume; RDFIND_INTEGRITY_STRICT=1 fails the run instead."""
        out = {}
        for p, (blocks_p, tele_p) in sorted(resumed.items()):
            blocks_p = faults.maybe_flip("flip@snapshot", blocks_p,
                                         pass_idx=p)
            ok = len(tele_p) >= _N_TAIL
            if ok:
                want = integrity.lanes_to_digest(tele_p[-2], tele_p[-1])
                ok = self._host_digest(blocks_p, block_layout) == want
            if ok:
                out[p] = (blocks_p, tele_p)
                continue
            if integrity.strict():
                raise integrity.IntegrityError(
                    f"{what}: snapshot digest mismatch at pass {p} "
                    f"(RDFIND_INTEGRITY_STRICT=1)")
            faults.record_degradation(self.stats, what, "integrity_miss",
                                      site="snapshot", **{"pass": p})
            integrity.note_mismatch(self.stats, site="snapshot", stage=what,
                                    pass_idx=p)
        return out

    def _verify_pull(self, blocks, tele, p, what, block_layout, cols, n_out):
        """Verify one freshly pulled pass against its device digest lanes.

        Host pulls are pure reads of committed device state, so in default
        mode a mismatch re-pulls (bounded by RDFIND_PULL_RETRIES) before it
        is accepted as real: a transient flip on the host path is REPAIRED
        and the output stays bit-identical.  Strict mode fails fast on the
        first mismatch (consistent with RDFIND_STRICT disabling pull
        retries); a persistent mismatch in default mode degrades flagged —
        the corrupt pass is named, never silently committed."""
        want = integrity.lanes_to_digest(tele[-2], tele[-1])
        blocks = faults.maybe_flip("flip@host_pull", blocks, pass_idx=p)
        if self._host_digest(blocks, block_layout) == want:
            return blocks
        if integrity.strict():
            raise integrity.IntegrityError(
                f"{what}: host-pull digest mismatch at pass {p} "
                f"(RDFIND_INTEGRITY_STRICT=1)")
        tries = max(1, int(os.environ.get("RDFIND_PULL_RETRIES", "3")))
        for _ in range(tries):
            blocks = self.collect_blocks(cols, n_out)
            if self._host_digest(blocks, block_layout) == want:
                integrity.note_mismatch(self.stats, site="host_pull",
                                        stage=what, pass_idx=p,
                                        repaired=True)
                return blocks
        faults.record_degradation(self.stats, what, "integrity_miss",
                                  site="host_pull", **{"pass": p})
        integrity.note_mismatch(self.stats, site="host_pull", stage=what,
                                pass_idx=p)
        return blocks

    def _agreement_payload(self, blocks, p, block_layout) -> list:
        """This host's [pass, digest_a, digest_b] rows for the pass-commit
        collective (multi-host digest agreement, PR 15): the RECOMPUTED
        block digest, compared across hosts after the batched allgather."""
        a, b = self._host_digest(blocks, block_layout)
        return [float(p), float(a), float(b)]

    def _agreement_check(self, rows, p, what) -> None:
        """Compare the allgathered digest rows.  A divergent replica
        surfaces as a named IntegrityError on EVERY host — each decides
        from identical allgathered state, so no host wedges a later
        collective against inconsistent peers.  Runs only when the
        integrity knob is on (the env must agree across hosts, same
        contract as RDFIND_TRACE)."""
        if bool((rows.max(axis=0) != rows.min(axis=0)).any()):
            raise integrity.IntegrityError(
                f"{what}: replica digest divergence at pass {p}: "
                f"{rows.tolist()}")

    def _check_replica_agreement(self, blocks, tele, p, what, block_layout):
        """Standalone form (one allgather); the pass executor instead rides
        the coalesced pass_commit collective."""
        rows = allgather_host_values(
            self._agreement_payload(blocks, p, block_layout),
            site="pass_commit")
        self._agreement_check(rows, p, what)

    def _maybe_rebalance(self):
        """Greedy least-loaded reassignment of hot lines (the reference's
        LoadBasedPartitioner semantics over measured loads)."""
        if self.num_dev <= 1:
            return
        # Full pair budget (all passes), matching the pair phase's effective
        # giant threshold so both stages share one load model.
        hot_jv, hot_len, dev_load = _hotlines_step(
            self.lines[0], self.n_rows, mesh=self.mesh, skew=self.skew,
            cap_pairs=self.cap_p * self.n_pass)
        hot_jv = host_gather(hot_jv).reshape(self.num_dev, -1)
        hot_len = host_gather(hot_len).reshape(self.num_dev, -1)
        cur = host_gather(dev_load).astype(np.float64)  # (D,) total load
        mask = hot_jv != int(SENTINEL)
        if not mask.any():
            return
        src = np.nonzero(mask)[0]
        jvs = hot_jv[mask]
        lens = hot_len[mask].astype(np.int64)
        loads = lens.astype(np.float64) * (lens - 1)

        # Base = everything not individually reassignable (cold lines + hot
        # lines beyond the per-device report cap).
        base = cur.copy()
        np.add.at(base, src, -loads)
        bins = base.copy()
        dest = np.empty(len(jvs), np.int64)
        for k in np.argsort(-loads):  # heaviest first, least-loaded bin wins
            d = int(np.argmin(bins))
            dest[k] = d
            bins[d] += loads[k]
        if self.stats is not None:
            mean = max(cur.mean(), 1.0)
            metrics.struct_set(self.stats, "rebalance", dict(
                hot_lines=int(len(jvs)),
                moved_lines=int((dest != src).sum()),
                load_max_over_mean_before=round(cur.max() / mean, 3),
                load_max_over_mean_planned=round(bins.max() / mean, 3)))
        if bins.max() >= cur.max() * _REBALANCE_MIN_GAIN:
            metrics.struct_update(self.stats, "rebalance", moved_lines=0)
            return  # hash placement is already close enough to balanced
        moving = dest != src
        if not moving.any():
            return
        mj, md, ml = jvs[moving], dest[moving], lens[moving]
        order = np.argsort(mj)
        mj, md, ml = mj[order], md[order], ml[order]
        # Per-(src, dst) moved-row volume bounds the exchange capacity.
        vol = np.zeros((self.num_dev, self.num_dev), np.int64)
        np.add.at(vol, (src[moving], dest[moving]), lens[moving])
        cap_move = _headroom(int(vol.max()), floor=1 << 8)
        h = segments.pow2_capacity(len(mj))
        moved_jv = np.full(h, int(SENTINEL), np.int32)
        moved_jv[:len(mj)] = mj
        moved_dest = np.zeros(h, np.int32)
        moved_dest[:len(mj)] = md
        for _ in range(self.max_retries):
            pend = [exchange.log_exchange(self.stats, "rebalance",
                                          num_dev=self.num_dev,
                                          capacity=cap_move,
                                          lanes=_LANES_REBALANCE,
                                          rows=int(lens[moving].sum()),
                                          hosts=self.hosts,
                                          hier=self.hier is not None)]
            t0 = time.perf_counter() if self._timed else 0.0
            with watchdog.collective(
                    "rebalance", sum(e.get("bytes", 0) for e in pend)):
                out = _rebalance_step(*self.lines, self.n_rows,
                                      moved_jv, moved_dest,
                                      mesh=self.mesh, cap_move=cap_move,
                                      hier=self.hier,
                                      dcn_chunks=self.dcn_chunks)
                if self._timed:
                    jax.block_until_ready(out)
                    exchange.log_dispatch_timing(
                        self.stats, pend, (time.perf_counter() - t0) * 1e3)
                *cols, n_rows, ovf = out
                ovf = int(host_gather(ovf)[0])
            if faults.overflow_injected("overflow@rebalance"):
                ovf = max(ovf, 1)
            if ovf == 0:
                break
            self._count_overflow_retry("rebalance", site="rebalance")
            cap_move = segments.pow2_capacity(2 * cap_move + ovf)
        else:
            # Ladder rung "skip": rebalancing is an output-neutral placement
            # optimization (exchanges B/C route by capture hash either way),
            # so the cheapest safe degradation is to keep hash placement.
            if faults.strict_mode():
                raise RuntimeError(
                    f"rebalance overflow persisted after {self.max_retries} "
                    f"retries ({ovf})")
            faults.record_degradation(self.stats, "rebalance", "skip",
                                      overflow=int(ovf))
            metrics.struct_update(self.stats, "rebalance", moved_lines=0)
            return
        self.lines = cols
        self.n_rows = n_rows

    def _count_overflow_retry(self, phase: str, site: str | None = None,
                              pass_idx: int | None = None) -> None:
        """Ledger + telemetry for one capacity-grow retry (ladder rung 0).
        `pass_idx` stamps pass-loop rungs so the forecast differential can
        order advisories against the rung that confirmed them."""
        if self.stats is not None:
            metrics.counter_add(self.stats, "n_overflow_retries")
            if site is not None:
                exchange.log_exchange_retry(self.stats, site)
        detail = {} if pass_idx is None else {"pass": int(pass_idx)}
        faults.record_degradation(self.stats, phase, "grow", **detail)

    def _overflow_exhausted(self, phase: str, detail: str):
        """Grow retries exhausted with no further rung for this phase: strict
        mode keeps the historical fail-fast RuntimeError; otherwise escalate
        straight to the single-device fallback (the discover entry points
        catch FallbackRequired and re-run with identical output)."""
        msg = (f"{phase} overflow persisted after {self.max_retries} retries "
               f"({detail})")
        if faults.strict_mode():
            raise RuntimeError(msg)
        raise faults.FallbackRequired(phase, detail)

    def _pair_caps(self):
        return dict(cap_pairs=self.cap_p, cap_exchange_c=self.cap_c,
                    cap_giant=self.cap_g, cap_giant_pairs=self.cap_gp,
                    skew=self.skew, cap_exchange_c_dcn=self.cap_c_dcn,
                    hier=self.hier, dcn_chunks=self.dcn_chunks)

    def _grow_pair_caps(self, ovf):
        if ovf[0] > 0:
            self.cap_p = segments.pow2_capacity(2 * self.cap_p + int(ovf[0]))
        if ovf[1] > 0:
            self.cap_c = segments.pow2_capacity(2 * self.cap_c + int(ovf[1]))
        if ovf[2] > 0:
            self.cap_g = segments.pow2_capacity(2 * self.cap_g + int(ovf[2]))
        if ovf[3] > 0:
            self.cap_gp = segments.pow2_capacity(2 * self.cap_gp + int(ovf[3]))
        if ovf[4] > 0:
            self.cap_c_dcn = segments.pow2_capacity(
                2 * self.cap_c_dcn + int(ovf[4]))
        self._check_pair_caps()

    def _check_pair_caps(self):
        # Local emission buffers count their own rows; exchanges B/C and the
        # giant-line all_gather count D x capacity.  The hierarchical DCN-hop
        # receive buffers (hosts x dcn_capacity) are strictly smaller than
        # their D x capacity hop-1 peers unless the dcn caps dominate.
        caps = dict(pair_stream=self.cap_p + self.cap_gp,
                    exchange_b=self.num_dev * self.cap_b,
                    exchange_c=self.num_dev * self.cap_c,
                    giant_gather=self.num_dev * self.cap_g)
        if self.hier is not None:
            caps.update(exchange_b_dcn=self.hosts * self.cap_b_dcn,
                        exchange_c_dcn=self.hosts * self.cap_c_dcn)
        _check_caps(**caps)

    def _adopt_n_pass(self, n_pass: int) -> None:
        """Re-derive the per-pass capacity plan for a snapshot's pass count.

        The caps come from the stashed plan maxima through the exact
        formulas __init__ used, so adoption reproduces the plan a fresh run
        at this n_pass would compute — grown/split state never leaks into a
        resumed attempt (cap doctrine: clean-pass output is
        capacity-independent)."""
        if int(n_pass) == self.n_pass:
            return
        self.n_pass = int(n_pass)
        self.cap_p = _headroom(self._plan_pairs // self.n_pass,
                               floor=1 << 10)
        self.cap_gp = _headroom(2 * self._plan_giant_pairs // self.n_pass,
                                floor=1 << 10)
        self.cap_c = _headroom((self.cap_p + self.cap_gp)
                               // max(self.num_dev, 1), floor=1 << 10)
        if self.hier is not None:
            self.cap_c_dcn = _headroom(
                self.hier[1] * ((self.cap_p + self.cap_gp)
                                // max(self.num_dev, 1)) // 2,
                floor=1 << 10)
        self._check_pair_caps()
        if self.stats is not None:
            metrics.gauge_set(self.stats, "n_pair_passes", self.n_pass)

    def _note_resume(self, *, vote_rounds=0, resharded_blocks=0,
                     resharded_bytes=0, **fields):
        """Accumulate elastic-resume lineage into the `elastic_resume`
        struct (count keys sum across phases, identity keys overwrite); the
        metrics shim mirrors it to the registry for Prometheus export."""
        if self.stats is None:
            return
        cur = self.stats.get("elastic_resume") or {}
        fields.update(
            to_num_dev=self.num_dev,
            vote_rounds=int(cur.get("vote_rounds", 0)) + int(vote_rounds),
            resharded_blocks=(int(cur.get("resharded_blocks", 0))
                              + int(resharded_blocks)),
            resharded_bytes=(int(cur.get("resharded_bytes", 0))
                             + int(resharded_bytes)))
        metrics.struct_update(self.stats, "elastic_resume", **fields)

    def _resolve_resume(self, snap, *, allow_adopt: bool) -> dict:
        """The per-phase resume decision: which committed passes to skip,
        under which pass count (possibly adopted from the snapshot).

        Single-process this is a local decision.  Multi-process it is the
        all-hosts-agree vote, batched into ONE allgather: each host
        contributes [has, stored n_pass, committed-pass bitmap as eight
        32-bit words] and every host derives the identical resume set from
        the identical allgathered rows — candidate partition only if every
        snapshot holder stored the same one, then the bitwise AND of the
        bitmap words across ALL hosts (a torn/missing/stale snapshot
        contributes zero words and shrinks the intersection — coarser
        resume, same results).  No host can skip its half of a collective
        and deadlock the mesh.  32-bit words are exact in the float64
        payload; eight of them cap the vote at 256 passes, so a host whose
        snapshot stores more votes has=0 (full re-run — a partition that
        size is outside every planner rung).

        `allow_adopt` is False after a split rung re-partitioned the phase
        mid-run: the snapshot's n_pass then no longer matches what THIS
        attempt must produce, and adoption would undo the split.

        Returns {pass_idx: (blocks, tele)} — empty means full re-run."""
        has = (snap is not None and bool(snap.parts) and snap.n_pass > 0
               and snap.num_dev > 0)
        if jax.process_count() == 1:
            if not has:
                return {}
            if snap.n_pass != self.n_pass:
                if not allow_adopt:
                    return {}
                self._adopt_n_pass(snap.n_pass)
                self._note_resume(adopted_n_pass=self.n_pass)
            return dict(snap.parts)
        n_words = 8
        if has and snap.n_pass > 32 * n_words:
            has = False
        vote = np.zeros(2 + n_words, np.float64)
        if has:
            vote[0] = 1.0
            vote[1] = float(snap.n_pass)
            for p in snap.parts:
                if 0 <= p < snap.n_pass:
                    w, bit = divmod(int(p), 32)
                    vote[2 + w] = float(int(vote[2 + w]) | (1 << bit))
        votes = allgather_host_values(vote, site="resume_vote")
        self._note_resume(vote_rounds=1)
        holders = votes[votes[:, 0] > 0]
        if holders.shape[0] == 0:
            return {}
        stored = {int(v) for v in holders[:, 1]}
        if len(stored) != 1:
            # Snapshot holders disagree on the partition (one host's file
            # predates a split rung): no pass can be common to all of them.
            return {}
        cand = stored.pop()
        if cand != self.n_pass and not allow_adopt:
            return {}
        # Intersect the committed bitmaps across ALL rows: non-holders
        # contributed zero words, so any missing/disagreeing host empties
        # the intersection (the missing-peer semantics of the old round 2).
        words = [-1] * n_words
        for row in votes:
            for w in range(n_words):
                words[w] &= int(row[2 + w])
        passes = [p for p in range(cand)
                  if words[p // 32] & (1 << (p % 32))]
        if not passes:
            return {}
        # A non-empty intersection proves every host holds these passes, so
        # snap.parts is present and covers them on this host too.
        if cand != self.n_pass:
            self._adopt_n_pass(cand)
            self._note_resume(adopted_n_pass=self.n_pass)
        return {p: snap.parts[p] for p in passes}

    def collect_blocks(self, cols, n_out):
        """Per-device compacted outputs -> host rows (ONE batched pull)."""
        *cols_h, n_out_h = host_gather_many(list(cols) + [n_out])
        block = cols_h[0].shape[0] // self.num_dev
        keep = np.zeros(cols_h[0].shape[0], bool)
        for dev in range(self.num_dev):
            keep[dev * block: dev * block + int(n_out_h[dev])] = True
        return [c[keep] for c in cols_h]

    def capture_table(self):
        """Host capture table in canonical (code, v1, v2) order.  Each distinct
        capture lives on exactly one device (hash-routed): no duplicates.

        Size budget: the S2L lattice generation is host-side numpy over this
        table (like the reference's driver-side plan construction), so the
        table must fit one host.  At 4x int64 per capture, the default
        budget of 2^27 captures is ~4 GiB of host RAM — far above any
        frequent-capture table a single v5e chip's HBM-resident join could
        have produced, but a real guard at the DBpedia-scale configs
        (BASELINE.json 3-4), which need sharded lattice generation, not a
        bigger host pull.  RDFIND_HOST_CAPTURES_BUDGET overrides.
        """
        total = int(host_gather(self.n_caps).sum())
        budget = int(os.environ.get("RDFIND_HOST_CAPTURES_BUDGET", 1 << 27))
        if total > budget:
            raise ValueError(
                f"capture table ({total} captures) exceeds the host-side "
                f"lattice budget ({budget}); raise "
                f"RDFIND_HOST_CAPTURES_BUDGET or use strategy 0 "
                f"(fully device-resident)")
        tc, tv1, tv2, tcnt = self.collect_blocks(self.tbl, self.n_caps)
        cap_code = tc.astype(np.int64)
        cap_v1 = tv1.astype(np.int64)
        cap_v2 = tv2.astype(np.int64)
        dep_count = tcnt.astype(np.int64)
        order = np.lexsort((cap_v2, cap_v1, cap_code))
        return (cap_code[order], cap_v1[order], cap_v2[order], dep_count[order])

    def _pass_args(self, p: int):
        return (jnp.full(1, p, jnp.int32), jnp.full(1, self.n_pass, jnp.int32))

    def _run_passes(self, step, what: str, *, site: str = "cind",
                    phase_key: str | None = None, fp_extra=None,
                    ledger_sites=("exchange_c", "giant_gather"),
                    block_layout: str = "rows"):
        """Pipelined dep-slice pass executor — the shared scaffolding of
        run_cinds and run_cooc.  `step(pass_args)` must return device arrays
        (cols, n_out, telemetry) with telemetry an exchange.pack_counters
        lane array of _TELE_LANES scalars whose first _N_OVF lanes are the
        overflow counters.

        Fault-domain hardening on top of the pipelined schedule:

          * every pass verdict carries the `overflow@{site}` injection gate
            and every commit the `preempt@discover` gate (runtime/faults);
          * exhausted grow retries escalate the degradation ladder instead of
            dying: double n_pass + shrink per-pass caps (up to
            RDFIND_MAX_PASS_SPLITS times), then FallbackRequired — the
            discover entry point re-runs single-device with identical
            output.  RDFIND_STRICT=1 keeps the historical RuntimeError;
          * with a ProgressStore attached, each committed pass's host blocks
            are snapshotted asynchronously (atomic + fsynced off the
            critical path) and a preempted run's successor replays only the
            unfinished passes (stats["resumed_passes"]).  Snapshots are
            mesh-portable: the fingerprint is num_dev-free, blocks are
            re-sharded on load (_reshard_pass_rows), the stored n_pass may
            be adopted, and multi-host runs agree on the resume set through
            _resolve_resume's allgather vote before any host skips a pass.

        Schedule: pass p+1's jitted step is enqueued as soon as pass p's is
        (up to dispatch.pass_depth() passes in flight), the packed telemetry
        of the head pass is staged to host asynchronously, and the head's
        block pull (collect_blocks) runs while its successors compute — so a
        clean pass costs exactly TWO host round trips (one control pull, one
        batched data pull), both overlapped with enqueued device work, versus
        the 3+ serial blocking host_gathers of the pre-pipelined loop.

        Optimistic dispatch: successors are enqueued before the head's
        overflow verdict is known.  On overflow the in-flight successors are
        DISCARDED (their programs finish on device; the results are simply
        never read), capacities grow, and execution resumes from the failed
        pass — completed passes are never re-run.  The rollback is sound
        because passes only read the immutable device-resident lines/table
        and partition the dependent captures, so a discarded successor has no
        side effects and its re-run under larger caps emits the same exact
        counts.  RDFIND_SYNC_PASSES=1 forces the serial schedule (depth 1,
        identical output by construction — differentially tested).

        Slices partition the dependent captures, so per-pass blocks
        concatenate directly.  Returns (host blocks, tail counters transposed
        to per-counter tuples of ints); publishes dispatch telemetry into
        self.stats."""
        phase_key = phase_key or site
        seq = self._phase_seq
        self._phase_seq += 1
        n_splits = 0
        while True:
            try:
                return self._attempt_passes(step, what, site, phase_key, seq,
                                            fp_extra, ledger_sites,
                                            block_layout=block_layout,
                                            allow_adopt=(n_splits == 0))
            except _PairCapsExhausted as e:
                if faults.strict_mode():
                    raise RuntimeError(e.msg) from None
                if n_splits < faults.max_pass_splits():
                    # Ladder rung "split": double the dep-slice pass count so
                    # each pass carries ~half the load, shrink the per-pass
                    # buffers to match, and re-run the phase from scratch
                    # (completed parts of THIS attempt partition differently
                    # under the new n_pass and cannot be reused).
                    n_splits += 1
                    faults.record_degradation(self.stats, what, "split",
                                              n_pass=self.n_pass * 2)
                    self.n_pass *= 2
                    self.cap_p = max(
                        segments.pow2_capacity(self.cap_p // 2), 1 << 10)
                    self.cap_gp = max(
                        segments.pow2_capacity(self.cap_gp // 2), 1 << 10)
                    self.cap_c = _headroom(
                        (self.cap_p + self.cap_gp) // max(self.num_dev, 1),
                        floor=1 << 10)
                    if self.hier is not None:
                        self.cap_c_dcn = _headroom(
                            self.hier[1] * ((self.cap_p + self.cap_gp)
                                            // max(self.num_dev, 1)) // 2,
                            floor=1 << 10)
                    self._check_pair_caps()
                    if self.stats is not None:
                        metrics.gauge_set(self.stats, "n_pair_passes",
                                          self.n_pass)
                    continue
                raise faults.FallbackRequired(what, e.msg) from None

    def _attempt_passes(self, step, what, site, phase_key, seq, fp_extra,
                        ledger_sites=("exchange_c", "giant_gather"), *,
                        block_layout="rows", allow_adopt=True):
        """One ladder attempt of the pipelined pass loop at the current
        n_pass/caps (see _run_passes for the schedule contract)."""
        d = dispatch.DispatchStats(pull_base=self._pull_base)
        t_attempt = time.perf_counter()
        meter = _SkewMeter(self.stats, what)
        stage = fp = None
        resumed = {}
        # Elastic resume: the phase fingerprint is mesh-independent (what
        # the pass PRODUCES), the snapshot meta carries how it was
        # partitioned (num_dev, n_pass), and multi-host runs agree on the
        # resume set through _resolve_resume's vote before any host skips a
        # collective.  Every host must attach a ProgressStore under the same
        # checkpoint config or none may (same contract as RDFIND_TRACE).
        progress = self.progress
        if progress is not None:
            stage, fp = progress.phase_fp(
                phase_key, seq,
                extra=dict(what=what, min_support=int(self.min_support),
                           **(fp_extra or {})))
            snap = progress.load(stage, fp)
            resumed = self._resolve_resume(snap, allow_adopt=allow_adopt)
            if resumed and snap.num_dev != self.num_dev:
                if block_layout == "rows":
                    nbytes = sum(np.asarray(b).nbytes
                                 for blocks_p, _ in resumed.values()
                                 for b in blocks_p)
                    resumed = {
                        p: (_reshard_pass_rows(blocks_p, self.num_dev),
                            tele_p)
                        for p, (blocks_p, tele_p) in resumed.items()}
                    self._note_resume(from_num_dev=int(snap.num_dev),
                                      resharded_blocks=len(resumed),
                                      resharded_bytes=nbytes)
                else:
                    # Sketch layout: per-device count-min partials fold
                    # through a saturating add, which is grouping-insensitive
                    # (saturation lemma) — no re-routing needed, the
                    # mesh-agnostic fold in _ha_build_table absorbs any
                    # device count.
                    self._note_resume(from_num_dev=int(snap.num_dev))
            if resumed and self._integrity_on:
                resumed = self._verify_snapshot(resumed, what, block_layout)
        # Cap-exhaustion forecaster (obs/forecast.py): fed each committed
        # pass's utilization fractions, it names the cap and predicted pass
        # BEFORE the grow/split rungs fire.  Resolved once per attempt,
        # AFTER resume resolution may have adopted the snapshot's n_pass.
        fc = (forecast.Forecaster(self.stats, self.n_pass, phase=what)
              if self.stats is not None and forecast.enabled() else None)
        # Phase clock: zero-cost no-op unless a skew consumer is live.
        now = time.perf_counter if meter.active else (lambda: 0.0)
        parts = [None] * self.n_pass
        teles = [None] * self.n_pass
        tries = [0] * self.n_pass
        for p, (blocks_p, tele_p) in resumed.items():
            if 0 <= p < self.n_pass:
                parts[p] = [np.asarray(b) for b in blocks_p]
                teles[p] = tuple(int(x) for x in tele_p)
        n_res = sum(1 for x in parts if x is not None)
        if n_res:
            if self.stats is not None:
                metrics.counter_add(self.stats, "resumed_passes", n_res)
            tracer.instant("elastic_resume", cat=tracer.CAT_RUN,
                           stage=stage or "", what=what,
                           resumed_passes=n_res, num_dev=self.num_dev)
        depth = dispatch.pass_depth()
        inflight = collections.deque()  # (p, cols, n_out, telemetry)
        p_next = 0
        while p_next < self.n_pass or inflight:
            # One `pass` span per committed head pass; the optimistic
            # dispatches of its successors, the control/block pulls and the
            # exchange-ledger instants are its children in the trace.
            head = inflight[0][0] if inflight else p_next
            with tracer.span("pass", cat=tracer.CAT_PASS, what=what,
                             **{"pass": head}):
                t_fill = now()
                while p_next < self.n_pass and len(inflight) < depth:
                    if parts[p_next] is not None:  # resumed from a checkpoint
                        p_next += 1
                        continue
                    with tracer.span("dispatch", cat=tracer.CAT_DISPATCH,
                                     what=what, **{"pass": p_next}):
                        # Every dispatched pass moves its full fixed-shape
                        # exchange-C and giant-gather buffers — including
                        # optimistically dispatched passes later discarded by
                        # a rollback, so the ledger records dispatches, not
                        # committed passes.
                        hier_on = self.hier is not None
                        pend = []
                        # ledger_sites names the exchanges this phase's step
                        # actually dispatches: the sketch-build phase of the
                        # sharded half-approx round has no exchange C (pairs
                        # never leave the device), so it must not ledger one.
                        if "exchange_c" in ledger_sites:
                            pend.append(exchange.log_exchange(
                                self.stats, "exchange_c",
                                num_dev=self.num_dev, capacity=self.cap_c,
                                lanes=_LANES_EXCHANGE_C, hosts=self.hosts,
                                hier=hier_on,
                                dcn_capacity=(self.cap_c_dcn if hier_on
                                              else None)))
                        # The giant-line all_gather is topology-oblivious
                        # (whole lines replicate everywhere) — hier=False, but
                        # host attribution still splits its ICI/DCN bytes.
                        if "giant_gather" in ledger_sites:
                            pend.append(exchange.log_exchange(
                                self.stats, "giant_gather",
                                num_dev=self.num_dev,
                                capacity=min(
                                    self.cap_g,
                                    self.lines[0].shape[0] // self.num_dev),
                                lanes=_LANES_GIANT, hosts=self.hosts))
                        t0 = time.perf_counter() if self._timed else 0.0
                        cols, n_out, tele = step(self._pass_args(p_next))
                        if self._timed:
                            jax.block_until_ready((cols, n_out, tele))
                            exchange.log_dispatch_timing(
                                self.stats, pend,
                                (time.perf_counter() - t0) * 1e3)
                        dispatch.stage_to_host([tele])
                    inflight.append((p_next, cols, n_out, tele))
                    p_next += 1
                if not inflight:
                    break  # everything left was already resumed
                d.saw_in_flight(len(inflight))
                p, cols, n_out, tele = inflight.popleft()
                t_counters = now()
                # The counters pull drains the head pass's whole device
                # program (exchange C + giant gather included) — the
                # deadman's payload estimate is the pass's exchange volume.
                pass_nbytes = self.num_dev * (
                    self.cap_c * _LANES_EXCHANGE_C + self.cap_g * _LANES_GIANT
                ) * 4
                with watchdog.collective("pairs", pass_nbytes):
                    tele_h = d.timed_pull(
                        lambda: exchange.unpack_counters(host_gather(tele),
                                                         _TELE_LANES,
                                                         self.num_dev),
                        overlapped=bool(inflight), what="pull-counters")
                ovf = tele_h[:_N_OVF]
                if faults.overflow_injected(f"overflow@{site}", pass_idx=p):
                    ovf = np.maximum(np.asarray(ovf), 1)
                if int(ovf.sum()) != 0:
                    tries[p] += 1
                    if tries[p] >= self.max_retries:
                        if self.stats is not None:
                            d.publish(self.stats)  # keep telemetry over rungs
                        raise _PairCapsExhausted(
                            f"{what} overflow persisted after "
                            f"{self.max_retries} retries "
                            f"({np.asarray(ovf).tolist()})")
                    self._count_overflow_retry(what, site="exchange_c",
                                               pass_idx=p)
                    inflight.clear()  # discard optimistic successors
                    self._grow_pair_caps(ovf)
                    d.n_cap_retries += 1
                    p_next = p  # resume from the failed pass only
                    continue
                t_blocks = now()
                with watchdog.collective("pairs", pass_nbytes):
                    parts[p] = d.timed_pull(
                        lambda: self.collect_blocks(cols, n_out),
                        overlapped=bool(inflight), what="pull-blocks")
                teles[p] = tuple(int(x) for x in tele_h[_N_OVF:])
                agree_payload = None
                if self._integrity_on:
                    parts[p] = self._verify_pull(parts[p], teles[p], p, what,
                                                 block_layout, cols, n_out)
                    # Digest agreement rides the coalesced pass_commit
                    # collective below (single-process the rows trivially
                    # agree; multi-process this is the PR-15 check at zero
                    # extra collectives).
                    agree_payload = self._agreement_payload(parts[p], p,
                                                            block_layout)
                if self._datastats_on or fc is not None:
                    # Per-pass cap-utilization trajectory from the tail
                    # telemetry lanes (already pulled — zero extra host
                    # traffic).  The lanes are global psum totals, so the
                    # fractions are average-per-device estimates; skew puts
                    # the max higher, which the overflow ladder owns.
                    ngl_p, ngp_p, npt_p = teles[p][:3]
                    fr = {"pairs": ((npt_p - ngp_p)
                                    / max(self.num_dev * self.cap_p, 1)),
                          "giant_pairs": (ngp_p
                                          / max(self.num_dev * self.cap_gp,
                                                1))}
                    metrics.gauge_set(None, "run_pass", p)
                    if self._datastats_on:
                        datastats.publish_pass_utilization(self.stats, p, fr)
                    if fc is not None:
                        fc.step(p, fr)
                t_commit = now()
                if tracer.enabled() or metrics.export_requested():
                    # Per-pass HBM watermark + allocation delta (near-cap
                    # warnings fire BEFORE the ladder has to) — sampled only
                    # with a live obs consumer so the disabled path stays
                    # free of per-pass host work.
                    obs_memory.sample(self.stats, label=f"{what} pass {p}")
                if progress is not None:
                    # Cumulative snapshot of every committed pass, written by
                    # a worker thread (atomic + fsynced) while successors
                    # compute.
                    progress.submit(stage, fp, {
                        i: (parts[i], teles[i]) for i in range(self.n_pass)
                        if parts[i] is not None},
                        num_dev=self.num_dev, n_pass=self.n_pass)
                if meter.active or agree_payload is not None:
                    # ONE batched per-pass collective carrying [pass,
                    # digest_a, digest_b?] + [phase breakdown?]: digest
                    # agreement and the skew meter used to cost one tiny
                    # allgather EACH — the gloo many-tiny-collectives abort
                    # scales with collective count, so they now share a
                    # payload.  Runs after progress.submit: a pass whose
                    # agreement later fails is digest-re-verified (clean
                    # miss) when its snapshot loads on resume.
                    t_end = now()
                    vec = meter.vec({
                        "exchange": (t_counters - t_fill) * 1e3,
                        "compute": (t_blocks - t_counters) * 1e3,
                        "pull": (t_commit - t_blocks) * 1e3,
                        "commit": (t_end - t_commit) * 1e3,
                    }) if meter.active else []
                    agree_head = agree_payload or []
                    rows = allgather_host_values(agree_head + vec,
                                                 site="pass_commit")
                    if agree_payload is not None:
                        self._agreement_check(rows[:, :3], p, what)
                    if meter.active:
                        meter.pass_committed_rows(
                            vec, rows[:, len(agree_head):])
                if faults.fires("preempt@discover", pass_idx=p):
                    if progress is not None:
                        progress.flush()  # the SIGTERM handler's analog
                    raise faults.Preempted(
                        f"injected preemption after {what} pass {p}")
        blocks = [np.concatenate([part[i] for part in parts])
                  for i in range(len(parts[0]))]
        if self.stats is not None:
            d.publish(self.stats)
            watchdog.publish(self.stats)
            metrics.gauge_set(self.stats, "cap_p_final", self.cap_p)
            # The overlap-efficiency row of this attempt (the DCN-chunk
            # autotuner input) and the cross-host skew verdict.
            metrics.struct_set(
                self.stats, "overlap",
                d.overlap_report((time.perf_counter() - t_attempt) * 1e3,
                                 n_passes=self.n_pass))
        meter.publish()
        if self._integrity_on and self.stats is not None:
            # Phase digest: the passes partition this phase's output rows,
            # so the wraparound sum of the per-pass lanes IS the phase's
            # digest — invariant to n_pass, row order, and mesh size.
            da = sum(int(t[-2]) for t in teles) & integrity.MASK32
            db = sum(int(t[-1]) for t in teles) & integrity.MASK32
            integrity.publish_stage(self.stats, phase_key, da, db,
                                    what=what, n_pass=self.n_pass)
        return blocks, tuple(zip(*teles))

    def run_cinds(self):
        """AllAtOnce finish over the device-resident lines."""
        def step(pass_args):
            out = _cind_step(*self.lines, self.n_rows, *self.tbl, self.n_caps,
                             jnp.int32(self.min_support), *pass_args,
                             mesh=self.mesh, **self._pair_caps())
            *cols, n_out, tele = out
            return cols, n_out, tele

        blocks, (ngl, ngp, npt, *_) = self._run_passes(step, "pair-phase",
                                                      site="cind",
                                                      phase_key="cind")
        if self.stats is not None:
            # max across passes: a mid-run cap_p growth shifts the giant
            # threshold between passes, so the last pass may see fewer giants
            # than an earlier one (ADVICE r5).
            metrics.gauge_set(self.stats, "n_giant_lines", max(ngl))
            metrics.gauge_set(self.stats, "n_giant_pairs", sum(ngp))
            # Emitted-pairs total (same stat the single-device models
            # publish): the pairs/s/chip numerator of the kernel-feed rows.
            metrics.counter_add(self.stats, "total_pairs", sum(npt))
        return blocks

    def _ha_build_table(self, fcode, fv1, fv2, fflag, n_flags, stat_key,
                        digest):
        """Round 1 of the sharded half-approximate 1/1: build per-pass
        per-device count-min partial tables over the level's pair stream
        (same ladder/progress machinery as the verification passes — an
        incomplete build would make the cut unsound), then fold + all-reduce
        them in ONE device dispatch and return the host copy of the global
        table.  Returns a numpy (ha_bits,) int32 array."""
        def step(pass_args):
            table, n_out, tele = _s2l_sketch_build(
                *self.lines, self.n_rows, fcode, fv1, fv2, fflag, n_flags,
                *pass_args, mesh=self.mesh, cap_pairs=self.cap_p,
                cap_giant=self.cap_g, cap_giant_pairs=self.cap_gp,
                skew=self.skew, ha_bits=self.ha_bits,
                ha_hashes=self.ha_hashes)
            return [table], n_out, tele

        blocks, (ngl, ngp, npt, *_) = self._run_passes(
            step, "HA sketch build", site="cooc", phase_key=f"{stat_key}:ha1",
            fp_extra={"flags": digest,
                      "ha": [self.ha_bits, self.ha_hashes, self.ha_thresh]},
            ledger_sites=("giant_gather",), block_layout="sketch")
        from ..ops import sketch
        # blocks[0] concatenates per-pass collect_blocks pulls of per-device
        # (bits,) partial tables — possibly committed at a DIFFERENT mesh
        # size (elastic resume), so the fold must not assume the row count
        # divides by num_dev.  Treat each partial as one row, zero-pad to
        # the mesh (zeros are the saturating fold's identity), and split the
        # rows evenly: the saturating add is grouping-insensitive
        # (saturation lemma, ops/sketch.py), so ANY arrangement folds to
        # the identical min(cap, true sum) table.
        parts = np.asarray(blocks[0], np.int32).reshape(-1, self.ha_bits)
        pad = -parts.shape[0] % self.num_dev
        if pad:
            parts = np.concatenate(
                [parts, np.zeros((pad, self.ha_bits), np.int32)])
        stacked = np.ascontiguousarray(parts.reshape(self.num_dev, -1))
        hier_on = self.hier is not None
        pend = [exchange.log_sketch_allreduce(
            self.stats, num_dev=self.num_dev, bits=self.ha_bits,
            hosts=self.hosts, hier=hier_on)]
        t0 = time.perf_counter() if self._timed else 0.0
        with watchdog.collective(
                "sketch", sum(e.get("bytes", 0) for e in pend)):
            out = _ha_reduce_step(make_global(stacked, self.mesh),
                                  mesh=self.mesh, bits=self.ha_bits,
                                  cap=sketch.MAX_COUNT_MIN_CAP,
                                  hier=self.hier)
            if self._timed:
                jax.block_until_ready(out)
                exchange.log_dispatch_timing(
                    self.stats, pend, (time.perf_counter() - t0) * 1e3)
            table = np.asarray(host_gather(out)).reshape(-1,
                                                         self.ha_bits)[0]
        if self.stats is not None:
            metrics.counter_add(self.stats, "ha_build_rounds")
            metrics.counter_add(self.stats, "total_pairs", sum(npt))
            metrics.counter_max(self.stats, "n_giant_lines", max(ngl))
            metrics.counter_add(self.stats, "n_giant_pairs", sum(ngp))
            metrics.gauge_set(self.stats, "ha_sketch_bits", self.ha_bits)
            metrics.gauge_set(self.stats, "ha_sketch_bytes",
                              self.ha_bits * 4)
            if self._datastats_on:
                # Sketch load factor as a cap-utilization row: occupied
                # counters vs table width — the dial for
                # RDFIND_SHARDED_HA_BITS (a saturated table still only
                # weakens the cut, never correctness).
                datastats.publish_cap_utilization(
                    self.stats, {"ha_sketch": self.ha_bits},
                    {"ha_sketch": int(np.count_nonzero(table))})
        return table

    def run_cooc(self, fcode, fv1, fv2, fflag, n_flags, stat_key):
        """S2L level verification over the device-resident lines.

        With RDFIND_SHARDED_HALF_APPROX on, runs the distributed two-round
        count-min 1/1 instead: round 1 builds + all-reduces the level's
        sketch (_ha_build_table), round 2 is the exact verification below
        with the sketch cut dropping sub-support pairs before exchange C.
        Output is bit-identical either way; the knob-off path runs the
        exact program (and progress fingerprints) it always ran."""
        # The level's flag table is part of the phase identity: a progress
        # snapshot from one lattice level must never satisfy another.
        digest = hashlib.sha256(b"".join(
            np.ascontiguousarray(a).tobytes()
            for a in (fcode, fv1, fv2, fflag, n_flags))).hexdigest()
        ha_table = None
        if self.ha_on:
            ha_table = self._ha_build_table(fcode, fv1, fv2, fflag, n_flags,
                                            stat_key, digest)

        def step(pass_args):
            if ha_table is None:
                out = _s2l_cooc(*self.lines, self.n_rows, fcode, fv1, fv2,
                                fflag, n_flags, *pass_args, mesh=self.mesh,
                                **self._pair_caps())
            else:
                out = _s2l_cooc_ha(*self.lines, self.n_rows, fcode, fv1, fv2,
                                   fflag, n_flags, *pass_args, ha_table,
                                   mesh=self.mesh, **self._pair_caps(),
                                   ha=(self.ha_bits, self.ha_hashes,
                                       self.ha_thresh))
            *cols, n_out, tele = out
            return cols, n_out, tele

        fp_extra = {"flags": digest}
        if ha_table is not None:
            # The cut changes exchange-C contents, so round-2 snapshots must
            # not satisfy (or be satisfied by) knob-off runs.  Knob-off
            # fingerprints are byte-identical to the historical ones.
            fp_extra["ha"] = [self.ha_bits, self.ha_hashes, self.ha_thresh]
        blocks, (ngl, ngp, npt, nha, *_) = self._run_passes(
            step, "sharded S2L cooc", site="cooc", phase_key=stat_key,
            fp_extra=fp_extra)
        if self.stats is not None:
            metrics.gauge_set(self.stats, stat_key, sum(npt))
            metrics.counter_add(self.stats, "total_pairs", sum(npt))
            metrics.counter_max(self.stats, "n_giant_lines", max(ngl))
            metrics.counter_add(self.stats, "n_giant_pairs", sum(ngp))
            if ha_table is not None:
                metrics.counter_add(self.stats, "ha_cut_pairs", sum(nha))
        return blocks


def _gather_preshard_triples(preshard) -> np.ndarray:
    """Host triple table from a preshard's global arrays.

    The fallback rung trades the no-host-table property for completing the
    run at all — at fallback scale (a workload one chip can finish) the
    gathered table fits the host by construction.
    """
    g_triples, g_valid = preshard
    t = np.asarray(host_gather(g_triples)).reshape(-1, 3)
    nv = np.asarray(host_gather(g_valid)).reshape(-1)
    block = t.shape[0] // max(nv.shape[0], 1)
    keep = np.zeros(t.shape[0], bool)
    for dev in range(nv.shape[0]):
        keep[dev * block: dev * block + int(nv[dev])] = True
    return t[keep]


def _single_device_fallback(kind: str, exc, triples, preshard, min_support,
                            projections, use_fis, use_ars, clean_implied,
                            stats, **kwargs) -> CindTable:
    """The degradation ladder's last rung: re-run the workload on this
    strategy family's output-identical single-device implementation (the
    reference's driver-side shape; SmallToLarge is the default family, and
    each sharded strategy falls back to its own twin so the CIND table stays
    bit-identical to a fault-free run)."""
    from . import allatonce, approximate, late_bb, small_to_large

    fn = {"allatonce": allatonce.discover,
          "small_to_large": small_to_large.discover,
          "approximate": approximate.discover,
          "late_bb": late_bb.discover}[kind]
    print(f"rdfind: sharded {exc.phase} could not complete ({exc.detail}); "
          f"degrading to the single-device {kind} strategy",
          file=sys.stderr)
    faults.record_degradation(stats, exc.phase, "fallback", strategy=kind,
                              reason=exc.detail)
    if triples is None and preshard is not None:
        triples = _gather_preshard_triples(preshard)
    if triples is None or np.asarray(triples).shape[0] == 0:
        return CindTable.empty()
    return fn(np.asarray(triples, np.int32), min_support,
              projections=projections,
              use_frequent_condition_filter=use_fis,
              use_association_rules=use_ars,
              clean_implied=clean_implied, stats=stats, **kwargs)


def discover_sharded(triples, min_support: int, mesh=None, projections: str = "spo",
                     use_fis: bool = False, use_ars: bool = False,
                     clean_implied: bool = False,
                     max_retries: int = 4, stats: dict | None = None,
                     skew: SkewPolicy | None = None,
                     combine: bool = True,
                     preshard=None, progress=None) -> CindTable:
    """Discover all CINDs with the full AllAtOnce step sharded over `mesh`.

    Output is identical to models.allatonce.discover with matching flags.  If
    `stats` is a dict it receives skew-engine counters (n_giant_lines,
    n_giant_pairs) and the measured capacity plan (planned_caps).

    `preshard=(global_triples, global_n_valid)` feeds pre-built global arrays
    (sharded multi-host ingest — runtime/multihost_ingest.py) instead of a
    host triple table; `triples` is then ignored and may be None.  With
    preshard, AR mining runs distributed (mine_ars_sharded).
    """
    if mesh is None:
        mesh = make_mesh()
    if preshard is None:
        triples = np.asarray(triples, np.int32)
        if triples.shape[0] == 0:
            return CindTable.empty()
    if not any(ch in projections for ch in "spo"):
        return CindTable.empty()
    min_support = max(int(min_support), 1)
    use_ars = use_ars and use_fis

    try:
        pipe = _Pipeline(mesh, triples, min_support, projections, use_fis,
                         use_ars, max_retries, stats, skew=skew,
                         combine=combine, preshard=preshard,
                         progress=progress)
        d_code, d_v1, d_v2, r_code, r_v1, r_v2, support = pipe.run_cinds()
    except faults.FallbackRequired as e:
        return _single_device_fallback(
            "allatonce", e, triples, preshard, min_support, projections,
            use_fis, use_ars, clean_implied, stats)

    table = CindTable(
        dep_code=d_code.astype(np.int64), dep_v1=d_v1.astype(np.int64),
        dep_v2=d_v2.astype(np.int64), ref_code=r_code.astype(np.int64),
        ref_v1=r_v1.astype(np.int64), ref_v2=r_v2.astype(np.int64),
        support=support.astype(np.int64))
    if use_ars:
        from . import allatonce
        rules = _mine_rules(triples, preshard, min_support, mesh)
        if stats is not None:
            metrics.struct_set(stats, "association_rules", rules)
        table = allatonce.filter_ar_implied_cinds(table, rules)
    if clean_implied:
        table = minimality.minimize_table_sharded(table, mesh)
    _publish_output_digest(stats, table)
    return table


# ---------------------------------------------------------------------------
# Sharded SmallToLarge: device-resident join lines + per-level flag broadcast.
# ---------------------------------------------------------------------------


def _s2l_flag_rows(jv, code, v1, v2, n_rows, fcode, fv1, fv2, fflag, n_flags):
    """Join the level's broadcast (dep?, ref?) flags onto the resident rows
    and compact away never-relevant rows.  Shared by the verification step
    and the round-1 sketch build, which must see the identical pair stream.

    Dropping never-relevant rows BEFORE the quadratic layout is THE saving of
    this strategy (cf. small_to_large._chunked_cooc's row_keep).  compact
    preserves the (value, capture) sort order.
    """
    n = jv.shape[0]
    valid = jnp.arange(n, dtype=jnp.int32) < n_rows[0]
    fvalid = jnp.arange(fcode.shape[0], dtype=jnp.int32) < n_flags[0]
    flags = exchange.sorted_join_counts([fcode, fv1, fv2], fflag, fvalid,
                                        [code, v1, v2], valid)
    dep_f = valid & (flags >= 2)
    ref_f = valid & (flags % 2 == 1)
    keep = dep_f | ref_f
    return segments.compact([jv, code, v1, v2, dep_f, ref_f], keep)


def _s2l_cooc_device(jv, code, v1, v2, n_rows, fcode, fv1, fv2, fflag, n_flags,
                     pass_idx, n_pass, ha_table=None, *, cap_pairs,
                     cap_exchange_c, cap_giant, cap_giant_pairs,
                     skew=DEFAULT_SKEW, cap_exchange_c_dcn=0,
                     hier=None, dcn_chunks=1, ha=None):
    """One level's verification: join flags onto rows, masked pair phase.

    ha=(bits, num_hashes, thresh) + the replicated all-reduced ha_table arm
    the round-2 count-min candidate cut inside the pair phase."""
    (jv2, code2, v12, v22, df2, rf2), n_keep = _s2l_flag_rows(
        jv, code, v1, v2, n_rows, fcode, fv1, fv2, fflag, n_flags)
    ha_cut = None if ha is None else (ha_table, ha[0], ha[1], ha[2])
    (ucols, uvalid, cooc, (ovf_p, ovf_c, ovf_g, ovf_gp, ovf_cd),
     n_giant_lines, n_giant_pairs, n_pairs_total, n_ha_cut) = _pair_phase(
        jv2, code2, v12, v22, n_keep, df2, rf2, cap_pairs=cap_pairs,
        cap_exchange_c=cap_exchange_c, cap_giant=cap_giant,
        cap_giant_pairs=cap_giant_pairs, skew=skew,
        pass_idx=pass_idx[0], n_pass=n_pass[0],
        cap_exchange_c_dcn=cap_exchange_c_dcn, hier=hier,
        dcn_chunks=dcn_chunks, ha_cut=ha_cut)
    out_cols, n_out = segments.compact(list(ucols) + [cooc], uvalid)
    dig_a, dig_b = _digest_lanes(
        out_cols, jnp.arange(out_cols[0].shape[0], dtype=jnp.int32) < n_out)
    tele = exchange.pack_counters([ovf_p, ovf_c, ovf_g, ovf_gp, ovf_cd,
                                   n_giant_lines, n_giant_pairs,
                                   n_pairs_total, n_ha_cut, dig_a, dig_b])
    return (*out_cols, jnp.full(1, n_out, jnp.int32), tele)


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "cap_pairs", "cap_exchange_c", "cap_giant",
                     "cap_giant_pairs", "skew", "cap_exchange_c_dcn", "hier",
                     "dcn_chunks"))
def _s2l_cooc(jv, code, v1, v2, n_rows, fcode, fv1, fv2, fflag, n_flags,
              pass_idx, n_pass, *, mesh, cap_pairs, cap_exchange_c, cap_giant,
              cap_giant_pairs, skew=DEFAULT_SKEW, cap_exchange_c_dcn=0,
              hier=None, dcn_chunks=1):
    fn = functools.partial(
        _s2l_cooc_device, cap_pairs=cap_pairs, cap_exchange_c=cap_exchange_c,
        cap_giant=cap_giant, cap_giant_pairs=cap_giant_pairs, skew=skew,
        cap_exchange_c_dcn=cap_exchange_c_dcn, hier=hier,
        dcn_chunks=dcn_chunks)
    return shard_map(
        fn, mesh=mesh,
        in_specs=(P(AXIS),) * 5 + (P(),) * 7,
        out_specs=P(AXIS),
        check_vma=False,
    )(jv, code, v1, v2, n_rows, fcode, fv1, fv2, fflag, n_flags,
      pass_idx, n_pass)


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "cap_pairs", "cap_exchange_c", "cap_giant",
                     "cap_giant_pairs", "skew", "cap_exchange_c_dcn", "hier",
                     "dcn_chunks", "ha"))
def _s2l_cooc_ha(jv, code, v1, v2, n_rows, fcode, fv1, fv2, fflag, n_flags,
                 pass_idx, n_pass, ha_table, *, mesh, cap_pairs,
                 cap_exchange_c, cap_giant, cap_giant_pairs,
                 skew=DEFAULT_SKEW, cap_exchange_c_dcn=0, hier=None,
                 dcn_chunks=1, ha=None):
    """_s2l_cooc with the round-2 count-min cut armed.  A separate jit (extra
    replicated ha_table operand + static ha triple) so the knob-off path
    compiles the exact program it compiled before this feature existed."""
    fn = functools.partial(
        _s2l_cooc_device, cap_pairs=cap_pairs, cap_exchange_c=cap_exchange_c,
        cap_giant=cap_giant, cap_giant_pairs=cap_giant_pairs, skew=skew,
        cap_exchange_c_dcn=cap_exchange_c_dcn, hier=hier,
        dcn_chunks=dcn_chunks, ha=ha)
    return shard_map(
        fn, mesh=mesh,
        in_specs=(P(AXIS),) * 5 + (P(),) * 8,
        out_specs=P(AXIS),
        check_vma=False,
    )(jv, code, v1, v2, n_rows, fcode, fv1, fv2, fflag, n_flags,
      pass_idx, n_pass, ha_table)


def _s2l_sketch_build_device(jv, code, v1, v2, n_rows, fcode, fv1, fv2, fflag,
                             n_flags, pass_idx, n_pass, *, cap_pairs,
                             cap_giant, cap_giant_pairs, skew=DEFAULT_SKEW,
                             ha_bits, ha_hashes):
    """Round 1 of the sharded half-approximate 1/1: one dep-slice pass of the
    level's pair stream folded into a per-device count-min partial table.

    Runs the SAME flag join + `_emit_local_pairs` emission as the
    verification step (same caps, same dep-slice hashing), so the partial
    counts sum — over devices and passes — to each pair's exact global cooc,
    and the pass loop's overflow ladder keeps the build complete (an
    incomplete build would under-estimate and make the round-2 cut unsound).
    No exchange C here: the pairs never leave the device, only the dense
    (bits,) table does, via `exchange.sketch_allreduce`.
    """
    from ..ops import sketch
    (jv2, code2, v12, v22, df2, rf2), n_keep = _s2l_flag_rows(
        jv, code, v1, v2, n_rows, fcode, fv1, fv2, fflag, n_flags)
    (pcols, pvalid2, pcnt, (ovf_p, ovf_g, ovf_gp),
     n_giant_lines, n_giant_pairs, n_pairs_total) = _emit_local_pairs(
        jv2, code2, v12, v22, n_keep, df2, rf2, cap_pairs=cap_pairs,
        cap_giant=cap_giant, cap_giant_pairs=cap_giant_pairs, skew=skew,
        pass_idx=pass_idx[0], n_pass=n_pass[0])
    table = sketch.count_min_partial(_ha_pair_keys(pcols), pcnt, pvalid2,
                                     bits=ha_bits, num_hashes=ha_hashes)
    z = jnp.int32(0)
    # Sketch digest: (local position, value) pairs — the psum over devices
    # matches obs/integrity.digest_sketch_rows over the stacked partials at
    # any mesh size with the same ha_bits.
    dig_a, dig_b = _digest_lanes(
        [jnp.arange(ha_bits, dtype=jnp.int32), table],
        jnp.ones((ha_bits,), dtype=bool))
    tele = exchange.pack_counters([ovf_p, z, ovf_g, ovf_gp, z, n_giant_lines,
                                   n_giant_pairs, n_pairs_total, z,
                                   dig_a, dig_b])
    return table, jnp.full(1, ha_bits, jnp.int32), tele


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "cap_pairs", "cap_giant", "cap_giant_pairs",
                     "skew", "ha_bits", "ha_hashes"))
def _s2l_sketch_build(jv, code, v1, v2, n_rows, fcode, fv1, fv2, fflag,
                      n_flags, pass_idx, n_pass, *, mesh, cap_pairs,
                      cap_giant, cap_giant_pairs, skew=DEFAULT_SKEW,
                      ha_bits, ha_hashes):
    fn = functools.partial(
        _s2l_sketch_build_device, cap_pairs=cap_pairs, cap_giant=cap_giant,
        cap_giant_pairs=cap_giant_pairs, skew=skew, ha_bits=ha_bits,
        ha_hashes=ha_hashes)
    return shard_map(
        fn, mesh=mesh,
        in_specs=(P(AXIS),) * 5 + (P(),) * 7,
        out_specs=P(AXIS),
        check_vma=False,
    )(jv, code, v1, v2, n_rows, fcode, fv1, fv2, fflag, n_flags,
      pass_idx, n_pass)


def _ha_reduce_device(parts, *, bits, cap, hier):
    """Fold one device's per-pass partial tables, then all-reduce.

    Each partial is already capped at `cap` <= 2^16-1, so the running int32
    sum stays far below wrap for any realistic pass count; the saturating
    minimum after every add keeps the value on the wire bounded by cap —
    the precondition of the saturation lemma (ops/sketch.py) that makes
    this bit-identical to host `merge_count_min`.
    """
    p = parts.reshape(-1, bits)

    def body(acc, row):
        return jnp.minimum(acc + row, cap), None

    tbl, _ = jax.lax.scan(body, jnp.zeros(bits, jnp.int32), p)
    return exchange.sketch_allreduce(tbl, AXIS, cap=cap, hier=hier)


@functools.partial(jax.jit, static_argnames=("mesh", "bits", "cap", "hier"))
def _ha_reduce_step(parts, *, mesh, bits, cap, hier=None):
    fn = functools.partial(_ha_reduce_device, bits=bits, cap=cap, hier=hier)
    return shard_map(fn, mesh=mesh, in_specs=(P(AXIS),), out_specs=P(AXIS),
                     check_vma=False)(parts)


class _ShardedCooc:
    """Host-side verification backend for the sharded SmallToLarge lattice.

    Each cooc() call broadcasts the level's per-capture flags as a replicated
    flag table (the analog of the reference's broadcast candidate Bloom
    filters) and runs the masked pair phase on the mesh.
    """

    def __init__(self, pipe: _Pipeline, cap_table):
        self.pipe = pipe
        self.cap_code, self.cap_v1, self.cap_v2, self.dep_count = cap_table

    def cooc(self, dep_ok, ref_ok, stat_key):
        """Global (dep, ref) -> co-occurrence counts for flagged capture pairs."""
        sel = np.flatnonzero(dep_ok | ref_ok)
        z = np.zeros(0, np.int64)
        if sel.size == 0:
            return z, z, z
        flag = dep_ok[sel].astype(np.int32) * 2 + ref_ok[sel].astype(np.int32)
        cap_f = segments.pow2_capacity(sel.size)
        pad = lambda a, fill: np.concatenate(
            [a, np.full(cap_f - a.shape[0], fill, a.dtype)])
        fcode = pad(self.cap_code[sel].astype(np.int32), SENTINEL)
        fv1 = pad(self.cap_v1[sel].astype(np.int32), SENTINEL)
        fv2 = pad(self.cap_v2[sel].astype(np.int32), SENTINEL)
        fflag = pad(flag, 0)
        n_flags = np.full(1, sel.size, np.int32)

        d_code, d_v1, d_v2, r_code, r_v1, r_v2, cnt = self.pipe.run_cooc(
            fcode, fv1, fv2, fflag, n_flags, stat_key)
        from .small_to_large import _lookup_capture_ids
        d = _lookup_capture_ids(self.cap_code, self.cap_v1, self.cap_v2,
                                d_code.astype(np.int64), d_v1.astype(np.int64),
                                d_v2.astype(np.int64))
        r = _lookup_capture_ids(self.cap_code, self.cap_v1, self.cap_v2,
                                r_code.astype(np.int64), r_v1.astype(np.int64),
                                r_v2.astype(np.int64))
        ok = (d >= 0) & (r >= 0)
        return d[ok], r[ok], cnt[ok].astype(np.int64)


# ---------------------------------------------------------------------------
# Sharded approximate strategies (2: ApproximateAllAtOnce, 3: LateBB): the
# sketch matrix is built and tiled over the mesh — each device ANDs partial
# dependent sketches from its local lines (cross-device AND = pmin over 0/1
# planes), then runs the containment matmul for its own block of dependent
# rows against the replicated ref side (no cross-device reduction; the
# distributed-by-construction contract of plan/TraversalStrategy.scala:28-33).
# ---------------------------------------------------------------------------


def _sketch_step_device(jv, code, v1, v2, n_rows, tc, tv1, tv2, n_caps, *,
                        c_pad, bits, num_hashes):
    from ..ops import sketch

    n = jv.shape[0]
    valid = jnp.arange(n, dtype=jnp.int32) < n_rows[0]
    cap_idx = segments.masked_table_index([tc, tv1, tv2], n_caps[0],
                                          [code, v1, v2], valid)
    ok = valid & (cap_idx >= 0)
    jv_key = jnp.where(valid, jv, SENTINEL)
    starts = segments.run_starts([jv_key]) & valid
    line_gid = jnp.cumsum(starts).astype(jnp.int32) - 1
    blooms = sketch.build_line_blooms(line_gid, jnp.maximum(cap_idx, 0), ok,
                                      num_lines=n, bits=bits,
                                      num_hashes=num_hashes)
    partial = sketch.intersect_dep_sketches(
        jnp.maximum(cap_idx, 0), blooms[jnp.clip(line_gid, 0, n - 1)], ok,
        num_caps=c_pad, bits=bits)
    planes = jax.lax.pmin(sketch.unpack_planes(partial), AXIS)

    num_dev = jax.lax.psum(1, AXIS)  # axis_size is missing from older jax
    block = c_pad // num_dev
    dep_lo = jax.lax.axis_index(AXIS) * block
    own = jax.lax.dynamic_slice(sketch.pack_planes(planes), (dep_lo, 0),
                                (block, bits // 32))
    ref_ids = jnp.arange(c_pad, dtype=jnp.int32)
    ref_ok = ref_ids < n_caps[0]
    # Dispatcher call: the packed Pallas kernel on TPU, jnp planes elsewhere
    # (pallas_call composes with shard_map; CPU-mesh tests take the jnp path).
    cand = sketch.contains_matrix(own, ref_ids, ref_ok, bits=bits,
                                  num_hashes=num_hashes)
    cand &= (dep_lo + jnp.arange(block, dtype=jnp.int32))[:, None] != \
        ref_ids[None, :]
    from ..ops import cooc as cooc_ops
    return cooc_ops.pack_bool(cand)


@functools.partial(jax.jit,
                   static_argnames=("mesh", "c_pad", "bits", "num_hashes"))
def _sketch_step(jv, code, v1, v2, n_rows, tc, tv1, tv2, n_caps, *, mesh,
                 c_pad, bits, num_hashes):
    fn = functools.partial(_sketch_step_device, c_pad=c_pad, bits=bits,
                           num_hashes=num_hashes)
    return shard_map(
        fn, mesh=mesh,
        in_specs=(P(AXIS),) * 5 + (P(),) * 4,
        out_specs=P(AXIS),
        check_vma=False,
    )(jv, code, v1, v2, n_rows, tc, tv1, tv2, n_caps)


# The sketch stage materializes (rows_per_device x bits) 0/1 planes in one
# jitted program (no line-aligned chunking inside shard_map yet — the
# single-chip path chunks on host, approximate._build_sketches).  Guard the
# transient instead of OOMing mid-collective.
SKETCH_PLANES_BUDGET = int(os.environ.get("RDFIND_SKETCH_PLANES_BUDGET",
                                          4 << 30))


def _sharded_sketch_candidates(pipe, cap_table, bits, num_hashes, stats):
    """(cand_dep, cand_ref) global capture-id pairs from the mesh-tiled
    containment matmul over the replicated frequent-capture table."""
    from ..ops import cooc as cooc_ops

    rows_cap = pipe.lines[0].shape[0] // pipe.num_dev
    if rows_cap * bits > SKETCH_PLANES_BUDGET:
        raise ValueError(
            f"sharded sketch stage would materialize ~{rows_cap * bits >> 30} "
            f"GiB of line-bloom planes per device; lower sketch_bits or use "
            f"strategy 0/1 (RDFIND_SKETCH_PLANES_BUDGET overrides)")

    cap_code, cap_v1, cap_v2, _ = cap_table
    num_caps = cap_code.shape[0]
    num_dev = pipe.num_dev
    # Pad to a multiple of 128 * device count: the per-device dep blocks tile
    # the table exactly AND stay 128-lane aligned for the containment matmul.
    # cooc.cap_pad applies the active padding policy (tile-multiple by
    # default — the mesh-tiled sketch matmul then issues almost no padding
    # rows — pow2-bucketed under RDFIND_TILE_SCHEDULE=0 for compile reuse).
    c_pad = cooc_ops.cap_pad(num_caps, mult=128 * num_dev)
    if stats is not None:
        metrics.struct_set(stats, "sketch_plan",
                           {"c_real": int(num_caps), "c_pad": int(c_pad)})
    pad = lambda a: np.concatenate(
        [a.astype(np.int32), np.full(c_pad - num_caps, SENTINEL, np.int32)])
    packed = _sketch_step(
        *pipe.lines, pipe.n_rows,
        pad(cap_code), pad(cap_v1),
        pad(cap_v2), np.full(1, num_caps, np.int32),
        mesh=pipe.mesh, c_pad=c_pad, bits=bits, num_hashes=num_hashes)
    bits_h = cooc_ops.unpack_cind_bits(host_gather(packed), c_pad)
    d, r = np.nonzero(bits_h[:num_caps, :num_caps])
    if stats is not None:
        metrics.gauge_set(stats, "n_sketch_candidates", int(d.size))
    return d.astype(np.int64), r.astype(np.int64)


def _check_preshard(triples, preshard, use_ars, use_fis):
    """Shared entry validation: host table XOR preshard global arrays.

    Returns (triples-as-int32-or-None, use_ars).  With `preshard` (sharded
    multi-host ingest) AR mining runs distributed (mine_ars_sharded)."""
    if preshard is not None:
        return None, use_ars and use_fis
    triples = np.asarray(triples, np.int32)
    if triples.shape[0] == 0:
        return None, use_ars and use_fis
    return triples, use_ars and use_fis


def _mine_rules(triples, preshard, min_support, mesh):
    """Rule table for the AR post-filter: host mining with a host triple
    table, the distributed count-exchange miner over a preshard."""
    if preshard is not None:
        return mine_ars_sharded(preshard[0], preshard[1], min_support, mesh)
    return frequency.mine_association_rules(triples, min_support)


def _sharded_prep_approx(triples, min_support, mesh, projections, use_fis,
                         use_ars, max_retries, sketch_bits, sketch_hashes,
                         stats, skew=None, combine=True, preshard=None,
                         progress=None):
    """Shared setup for sharded strategies 2/3: pipeline, frequent-capture
    table, sketch candidates, and the sharded verification backend."""
    pipe = _Pipeline(mesh, triples, min_support, projections, use_fis, use_ars,
                     max_retries, stats, skew=skew, combine=combine,
                     preshard=preshard, progress=progress)
    cap_code, cap_v1, cap_v2, dep_count = pipe.capture_table()
    freq_cap = dep_count >= min_support
    cap_table = tuple(a[freq_cap] for a in (cap_code, cap_v1, cap_v2,
                                            dep_count))
    if cap_table[0].shape[0] == 0:
        return None
    if stats is not None:
        n_triples = (triples.shape[0] if preshard is None
                     else int(host_gather(preshard[1]).sum()))
        metrics.set_many(stats, n_triples=n_triples,
                         n_captures=int(cap_table[0].shape[0]), total_pairs=0)
    cand_dep, cand_ref = _sharded_sketch_candidates(
        pipe, cap_table, sketch_bits, sketch_hashes, stats)
    backend = _ShardedCooc(pipe, cap_table)
    return cap_table, cand_dep, cand_ref, backend


def _publish_output_digest(stats, table):
    """Stamp the run's output digest — order-invariant over the final CIND
    set, so identical across strategies, mesh sizes, and knob settings
    whenever the logical result is — into the integrity stages."""
    if stats is not None and integrity.enabled():
        integrity.publish_stage(
            stats, "output", *integrity.digest_table(table),
            rows=int(np.asarray(table.support).shape[0]))


def _finish_table(cap_table, d, r, sup, triples, min_support, use_ars,
                  clean_implied, stats, mesh=None, preshard=None):
    from . import allatonce

    cap_code, cap_v1, cap_v2, _ = cap_table
    table = CindTable(
        dep_code=cap_code[d], dep_v1=cap_v1[d], dep_v2=cap_v2[d],
        ref_code=cap_code[r], ref_v1=cap_v1[r], ref_v2=cap_v2[r],
        support=sup)
    if use_ars:
        rules = _mine_rules(triples, preshard, min_support, mesh)
        if stats is not None:
            metrics.struct_set(stats, "association_rules", rules)
        table = allatonce.filter_ar_implied_cinds(table, rules)
    if clean_implied:
        table = (minimality.minimize_table_sharded(table, mesh)
                 if mesh is not None else minimality.minimize_table(table))
    _publish_output_digest(stats, table)
    return table


def discover_sharded_approx(triples, min_support: int, mesh=None,
                            projections: str = "spo", use_fis: bool = False,
                            use_ars: bool = False, clean_implied: bool = False,
                            max_retries: int = 4, sketch_bits: int = 2048,
                            sketch_hashes: int = 4,
                            stats: dict | None = None,
                            skew: SkewPolicy | None = None,
                            combine: bool = True,
                         preshard=None, progress=None) -> CindTable:
    """Sharded ApproximateAllAtOnce (strategy 2): mesh-tiled sketch containment
    for candidates, exact sharded counting for verification.  Output is
    identical to models.approximate.discover (= raw AllAtOnce)."""
    from . import small_to_large

    if mesh is None:
        mesh = make_mesh()
    triples, use_ars = _check_preshard(triples, preshard, use_ars, use_fis)
    if triples is None and preshard is None:
        return CindTable.empty()
    if not any(ch in projections for ch in "spo"):
        return CindTable.empty()
    min_support = max(int(min_support), 1)

    try:
        prep = _sharded_prep_approx(triples, min_support, mesh, projections,
                                    use_fis, use_ars, max_retries, sketch_bits,
                                    sketch_hashes, stats, skew=skew,
                                    combine=combine, preshard=preshard,
                                    progress=progress)
        if prep is None:
            return CindTable.empty()
        cap_table, cand_dep, cand_ref, backend = prep
        cap_code, cap_v1, cap_v2, dep_count = cap_table
        d, r, sup = small_to_large._verify_level(
            backend.cooc, cand_dep, cand_ref, cap_code.shape[0], dep_count,
            cap_code, cap_v1, cap_v2, min_support, "pairs_verify")
    except faults.FallbackRequired as e:
        return _single_device_fallback(
            "approximate", e, triples, preshard, min_support, projections,
            use_fis, use_ars, clean_implied, stats,
            sketch_bits=sketch_bits, sketch_hashes=sketch_hashes)
    return _finish_table(cap_table, d, r, sup, triples, min_support, use_ars,
                         clean_implied, stats, mesh=mesh, preshard=preshard)


def discover_sharded_late_bb(triples, min_support: int, mesh=None,
                             projections: str = "spo", use_fis: bool = False,
                             use_ars: bool = False, clean_implied: bool = False,
                             max_retries: int = 4, sketch_bits: int = 2048,
                             sketch_hashes: int = 4,
                             stats: dict | None = None,
                            skew: SkewPolicy | None = None,
                            combine: bool = True,
                         preshard=None, progress=None) -> CindTable:
    """Sharded LateBB (strategy 3): one mesh-tiled sketch pass, then the
    unary-dependent round and the 1/x-pruned binary round verify on the mesh.
    Output is identical to models.late_bb.discover."""
    from . import small_to_large

    if mesh is None:
        mesh = make_mesh()
    triples, use_ars = _check_preshard(triples, preshard, use_ars, use_fis)
    if triples is None and preshard is None:
        return CindTable.empty()
    if not any(ch in projections for ch in "spo"):
        return CindTable.empty()
    min_support = max(int(min_support), 1)

    try:
        prep = _sharded_prep_approx(triples, min_support, mesh, projections,
                                    use_fis, use_ars, max_retries, sketch_bits,
                                    sketch_hashes, stats, skew=skew,
                                    combine=combine, preshard=preshard,
                                    progress=progress)
        if prep is None:
            return CindTable.empty()
        cap_table, cand_dep, cand_ref, backend = prep
        cap_code, cap_v1, cap_v2, dep_count = cap_table
        num_caps = cap_code.shape[0]
        dep_is_unary = np.asarray(cc.is_unary(cap_code))[cand_dep]

        d1, r1, sup1 = small_to_large._verify_level(
            backend.cooc, cand_dep[dep_is_unary], cand_ref[dep_is_unary],
            num_caps, dep_count, cap_code, cap_v1, cap_v2, min_support,
            "pairs_round1")
        c2_dep, c2_ref = cand_dep[~dep_is_unary], cand_ref[~dep_is_unary]
        keep = small_to_large._prune_22_vs_12(c2_dep, c2_ref, d1, r1,
                                              cap_code, cap_v1, cap_v2)
        d2, r2, sup2 = small_to_large._verify_level(
            backend.cooc, c2_dep[keep], c2_ref[keep], num_caps, dep_count,
            cap_code, cap_v1, cap_v2, min_support, "pairs_round2")
    except faults.FallbackRequired as e:
        return _single_device_fallback(
            "late_bb", e, triples, preshard, min_support, projections,
            use_fis, use_ars, clean_implied, stats,
            sketch_bits=sketch_bits, sketch_hashes=sketch_hashes)
    if stats is not None:
        metrics.set_many(stats, n_round1_cinds=len(d1),
                         n_round2_cinds=len(d2))
    return _finish_table(
        cap_table, np.concatenate([d1, d2]), np.concatenate([r1, r2]),
        np.concatenate([sup1, sup2]), triples, min_support, use_ars,
        clean_implied, stats, mesh=mesh, preshard=preshard)


def discover_sharded_s2l(triples, min_support: int, mesh=None,
                         projections: str = "spo", use_fis: bool = True,
                         use_ars: bool = False, clean_implied: bool = False,
                         max_retries: int = 4,
                         stats: dict | None = None,
                         skew: SkewPolicy | None = None,
                         combine: bool = True,
                         preshard=None, progress=None) -> CindTable:
    """Sharded SmallToLarge: the reference's default strategy on the mesh.

    Join lines are built once and stay device-resident; the host drives the
    identical lattice logic as small_to_large.discover (shared code), with each
    level's verification running as a masked pair phase over the mesh.  Output
    is identical to small_to_large.discover with matching flags.

    Where the frequent-condition filter is on and the membership fits the
    dense plan, on a flat mesh of one host without per-pass checkpoints or
    the half-approximate cut, every level is verified instead against one
    membership matmul per device summed over the mesh
    (models/sharded_dense.py), as small_to_large.discover's dense backend
    does on one device.
    """
    from . import small_to_large

    if mesh is None:
        mesh = make_mesh()
    triples, use_ars = _check_preshard(triples, preshard, use_ars, use_fis)
    if triples is None and preshard is None:
        return CindTable.empty()
    if not any(ch in projections for ch in "spo"):
        return CindTable.empty()
    min_support = max(int(min_support), 1)

    num_dev = mesh.devices.size
    if (preshard is None and progress is None and combine
            and hier_spec(num_dev) is None
            and not sharded_half_approx_enabled()):
        t_loc = _block_rows(triples.shape[0], num_dev)
        if sharded_dense.eligible(num_dev, t_loc, min_support, use_fis):
            table = _discover_s2l_dense(triples, t_loc, min_support, mesh,
                                        projections, use_ars, clean_implied,
                                        stats)
            if table is not None:
                return table

    try:
        pipe = _Pipeline(mesh, triples, min_support, projections, use_fis,
                         use_ars, max_retries, stats, skew=skew,
                         combine=combine, preshard=preshard,
                         progress=progress)
        cap_code, cap_v1, cap_v2, dep_count = pipe.capture_table()
        # Frequent captures only (the single-device capture filter; infrequent
        # ones can appear in no CIND on either side).
        freq_cap = dep_count >= min_support
        cap_code, cap_v1, cap_v2, dep_count = (
            a[freq_cap] for a in (cap_code, cap_v1, cap_v2, dep_count))
        num_caps = cap_code.shape[0]
        if num_caps == 0:
            return CindTable.empty()

        if stats is not None:
            n_triples = (triples.shape[0] if preshard is None
                         else int(host_gather(pipe._n_valid).sum()))
            metrics.set_many(stats, n_triples=n_triples,
                             n_captures=num_caps, total_pairs=0)

        backend = _ShardedCooc(pipe, (cap_code, cap_v1, cap_v2, dep_count))

        rules = (_mine_rules(triples, preshard, min_support, pipe.mesh)
                 if use_ars else None)
        if use_ars and stats is not None:
            metrics.struct_set(stats, "association_rules", rules)

        table = small_to_large._run_lattice(
            backend.cooc, cap_code, cap_v1, cap_v2, dep_count, num_caps,
            min_support, use_ars, rules, clean_implied, stats, mesh=pipe.mesh)
        _publish_output_digest(stats, table)
        return table
    except faults.FallbackRequired as e:
        return _single_device_fallback(
            "small_to_large", e, triples, preshard, min_support, projections,
            use_fis, use_ars, clean_implied, stats)


def _discover_s2l_dense(triples, t_loc, min_support, mesh, projections,
                        use_ars, clean_implied, stats):
    """Sharded SmallToLarge on the dense path (models/sharded_dense.py), or
    None when its membership matrix does not fit the dense plan."""
    from . import small_to_large

    padded, n_valid, _ = _shard_triples(triples, mesh.devices.size, t_loc)
    with tracer.span("upload-triples", cat=tracer.CAT_DISPATCH):
        g_triples = make_global(padded, mesh)
        g_n_valid = make_global(n_valid, mesh)
    dense = sharded_dense.prepare(mesh, g_triples, g_n_valid, t_loc,
                                  int(triples.max()) + 1, min_support,
                                  projections, use_ars, stats)
    if dense is None:
        return None
    if dense == ():
        return CindTable.empty()
    cooc_fn, cap_code, cap_v1, cap_v2, dep_count = dense
    if stats is not None:
        metrics.set_many(stats, n_triples=triples.shape[0],
                         n_captures=cap_code.shape[0], total_pairs=0)
    rules = (_mine_rules(triples, None, min_support, mesh)
             if use_ars else None)
    if use_ars and stats is not None:
        metrics.struct_set(stats, "association_rules", rules)
    table = small_to_large._run_lattice(
        cooc_fn, cap_code, cap_v1, cap_v2, dep_count, cap_code.shape[0],
        min_support, use_ars, rules, clean_implied, stats, mesh=mesh)
    _publish_output_digest(stats, table)
    return table


@functools.lru_cache(maxsize=None)
def _stage_count_fcs(mesh, capacity: int, include_binary: bool):
    """Compiled shard_map program: global distinct frequent-condition counts.

    The distributed --find-only-fcs report over preshard arrays
    (RDFind.scala:298-306 counted cluster-wide): per field group, distinct
    keys travel to their hash owner, which counts its frequent ones; psum
    totals them.
    """
    def f(triples, n_valid, min_support):
        t_loc = triples.shape[0]
        valid = jnp.arange(t_loc, dtype=jnp.int32) < n_valid[0]
        groups = [(fld,) for fld in range(3)]
        if include_binary:
            groups += [(0, 1), (0, 2), (1, 2)]
        counts = []
        ovf_total = jnp.int32(0)
        for i, fields in enumerate(groups):
            cols = [triples[:, fld] for fld in fields]
            n_u, ovf = exchange.global_distinct_frequent(
                cols, valid, min_support, AXIS, capacity, seed=101 + i)
            counts.append(n_u)
            ovf_total += ovf
        return jnp.stack(counts), ovf_total

    return jax.jit(shard_map(
        f, mesh=mesh, in_specs=(P(AXIS, None), P(AXIS), P()),
        out_specs=(P(), P())))


@functools.lru_cache(maxsize=None)
def _stage_datastats(mesh, giant_load: int):
    """Compiled shard_map program: the data plane's one-shot distribution
    snapshot over the pipeline's resident state (obs/datastats.py).

    Returns three tiny replicated arrays — the 32-bin log2 join-line size
    histogram, the 32-bin capture support spectrum, and a packed scalar lane
    [n_lines, max_line, n_giant_lines, n_captures, max_support] — so the
    host pull is O(32) ints however large the resident rows are.  Giant =
    quadratic load over the pair phase's absolute backstop (`giant_load`)."""
    def f(jv, n_rows, tcnt, n_caps):
        r = jv.shape[0]
        valid = jnp.arange(r, dtype=jnp.int32) < n_rows[0]
        # Rebalancing may interleave value buckets; a local sort restores
        # the contiguous-run invariant the run helpers need.
        jv_s = jnp.sort(jnp.where(valid, jv, SENTINEL))
        sizes = segments.masked_row_counts([jv_s], valid)
        line = segments.run_starts([jv_s]) & valid & (sizes > 0)
        exp = jnp.clip(31 - jax.lax.clz(jnp.maximum(sizes, 1)), 0, 31)
        hist = jax.lax.psum(
            jnp.zeros(32, jnp.int32).at[exp].add(line.astype(jnp.int32)),
            AXIS)
        load = sizes.astype(jnp.float32) * (sizes - 1).astype(jnp.float32)
        n_giant = jax.lax.psum(
            jnp.sum((line & (load > float(giant_load))).astype(jnp.int32)),
            AXIS)
        n_lines = jax.lax.psum(jnp.sum(line.astype(jnp.int32)), AXIS)
        max_line = jax.lax.pmax(jnp.max(jnp.where(line, sizes, 0)), AXIS)

        c = tcnt.shape[0]
        cvalid = (jnp.arange(c, dtype=jnp.int32) < n_caps[0]) & (tcnt > 0)
        cexp = jnp.clip(31 - jax.lax.clz(jnp.maximum(tcnt, 1)), 0, 31)
        chist = jax.lax.psum(
            jnp.zeros(32, jnp.int32).at[cexp].add(cvalid.astype(jnp.int32)),
            AXIS)
        n_capt = jax.lax.psum(jnp.sum(cvalid.astype(jnp.int32)), AXIS)
        max_sup = jax.lax.pmax(jnp.max(jnp.where(cvalid, tcnt, 0)), AXIS)
        sc = exchange.pack_counters([n_lines, max_line, n_giant, n_capt,
                                     max_sup])
        return hist, chist, sc

    return jax.jit(shard_map(
        f, mesh=mesh, in_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS)),
        out_specs=(P(), P(), P())))


@functools.lru_cache(maxsize=None)
def _stage_digest(mesh):
    """Compiled shard_map program: the integrity plane's stage digests over
    the pipeline's resident state — two order/mesh-invariant content-digest
    lanes each for the join-line rows and the capture table
    (obs/integrity.py), packed into one 4-lane array so the host pull is
    O(4) ints however large the state is."""
    def f(jv, code, v1, v2, n_rows, tc, tv1, tv2, tcnt, n_caps):
        lvalid = jnp.arange(jv.shape[0], dtype=jnp.int32) < n_rows[0]
        la, lb = _digest_lanes([jv, code, v1, v2], lvalid)
        cvalid = jnp.arange(tc.shape[0], dtype=jnp.int32) < n_caps[0]
        ca, cb = _digest_lanes([tc, tv1, tv2, tcnt], cvalid)
        return exchange.pack_counters([la, lb, ca, cb])

    return jax.jit(shard_map(
        f, mesh=mesh, in_specs=(P(AXIS),) * 10, out_specs=P()))


def _stage_join_histogram(mesh, capacity: int, projections: str):
    """Compiled shard_map program: per-line distinct-capture counts over a
    preshard (the distributed --create-join-histogram pass,
    RDFind.scala:448-452 — an extra map/groupBy job, as in the reference)."""
    def f(triples, n_valid):
        t_loc = triples.shape[0]
        valid = jnp.arange(t_loc, dtype=jnp.int32) < n_valid[0]
        cands = emit_join_candidates(triples, frequency.no_filter(valid),
                                     projections)
        u_cols, u_valid, _, _ = segments.masked_unique(
            [cands.join_val, cands.code, cands.v1, cands.v2], cands.valid)
        d = jax.lax.psum(1, AXIS)
        bucket = hashing.bucket_of([u_cols[0]], d, seed=433)
        recv, recv_valid, ovf, _ = exchange.route(u_cols, u_valid, bucket,
                                                  AXIS, capacity)
        r_cols, r_valid, _, _ = segments.masked_unique(recv, recv_valid)
        # masked_unique sorts by key, so each join value is one contiguous
        # run at its owner: line size = run length, one representative per run.
        sizes = segments.masked_row_counts([r_cols[0]], r_valid)
        is_rep = segments.run_starts([r_cols[0]]) & r_valid
        return jnp.where(is_rep, sizes, 0), ovf

    return jax.jit(shard_map(
        f, mesh=mesh, in_specs=(P(AXIS, None), P(AXIS)),
        out_specs=(P(AXIS), P())))


def join_histogram_sharded(g_triples, g_valid, projections: str, mesh,
                           max_retries: int = 4):
    """(line_size, occurrence_count) pairs over a preshard — output-identical
    to the replicated driver's histogram on the same data."""
    num_dev = mesh.devices.size
    t_loc = g_triples.shape[0] // num_dev
    capacity = _headroom(-(-9 * t_loc // num_dev))
    for _ in range(max_retries):
        prog = _stage_join_histogram(mesh, capacity, projections)
        line_sizes, ovf = prog(g_triples, g_valid)
        ovf = int(np.asarray(host_gather(ovf)).reshape(-1)[0])
        if ovf == 0:
            break
        capacity = segments.pow2_capacity(2 * capacity + ovf)
        _check_exchange_caps(num_dev, histogram=capacity)
    else:
        raise RuntimeError(
            f"join-histogram exchange overflow persisted after "
            f"{max_retries} retries (ovf={ovf})")
    sizes_h = np.asarray(host_gather(line_sizes)).reshape(-1)
    sizes_h = sizes_h[sizes_h > 0]
    sizes, times = np.unique(sizes_h, return_counts=True)
    return list(zip(sizes.tolist(), times.tolist()))


@functools.lru_cache(maxsize=None)
def _stage_mine_ars(mesh, cap_counts: int, cap_rules: int):
    """Compiled shard_map program: distributed perfect-confidence AR mining.

    The preshard form of frequency._stage_rules (FrequentConditionPlanner.
    scala:130-194): per-row global counts come from the count exchange, rule
    verdicts are local comparisons, and the distinct rule rows travel to their
    hash owner for global dedupe — no host ever holds the triple table.
    """
    def f(triples, n_valid, min_support):
        t_loc = triples.shape[0]
        valid = jnp.arange(t_loc, dtype=jnp.int32) < n_valid[0]
        ovf = jnp.int32(0)
        unary, binary = [], []
        for fld in range(3):
            cnt, o = exchange.global_row_counts(
                [triples[:, fld]], valid, AXIS, cap_counts, seed=401 + fld)
            unary.append(cnt)
            ovf += o
        for k, (a, b) in enumerate(frequency._FIELD_PAIRS):
            cnt, o = exchange.global_row_counts(
                [triples[:, a], triples[:, b]], valid, AXIS, cap_counts,
                seed=404 + k)
            binary.append(cnt)
            ovf += o
        # Local distinct rules (the shared emitter), then one route to the
        # key's hash owner; owners partition the rule space, so their
        # distinct sets are globally disjoint.
        u_cols, u_valid, _ = frequency.emit_rule_rows(
            triples, valid, min_support, unary, binary)
        d = jax.lax.psum(1, AXIS)
        bucket = hashing.bucket_of(u_cols[:4], d, seed=419)
        recv, recv_valid, o_r, _ = exchange.route(u_cols, u_valid, bucket,
                                                  AXIS, cap_rules)
        r_cols, r_valid, _, _ = segments.masked_unique(recv, recv_valid)
        # Count-exchange and rule-route overflows stay separate so retries
        # grow only the buffer that actually overflowed (D*capacity-sized
        # route buffers are the scarce resource here).
        return (*r_cols, r_valid, ovf, o_r)

    return jax.jit(shard_map(
        f, mesh=mesh, in_specs=(P(AXIS, None), P(AXIS), P()),
        out_specs=(*([P(AXIS)] * 6), P(), P())))


def mine_ars_sharded(g_triples, g_valid, min_support: int, mesh,
                     max_retries: int = 4):
    """Association rules over a preshard: same host rule table as
    frequency.mine_association_rules, mined with count exchanges + one
    rule-row route (no host triple table)."""
    num_dev = mesh.devices.size
    t_loc = g_triples.shape[0] // num_dev
    cap_counts = _headroom(-(-t_loc // num_dev))
    cap_rules = _headroom(CAP_FLOOR)
    for _ in range(max_retries):
        prog = _stage_mine_ars(mesh, cap_counts, cap_rules)
        *cols, r_valid, ovf_c, ovf_r = prog(g_triples, g_valid,
                                            jnp.int32(max(int(min_support),
                                                          1)))
        ovf_c = int(np.asarray(host_gather(ovf_c)).reshape(-1)[0])
        ovf_r = int(np.asarray(host_gather(ovf_r)).reshape(-1)[0])
        if ovf_c == 0 and ovf_r == 0:
            break
        if ovf_c:
            cap_counts = segments.pow2_capacity(2 * cap_counts + ovf_c)
        if ovf_r:
            cap_rules = segments.pow2_capacity(2 * cap_rules + ovf_r)
        _check_exchange_caps(num_dev, ar_counts=cap_counts,
                             ar_rules=cap_rules)
    else:
        raise RuntimeError(
            f"association-rule exchange overflow persisted after "
            f"{max_retries} retries (ovf={ovf_c}+{ovf_r})")
    keep = np.asarray(host_gather(r_valid))
    return [np.asarray(host_gather(c))[keep] for c in cols]


def count_fcs_sharded(g_triples, g_valid, min_support: int, mesh,
                      include_binary: bool, max_retries: int = 4):
    """(n_unary, n_binary|None) distinct frequent conditions over a preshard.

    Capacity follows the plan/retry contract (expected per-(src, dst) volume
    t_loc / D, doubled on overflow) — a worst-case pow2(t_loc) plan would put
    full-table-sized route buffers on every device.
    """
    num_dev = mesh.devices.size
    t_loc = g_triples.shape[0] // num_dev
    capacity = _headroom(-(-t_loc // num_dev))
    for _ in range(max_retries):
        prog = _stage_count_fcs(mesh, capacity, include_binary)
        counts, ovf = prog(g_triples, g_valid,
                           jnp.int32(max(int(min_support), 1)))
        ovf = int(np.asarray(host_gather(ovf)).reshape(-1)[0])
        if ovf == 0:
            break
        capacity = segments.pow2_capacity(2 * capacity + ovf)
        _check_exchange_caps(num_dev, fcs=capacity)
    else:
        raise RuntimeError(
            f"frequent-condition exchange overflow persisted after "
            f"{max_retries} retries (ovf={ovf})")
    counts = np.asarray(host_gather(counts)).reshape(-1)[:6 if include_binary
                                                        else 3]
    n_unary = int(counts[:3].sum())
    n_binary = int(counts[3:].sum()) if include_binary else None
    return n_unary, n_binary


@functools.lru_cache(maxsize=None)
def _stage_dedupe_preshard(mesh, capacity: int):
    """Compiled shard_map program: global row dedup of a preshard.

    The distributed --distinct-triples pass (the reference's
    triples.distinct): rows travel to their hash owner, the owner keeps one
    copy of each, and the deduped rows stay owner-resident (any placement is
    valid — exchange A re-routes every row by join value anyway).
    """
    def f(triples, n_valid):
        t_loc = triples.shape[0]
        valid = jnp.arange(t_loc, dtype=jnp.int32) < n_valid[0]
        cols = [triples[:, i] for i in range(3)]
        d = jax.lax.psum(1, AXIS)
        bucket = hashing.bucket_of(cols, d, seed=31)
        recv, recv_valid, ovf, _ = exchange.route(cols, valid, bucket, AXIS,
                                                  capacity)
        u_cols, u_valid, _, n_u = segments.masked_unique(recv, recv_valid)
        out = jnp.stack(u_cols[:3], axis=1)[:t_loc]
        return out, n_u.reshape(1), ovf

    return jax.jit(shard_map(
        f, mesh=mesh, in_specs=(P(AXIS, None), P(AXIS)),
        out_specs=(P(AXIS, None), P(AXIS), P())))


def dedupe_preshard(g_triples, g_valid, mesh, max_retries: int = 4):
    """Global distinct rows over a preshard; returns (g_triples, g_valid, total).

    Capacity follows the plan/retry contract: expected per-(src, dst) volume
    is t_loc / D (hash spreads rows evenly), overflow doubles and retries —
    a worst-case capacity of t_loc would put a full-table-sized receive
    buffer on every device, which is exactly what sharding must avoid.
    """
    num_dev = mesh.devices.size
    t_loc = g_triples.shape[0] // num_dev
    capacity = _headroom(-(-t_loc // num_dev))
    for _ in range(max_retries):
        prog = _stage_dedupe_preshard(mesh, capacity)
        out, n_valid, ovf = prog(g_triples, g_valid)
        ovf = int(np.asarray(host_gather(ovf)).reshape(-1)[0])
        if ovf == 0:
            break
        capacity = segments.pow2_capacity(2 * capacity + ovf)
        _check_exchange_caps(num_dev, distinct=capacity)
    else:
        raise RuntimeError(
            f"distinct-triples exchange overflow persisted after "
            f"{max_retries} retries (ovf={ovf})")
    n_valid_h = np.asarray(host_gather(n_valid)).reshape(-1)
    if (n_valid_h > t_loc).any():
        # A skewed hash can land more than t_loc DISTINCT rows on one owner;
        # the [:t_loc] block slice must never silently truncate them.
        raise RuntimeError(
            f"distinct-triples owner block overflow (max owner rows="
            f"{int(n_valid_h.max())} > t_loc={t_loc}); rerun with more "
            f"devices")
    total = int(n_valid_h.sum())
    return out, n_valid, total
