"""Device mesh helpers, single- and multi-host.

A 1-D data mesh is the core topology for CIND discovery (the workload is batch
dataflow, not tensor algebra): every exchange is value- or capture-hash bucketed
all_to_all over the single axis, which XLA lowers to ICI collectives within a slice
and DCN across slices.  Mirrors the role of StratosphereParameters'
degree-of-parallelism + executor config (rdfind-util/.../StratosphereParameters.
java:35-154).

Multi-host: `initialize_multihost` wires JAX's distributed runtime (the
DCN-analog of the reference's multi-node Flink runtime — JobManager RPC +
netty shuffles, pom.xml:33 / StratosphereParameters.java:68-122), after which
`make_mesh()` spans every process's devices and the sharded pipelines' host
orchestration reads global state via `host_gather`.
"""

from __future__ import annotations

import os

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

AXIS = "d"


def topology_hosts(num_dev: int) -> int:
    """How many hosts the `num_dev`-device mesh spans, for ledger attribution
    and the hierarchical factorization.

    `RDFIND_HIER_HOSTS` overrides the runtime's process count — that is how
    single-process tests (8 fake CPU devices) and benches model a 2-host pod
    proxy.  A host count that does not divide the mesh degenerates to 1
    (every device "local"): the hierarchical path requires an even
    (hosts x local) factorization of the axis.
    """
    try:
        hosts = int(os.environ.get("RDFIND_HIER_HOSTS", "") or
                    jax.process_count())
    except ValueError:
        hosts = jax.process_count()
    if hosts < 1 or num_dev % hosts != 0:
        return 1
    return hosts


def hier_spec(num_dev: int):
    """Resolve `RDFIND_HIER_EXCHANGE` to a (hosts, local_devices) factorization
    of the 1-D axis, or None for the flat single-hop exchange.

    auto (default) -- hierarchical only when the mesh spans >1 host (flat is
    strictly cheaper on one host: the two-level path moves every row twice);
    1 -- force hierarchical even single-host (tests / benches exercise the
    path via `RDFIND_HIER_HOSTS`); 0 -- force the flat path exactly.
    """
    knob = os.environ.get("RDFIND_HIER_EXCHANGE", "auto").strip().lower()
    if knob in ("0", "off", "flat"):
        return None
    hosts = topology_hosts(num_dev)
    if knob in ("1", "on", "force"):
        return (hosts, num_dev // hosts)
    if hosts <= 1:  # auto
        return None
    return (hosts, num_dev // hosts)


def dcn_chunks() -> int:
    """`RDFIND_HIER_DCN_CHUNKS`: split the inter-host hop of a hierarchical
    exchange into this many independent all_to_all slices of the capacity
    axis (overlap food for the dispatch-ahead executor).  1 = one collective;
    ``auto`` picks from the last measured overlap report (ROADMAP item 3 —
    see :func:`dcn_chunks_auto`).
    """
    knob = os.environ.get("RDFIND_HIER_DCN_CHUNKS", "1").strip().lower()
    if knob == "auto":
        from ..obs import metrics

        return dcn_chunks_auto(metrics.registry().get("overlap"))
    try:
        return max(1, int(knob or "1"))
    except ValueError:
        return 1


def dcn_chunks_auto(report) -> int:
    """Chunk count from a measured overlap report (the DispatchStats
    `overlap_report` dict the executor publishes under the "overlap" key).

    The heuristic reads `overlap_efficiency` — where the measured wall sat
    between the perfect-overlap and fully-serial bounds on the LAST run:

    * no report yet / no pulls worth hiding (pull_ms < 1 ms) -> 1 — there is
      nothing for extra chunks to overlap, and each chunk adds a collective's
      fixed latency;
    * efficiency >= 0.85 -> 1 — the dispatch-ahead executor is already
      hiding the pulls; splitting the hop only adds launch overhead;
    * efficiency >= 0.5 -> 2 — partial overlap: halving the hop gives the
      executor a second slice to hide behind compute;
    * below 0.5 -> 4 — the DCN hop dominates the critical path; finer
      slices are the only overlap food available (4 keeps per-slice payloads
      well above the latency floor; going finer has measured negative).

    Deliberately one-shot (reads the previous run, steers the next) rather
    than a controller: exchange walls are noisy at small scale and a stable
    knob beats a hunting one.
    """
    if not isinstance(report, dict):
        return 1
    eff = report.get("overlap_efficiency")
    pull_ms = report.get("pull_ms") or 0.0
    if eff is None or pull_ms < 1.0:
        return 1
    if eff >= 0.85:
        return 1
    if eff >= 0.5:
        return 2
    return 4


def shard_map(fn, *, mesh, in_specs, out_specs, check_vma=False):
    """The ONE entry point for every sharded program in this repo.

    The pipelines disable the replication check (their collective programs
    trip its conservative inference), so every call site goes through this
    one default."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def link_probe_enabled() -> bool:
    """Whether the startup link-capability probe is armed
    (RDFIND_LINK_PROBE=1).  Off by default: it costs a few tiny collectives
    at pipeline start; the exchange timers degrade gracefully without it
    (achieved GB/s is still reported, utilization-of-peak is not)."""
    return os.environ.get("RDFIND_LINK_PROBE", "") not in ("", "0")


_LINK_PROBE_KEY = None  # (num_dev, hosts) the cached probe ran under

_PROBE_CAP = 1 << 13  # rows per buffer lane: tiny (KBs-MBs), but a full
_PROBE_REPS = 3       # fixed-shape collective through the real links


def _time_a2a(mesh, group: int, cap: int, groups) -> float:
    """Achieved GB/s of one all_to_all over `groups` (ledger byte
    convention: every participating device moves its whole (group, cap)
    int32 buffer, self-rows included — matching exchange_split_bytes so
    utilization-of-peak compares like with like)."""
    import time

    from .exchange import _a2a

    num_dev = int(mesh.devices.size)
    spec = P(AXIS, None)
    data = make_global(
        np.zeros((num_dev * group, cap), np.int32), mesh)
    fn = jax.jit(shard_map(
        lambda b: _a2a(b, AXIS, groups),
        mesh=mesh, in_specs=spec, out_specs=spec))
    jax.block_until_ready(fn(data))  # compile + warm the route
    t0 = time.perf_counter()
    for _ in range(_PROBE_REPS):
        data = fn(data)
    jax.block_until_ready(data)
    dt = (time.perf_counter() - t0) / _PROBE_REPS
    nbytes = num_dev * group * cap * 4
    return nbytes / max(dt, 1e-9) / 1e9


def link_probe(mesh=None, force: bool = False) -> dict:
    """One-shot per-hop link-capability microbench, cached in the metrics
    registry (obs/metrics.link_caps).

    Runs a tiny fixed-shape all_to_all per hop of the current topology —
    intra-host groups (ICI; the full mesh when single-host) and, when the
    (hosts x local) factorization exists, inter-host groups (DCN) — and
    records achieved GB/s as the measured peak each exchange timer
    normalizes against.  Idempotent per (num_dev, hosts): the sharded
    pipeline calls maybe_link_probe at init and only the first call pays.
    """
    global _LINK_PROBE_KEY
    from ..obs import metrics, tracer

    if mesh is None:
        mesh = make_mesh()
    num_dev = int(mesh.devices.size)
    hosts = topology_hosts(num_dev)
    key = (num_dev, hosts)
    if not force and _LINK_PROBE_KEY == key:
        return metrics.link_caps()
    from .exchange import hier_groups

    caps = {"num_dev": num_dev, "hosts": hosts, "probe_cap": _PROBE_CAP}
    if hosts > 1:
        intra, inter = hier_groups((hosts, num_dev // hosts))
        caps["ici_gbps"] = round(
            _time_a2a(mesh, num_dev // hosts, _PROBE_CAP, intra), 3)
        caps["dcn_gbps"] = round(
            _time_a2a(mesh, hosts, _PROBE_CAP, inter), 3)
    else:
        caps["ici_gbps"] = round(
            _time_a2a(mesh, num_dev, _PROBE_CAP, None), 3)
    _LINK_PROBE_KEY = key
    metrics.set_link_caps(caps)
    tracer.instant("link_probe", cat=tracer.CAT_EXCHANGE, **caps)
    return caps


def maybe_link_probe(mesh=None) -> dict:
    """link_probe when armed (the pipeline-init call site); {} otherwise."""
    if not link_probe_enabled():
        return {}
    return link_probe(mesh)


_MULTIHOST_INITIALIZED = False


def initialize_multihost(coordinator: str, num_processes: int,
                         process_id: int, *,
                         shutdown_timeout_seconds: int = 7200) -> None:
    """Join this process to a multi-host run (idempotent per process).

    `coordinator` is `host:port` of process 0.  Must be called before any
    other jax API touches the backend.

    shutdown_timeout_seconds raises jax's default 300 s exit barrier: hosts
    finish batch phases minutes apart when compute is uneven (or, on the CPU
    minicluster, when one core timeshares every "device"), and a host that
    exits first must wait at the barrier instead of tearing the runtime down
    under its peers (observed: an 8M-triple 2-process run lost host 0 to the
    default barrier while it was still in its final phase).
    """
    global _MULTIHOST_INITIALIZED
    # NB: probing via jax.process_count() would itself initialize the XLA
    # backend and make initialize() illegal — use the distributed-state API.
    # (is_initialized() is newer jax; 0.4.x readers go through global_state.)
    is_init = getattr(jax.distributed, "is_initialized", None)
    if is_init is None:
        from jax._src import distributed as _dist

        def is_init():
            return _dist.global_state.client is not None
    if _MULTIHOST_INITIALIZED or is_init():
        return  # already joined (jax.distributed.initialize is once-only)
    try:
        # Multi-process CPU backends need an explicit cross-process
        # collectives implementation on some jax versions ("Multiprocess
        # computations aren't implemented on the CPU backend" otherwise);
        # gloo is the TCP one.  No effect on TPU clients.  NB the flag is
        # not always readable as a config attribute — update() is the only
        # portable accessor, so only an explicit non-default survives.
        cur = getattr(jax.config, "jax_cpu_collectives_implementation", None)
        if cur in (None, "none"):
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception:
        pass  # versions without the flag don't need it
    kwargs = dict(coordinator_address=coordinator,
                  num_processes=num_processes, process_id=process_id)
    extra = dict(shutdown_timeout_seconds=shutdown_timeout_seconds,
                 **_init_timeout_kwargs())
    while True:
        try:
            jax.distributed.initialize(**kwargs, **extra)
            break
        except TypeError:
            # Older jax predates one of the knobs (exit barrier /
            # per-attempt init timeout): drop them one at a time — joining
            # with defaults beats not joining at all.
            if not extra:
                raise
            extra.popitem()
    _MULTIHOST_INITIALIZED = True


def ensure_distributed(coordinator: str, num_processes: int,
                       process_id: int, *,
                       shutdown_timeout_seconds: int = 7200) -> int:
    """`initialize_multihost` with bounded retry + seeded exponential
    backoff around the rendezvous — the product-level form of the retry
    the two-process test tier used to carry in-test.

    The gloo TCP rendezvous wedges nondeterministically on loaded CI boxes
    (observed ~9-minute burns before an external retry rescued the run);
    here each attempt is bounded by the distributed runtime's own
    initialization timeout (RDFIND_INIT_TIMEOUT_S where the jax version
    accepts it) under a watchdog deadman, failures back off with the fault
    ladder's jittered schedule, and RDFIND_INIT_RETRIES (default 3)
    attempts are made before giving up.  Returns the number of retries
    used (0 = first attempt joined), published as
    ``distributed_init_retries`` in the metrics registry.

    Single-process callers (num_processes <= 1) are a no-op returning 0.
    """
    from ..obs import metrics
    from ..runtime import faults, watchdog

    if num_processes <= 1:
        return 0
    tries = max(1, int(os.environ.get("RDFIND_INIT_RETRIES", "3")))
    last: Exception | None = None
    for attempt in range(tries):
        try:
            with watchdog.collective("init", force=True):
                initialize_multihost(
                    coordinator, num_processes, process_id,
                    shutdown_timeout_seconds=shutdown_timeout_seconds)
            metrics.gauge_set(None, "distributed_init_retries", attempt)
            return attempt
        except (faults.Preempted, faults.FallbackRequired):
            raise
        except Exception as e:
            last = e
            _teardown_distributed()
            if attempt == tries - 1:
                break
            delay_ms = faults._backoff_ms(attempt)
            print(f"rdfind: distributed init attempt {attempt + 1}/{tries} "
                  f"failed ({e}); retrying after {delay_ms:.0f} ms",
                  file=__import__("sys").stderr, flush=True)
            import time as _time

            _time.sleep(delay_ms / 1e3)
    metrics.gauge_set(None, "distributed_init_retries", tries - 1)
    raise RuntimeError(
        f"distributed init failed after {tries} attempts") from last


def _teardown_distributed() -> None:
    """Best-effort shutdown between init retries: jax.distributed.initialize
    is once-only per live client, so a failed rendezvous must release its
    half-open state before the next attempt."""
    global _MULTIHOST_INITIALIZED
    _MULTIHOST_INITIALIZED = False
    try:
        jax.distributed.shutdown()
    except Exception:
        pass


def _init_timeout_kwargs() -> dict:
    """initialization_timeout for jax versions that accept it: bounds one
    rendezvous attempt so ensure_distributed's retry loop gets control
    back (RDFIND_INIT_TIMEOUT_S; 0/unset keeps jax's default)."""
    try:
        t = float(os.environ.get("RDFIND_INIT_TIMEOUT_S", "0"))
    except ValueError:
        t = 0.0
    return {"initialization_timeout": int(t)} if t > 0 else {}


def make_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    """A 1-D mesh over the first `n_devices` available devices (all by default).

    Under a multi-host runtime `jax.devices()` spans every process, so the
    mesh does too.
    """
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        if n_devices > len(devices):
            raise ValueError(
                f"requested {n_devices} devices, only {len(devices)} available")
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (AXIS,))


def _host_gather_raw(x) -> np.ndarray:
    if jax.process_count() == 1:
        return np.asarray(x)
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(x, tiled=True))


def host_gather(x) -> np.ndarray:
    """Device output -> host numpy, valid on every process.

    Single-process: a plain transfer.  Multi-process: shard_map outputs over
    P(AXIS) are globally sharded and not fully addressable from one host, so
    gather them with process_allgather (one DCN collective).

    Pulls are pure reads of device state, so every gather rides the fault
    gate + bounded backoff retry of runtime/faults.guarded_pull (the
    `host_pull` injection site; RDFIND_STRICT=1 fails fast).
    """
    from ..runtime import faults

    return faults.guarded_pull(lambda: _host_gather_raw(x))


def host_gather_many(xs) -> list:
    """Batched host_gather: ONE blocking round trip for a list of arrays.

    Single-process, a single device_get drains every pending transfer at once
    (pair it with dispatch.stage_to_host so the copies were already in
    flight).  Multi-process each array still needs its own allgather
    collective, but issuing them back-to-back keeps the DCN pipe busy.
    Counts as ONE host_pull fault-site hit either way (one round trip).
    """
    from ..runtime import faults

    xs = list(xs)
    if jax.process_count() == 1:
        return faults.guarded_pull(lambda: jax.device_get(xs))
    return faults.guarded_pull(lambda: [_host_gather_raw(x) for x in xs])


def allgather_host_values(values, site: str = "allgather") -> np.ndarray:
    """(n_hosts, k) matrix of per-host floats: one tiny DCN allgather under
    a multi-process runtime, the identity single-process.

    The skew meter rides this each committed pass (per-host wall + phase
    breakdown are HOST-side clocks, so they cannot fuse into the device
    telemetry lanes) — the payload is a handful of float64s, noise next to
    the pass's own counter pull.

    `site` names the caller for the collective watchdog (and the
    wedge@<site> fault family): the deadman is armed around the gather, so
    a peer that never answers becomes a recoverable preemption instead of
    an indefinite block.
    """
    from ..runtime import watchdog

    arr = np.asarray(values, np.float64).reshape(1, -1)
    with watchdog.collective(site, arr.nbytes * jax.process_count()):
        if jax.process_count() == 1:
            return arr
        from jax.experimental import multihost_utils

        out = np.asarray(multihost_utils.process_allgather(arr))
    return out.reshape(-1, arr.shape[1])


def make_global(host_array: np.ndarray, mesh: Mesh) -> jax.Array:
    """A global row-sharded device array from an identical-on-every-host
    numpy array (rows divide evenly by the mesh size).

    Single-process this is a plain device put; multi-process each host
    donates only the rows its devices own.
    """
    sharding = NamedSharding(mesh, P(AXIS) if host_array.ndim == 1
                             else P(AXIS, *([None] * (host_array.ndim - 1))))
    if jax.process_count() == 1:
        return jax.device_put(host_array, sharding)
    return jax.make_array_from_callback(
        host_array.shape, sharding, lambda idx: host_array[idx])
