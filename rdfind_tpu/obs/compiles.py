"""Per-program compile counter, kept by the program itself.

One ``jax.monitoring`` listener per process sees every backend compile
(``/jax/core/compile/backend_compile_duration``, which carries the
program's ``fun_name``) and the persistent cache's verdict on it
(``/jax/compilation_cache/cache_hits``, or ``cache_misses`` when a fresh
compile is written to the cache, recorded on the compiling thread inside
that duration).  Each compile is kept as one record: the program, its
seconds, the cache's verdict (``hit``: loaded from the cache; ``miss``:
compiled and written to it; ``uncached``: compiled and not written, as
with the cache off) and the driver stage that was open.  The driver
resets the records at every run start and puts them into
``RunResult.counters``:

  compiles          backend compile requests, cache loads included;
  compile-s         their seconds;
  compile-misses    those the persistent cache did not serve (compiled
                    anew: ``miss`` or ``uncached``);
  compile-programs  "<program>@<stage>:<seconds>:<verdict>" per compile.

With tracing on, each compile is also a ``compile`` instant on the trace
and the heartbeat's ``last_compile``.  With tracing off the listener only
appends a record, and it runs only when something compiles, never on the
dispatch path.

Stdlib-only at import time, like the rest of obs: jax is imported by
``install`` alone.
"""

from __future__ import annotations

import threading

from . import metrics, tracer

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_lock = threading.Lock()
_local = threading.local()
_installed = False
_records: list = []  # (program, seconds, verdict, stage) since the reset


def install() -> None:
    """Register the listeners, once per process."""
    global _installed
    with _lock:
        if _installed:
            return
        import jax

        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _installed = True


def reset() -> None:
    """Forget the records (the driver calls it at every run start)."""
    with _lock:
        _records.clear()


def _on_event(event: str, **_) -> None:
    if event == CACHE_HIT_EVENT:
        _local.verdict = "hit"
    elif event == CACHE_MISS_EVENT:
        _local.verdict = "miss"


def _on_duration(event: str, seconds: float, fun_name: str = "?",
                 **_) -> None:
    if event != COMPILE_EVENT:
        return
    verdict = getattr(_local, "verdict", "uncached")
    _local.verdict = "uncached"
    stage = metrics.registry().get("run_stage")
    with _lock:
        _records.append((fun_name, float(seconds), verdict, stage))
    if tracer.enabled():
        tracer.instant("compile", cat=tracer.CAT_COMPILE, program=fun_name,
                       seconds=round(float(seconds), 6), cache=verdict,
                       stage=stage)
        tracer.set_status(last_compile=fun_name)


def counters() -> dict:
    """The run's compile counters (module docstring)."""
    with _lock:
        recs = list(_records)
    return {
        "compiles": len(recs),
        "compile-s": round(sum(r[1] for r in recs), 6),
        "compile-misses": sum(r[2] != "hit" for r in recs),
        "compile-programs": [f"{name}@{stage}:{secs:.6f}:{verdict}"
                             for name, secs, verdict, stage in recs],
    }
