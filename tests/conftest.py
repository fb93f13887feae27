"""Test harness: force an 8-device CPU mesh before jax is imported.

This plays the role of the reference's embedded Flink minicluster
(StratosphereParameters.java:75-94) — multi-device behavior is exercised on one host.
"""

import os
import subprocess
import sys

import pytest

# RDFIND_TEST_TPU=1 lifts the CPU pin so the `-m tpu` tier (on-chip Pallas
# parity + end-to-end golden, tests/test_tpu_tier.py) can reach the real
# backend; everything below down to the final config.update is gated on it.
_FORCE_CPU = not os.environ.get("RDFIND_TEST_TPU")

if _FORCE_CPU:
    os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if _FORCE_CPU and "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()


def _flags_supported(flags: str) -> bool:
    """Whether THIS jaxlib accepts `flags` (unknown XLA flags abort the
    process at backend init — parse_flags_from_env.cc CHECK-fails — so the
    only safe probe is a killable subprocess).  Any probe failure just
    means "don't pin the flags"."""
    code = "import jax; jax.devices()"
    try:
        r = subprocess.run(
            [sys.executable, "-c", code],
            env={**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": flags},
            capture_output=True, timeout=120)
    except Exception:
        return False
    return r.returncode == 0


_COLLECTIVE_FLAGS = (
    # One-core box: the in-process CPU communicator CHECK-fails ("stuck")
    # when heavy per-device work staggers a rendezvous; raise its patience.
    # Older jaxlibs predate these flags and ABORT on unknown XLA_FLAGS, so
    # they are probed before being pinned (a wrong guess kills every test).
    " --xla_cpu_collective_timeout_seconds=7200"
    " --xla_cpu_collective_call_warn_stuck_timeout_seconds=600"
    " --xla_cpu_collective_call_terminate_timeout_seconds=7200")
if ("collective_call_terminate" not in os.environ["XLA_FLAGS"]
        and _flags_supported(os.environ["XLA_FLAGS"] + _COLLECTIVE_FLAGS)):
    os.environ["XLA_FLAGS"] += _COLLECTIVE_FLAGS

if ("backend_optimization_level" not in os.environ["XLA_FLAGS"]
        and not os.environ.get("RDFIND_TEST_XLA_DEFAULT_OPT")):
    # The fast tier is XLA-CPU-compile-dominated; LLVM -O0 cuts cold compiles
    # ~40% with identical outputs (measured r5: discover_sharded cold 18.5 s
    # -> 11.2 s, same CINDs).  Tests only — production paths never see this.
    # RDFIND_TEST_XLA_DEFAULT_OPT=1 lifts the pin so a tier can compile at
    # the default (production) optimization level: the slow tier's
    # test_default_xla_opt_smoke exercises that path in a subprocess, and CI
    # can export the var to run the whole suite at default opt (ADVICE r5).
    os.environ["XLA_FLAGS"] += " --xla_backend_optimization_level=0"

# The persistent compilation cache stays OFF in tests, child processes
# included (the entry points place it via runtime/compile_cache.py): on
# this image XLA's AOT loader warns of compile/host machine-feature
# mismatches ("could lead to SIGILL") when reloading cached CPU
# executables across processes.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)


def _memory_maps() -> int:
    """This process's memory mappings (0 where /proc is missing)."""
    try:
        with open("/proc/self/maps", "rb") as f:
            return sum(1 for _ in f)
    except OSError:
        return 0


def _max_memory_maps() -> int:
    try:
        with open("/proc/sys/vm/max_map_count") as f:
            return int(f.read())
    except (OSError, ValueError):
        return 65530  # the kernel's default


_MAP_BUDGET = _max_memory_maps() // 2


def pytest_runtest_teardown(item, nextitem):
    """Every program a test compiles stays loaded in JAX's caches, and each
    loaded XLA CPU executable holds memory mappings of its own (a few
    hundred for an 8-device shard_map program).  A worker that has run a
    few hundred such tests nears the kernel's per-process limit
    (vm.max_map_count), and the next executable's load then fails inside
    XLA, killing the worker: tests/test_sharded_half_approx.py alone leaves
    ~59k of the default 65,530.  Past half the limit, drop the caches; the
    next tests compile what they need again."""
    if _memory_maps() > _MAP_BUDGET:
        import gc

        import jax

        jax.clear_caches()
        gc.collect()


@pytest.fixture
def s2l_pair_phase(monkeypatch):
    """Sharded strategy 1 on its masked pair phase, for the tests of that
    phase's machinery (the dense path of models/sharded_dense.py takes
    the small graphs of these tests otherwise)."""
    from rdfind_tpu.models import sharded_dense

    monkeypatch.setattr(sharded_dense, "eligible", lambda *a, **k: False)
