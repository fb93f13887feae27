"""Test harness: force an 8-device CPU mesh before jax is imported.

This plays the role of the reference's embedded Flink minicluster
(StratosphereParameters.java:75-94) — multi-device behavior is exercised on one host.
"""

import os
import subprocess
import sys

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
