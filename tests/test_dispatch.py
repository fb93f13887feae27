"""Pipelined async pass execution: parity with the forced-sync schedule,
the optimistic-dispatch rollback path, and the dispatch telemetry contract.

RDFIND_SYNC_PASSES=1 forces every pipelined executor (sharded._run_passes,
cooc.extract_packed_iter, small_to_large._iter_chunk_pairs) back to the
serial pull-then-dispatch schedule; outputs must be bit-identical either way.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from rdfind_tpu.data import CindTable
from rdfind_tpu.models import allatonce, sharded, small_to_large
from rdfind_tpu.parallel.mesh import make_mesh
from rdfind_tpu.utils.synth import generate_triples


@pytest.fixture(scope="module")
def mesh8():
    assert len(jax.devices()) >= 8, "conftest should provide 8 CPU devices"
    return make_mesh(8)


def _multipass_workload():
    return generate_triples(300, seed=21, n_predicates=8, n_entities=32)


def test_pipelined_matches_forced_sync(mesh8, monkeypatch):
    """Smoke + parity on the fake-device mesh (fast tier): bit-identical
    CIND blocks pipelined vs RDFIND_SYNC_PASSES=1 across a multi-pass
    streaming workload, and telemetry that PROVES the overlap happened."""
    triples = _multipass_workload()
    monkeypatch.setattr(sharded, "PAIR_ROW_BUDGET", 1 << 13)
    monkeypatch.delenv("RDFIND_SYNC_PASSES", raising=False)
    s_async: dict = {}
    a = sharded.discover_sharded(triples, 2, mesh=mesh8, stats=s_async)
    monkeypatch.setenv("RDFIND_SYNC_PASSES", "1")
    s_sync: dict = {}
    b = sharded.discover_sharded(triples, 2, mesh=mesh8, stats=s_sync)
    assert s_async["n_pair_passes"] > 1  # the streaming path really ran
    assert a.to_rows() == b.to_rows()
    assert a.to_rows() == allatonce.discover(triples, 2).to_rows()
    # Overlap proof: a successor pass was enqueued while the head pass's
    # pulls blocked, and pull time actually accrued under that overlap.
    assert s_async["n_passes_in_flight"] >= 2
    assert s_async["pull_overlap_ms"] > 0
    assert s_sync["n_passes_in_flight"] == 1
    assert s_sync["pull_overlap_ms"] == 0
    # Sync-count model: one fused telemetry pull + one batched block pull
    # per clean pass (the pre-pipelined loop cost >= 3 blocking gathers).
    assert s_async["n_host_syncs"] == 2 * s_async["n_pair_passes"]
    # Final cap_p is recorded and can only have grown from the plan.
    assert s_async["cap_p_final"] >= s_async["planned_caps"]["pairs"]


def test_pipelined_s2l_matches_forced_sync(mesh8, monkeypatch,
                                          s2l_pair_phase):
    """The S2L lattice drives run_cooc once per level; every level's pass
    loop must stay exact under pipelining."""
    triples = _multipass_workload()
    monkeypatch.setattr(sharded, "PAIR_ROW_BUDGET", 1 << 13)
    monkeypatch.delenv("RDFIND_SYNC_PASSES", raising=False)
    s0: dict = {}
    a = sharded.discover_sharded_s2l(triples, 2, mesh=mesh8, stats=s0)
    monkeypatch.setenv("RDFIND_SYNC_PASSES", "1")
    b = sharded.discover_sharded_s2l(triples, 2, mesh=mesh8)
    assert s0["n_pair_passes"] > 1
    assert s0["n_passes_in_flight"] >= 2
    assert a.to_rows() == b.to_rows()
    assert a.to_rows() == small_to_large.discover(triples, 2).to_rows()


@pytest.mark.parametrize("sync_mode", ["", "1"])
def test_injected_overflow_rollback(mesh8, monkeypatch, sync_mode):
    """An undersized pair cap must overflow mid-run, discard the in-flight
    successor (optimistic dispatch), grow the caps, re-run only the failed
    pass — and still produce the exact CIND set, in both schedules."""
    triples = _multipass_workload()
    monkeypatch.setattr(sharded, "PAIR_ROW_BUDGET", 1 << 13)
    if sync_mode:
        monkeypatch.setenv("RDFIND_SYNC_PASSES", sync_mode)
    else:
        monkeypatch.delenv("RDFIND_SYNC_PASSES", raising=False)
    ids = np.asarray(triples, np.int32)
    stats: dict = {}
    pipe = sharded._Pipeline(mesh8, ids, 2, "spo", False, False, 8, stats)
    assert pipe.n_pass > 1
    # Sabotage exchange C: pair partials must ride it no matter how the
    # skew engine classifies lines, so pass 0 is guaranteed to overflow
    # (shrinking cap_p alone just reroutes load through the giant backstop).
    pipe.cap_c = 1 << 2
    blocks = pipe.run_cinds()
    assert stats["n_pair_cap_retries"] >= 1
    assert pipe.cap_c > 1 << 2  # the rollback grew the overflowed cap
    d_code, d_v1, d_v2, r_code, r_v1, r_v2, support = blocks
    table = CindTable(
        dep_code=d_code.astype(np.int64), dep_v1=d_v1.astype(np.int64),
        dep_v2=d_v2.astype(np.int64), ref_code=r_code.astype(np.int64),
        ref_v1=r_v1.astype(np.int64), ref_v2=r_v2.astype(np.int64),
        support=support.astype(np.int64))
    assert table.to_rows() == allatonce.discover(ids, 2).to_rows()


def test_chunked_backend_pipelined_parity(monkeypatch):
    """The single-device chunked pair loop (_iter_chunk_pairs) pipelines its
    chunk pulls; a tiny chunk budget must give identical output either way."""
    triples = generate_triples(200, seed=7, n_predicates=6, n_entities=24)
    monkeypatch.delenv("RDFIND_SYNC_PASSES", raising=False)
    a = small_to_large.discover(triples, 2, pair_chunk_budget=1 << 10)
    monkeypatch.setenv("RDFIND_SYNC_PASSES", "1")
    b = small_to_large.discover(triples, 2, pair_chunk_budget=1 << 10)
    assert a.to_rows() == b.to_rows()
    assert a.to_rows() == small_to_large.discover(triples, 2).to_rows()


def test_extract_packed_iter_pipelined_parity(monkeypatch):
    """The batched tile decode must return identical index pairs with and
    without the one-batch-ahead prefetch (residency-halved batches)."""
    import jax.numpy as jnp

    from rdfind_tpu.ops import cooc as cooc_ops

    rng = np.random.default_rng(3)
    tiles = [jnp.asarray(rng.integers(0, 1 << 32, (8, 4), dtype=np.uint64)
                         .astype(np.uint32)) for _ in range(9)]
    shapes = [(rng.integers(1, 9), rng.integers(1, 129)) for _ in range(9)]
    tile_bits = 8 * 4 * 32

    def thunks():
        return [lambda p=p, s=s: (p, int(s[0]), int(s[1]))
                for p, s in zip(tiles, shapes)]

    monkeypatch.delenv("RDFIND_SYNC_PASSES", raising=False)
    # Tiny residency budget => several batches => the prefetch really runs.
    monkeypatch.setattr(cooc_ops, "EXTRACT_DEVICE_ELEMS", 4 * tile_bits)
    got = cooc_ops.extract_packed_iter(thunks(), tile_bits)
    monkeypatch.setenv("RDFIND_SYNC_PASSES", "1")
    want = cooc_ops.extract_packed_iter(thunks(), tile_bits)
    assert len(got) == len(want)
    for (gd, gr), (wd, wr) in zip(got, want):
        assert np.array_equal(gd, wd) and np.array_equal(gr, wr)


@pytest.mark.slow
@pytest.mark.parametrize("seed", [51, 53, 59])
def test_pipelined_fuzz_parity(mesh8, monkeypatch, seed):
    """Random multi-pass workloads: the pipelined schedule must stay exact
    regardless of how the dep slices cut the capture space."""
    import random

    rng = random.Random(seed)
    rows = [(f"s{rng.randrange(10)}", f"p{rng.randrange(4)}",
             f"o{rng.randrange(8)}") for _ in range(250)]
    from rdfind_tpu.dictionary import intern_triples
    ids, _ = intern_triples(np.asarray(rows, dtype=object))
    monkeypatch.setattr(sharded, "PAIR_ROW_BUDGET", 1 << 12)
    monkeypatch.delenv("RDFIND_SYNC_PASSES", raising=False)
    s: dict = {}
    a = sharded.discover_sharded(ids, 2, mesh=mesh8, stats=s)
    monkeypatch.setenv("RDFIND_SYNC_PASSES", "1")
    b = sharded.discover_sharded(ids, 2, mesh=mesh8)
    assert s["n_pair_passes"] > 1
    assert a.to_rows() == b.to_rows()
    assert a.to_rows() == allatonce.discover(ids, 2).to_rows()


@pytest.mark.slow
def test_default_xla_opt_smoke():
    """One smoke compile at the DEFAULT XLA optimization level: conftest pins
    -O0 for test speed, so without this no test exercises the production
    compile path (ADVICE r5).  RDFIND_TEST_XLA_DEFAULT_OPT=1 lifts the pin;
    a subprocess is required because XLA_FLAGS are baked in at backend init."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        "import os\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'\n"
        f"import sys; sys.path.insert(0, {repo!r})\n"
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "from rdfind_tpu.models import allatonce, sharded\n"
        "from rdfind_tpu.parallel.mesh import make_mesh\n"
        "from rdfind_tpu.utils.synth import generate_triples\n"
        "t = generate_triples(120, seed=3, n_predicates=5, n_entities=16)\n"
        "a = sharded.discover_sharded(t, 2, mesh=make_mesh(8))\n"
        "b = allatonce.discover(t, 2)\n"
        "assert a.to_rows() == b.to_rows()\n"
        "print('OK')\n")
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["RDFIND_TEST_XLA_DEFAULT_OPT"] = "1"
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=1200)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip().splitlines()[-1] == "OK"
