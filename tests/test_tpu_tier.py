"""On-chip test tier (`-m tpu`): chip regressions visible without a bench run.

These tests need a REAL TPU backend: the Pallas containment kernel runs
non-interpreted and one end-to-end golden pins the whole device pipeline
against the host oracle (VERDICT r5 #9).  Off-chip they skip — the default
CI tier stays green on CPU-only hosts.

Running on-chip requires lifting the harness's CPU pin:

    RDFIND_TEST_TPU=1 pytest -m tpu tests/

(conftest.py only forces the 8-device CPU mesh when RDFIND_TEST_TPU is
unset.)  chip_smoke.py is the end-to-end check on the chip.
"""

import numpy as np
import pytest

import jax

pytestmark = pytest.mark.tpu


def _on_tpu() -> bool:
    try:
        return jax.devices()[0].platform == "tpu"
    except Exception:
        return False


on_chip = pytest.mark.skipif(not _on_tpu(),
                             reason="requires a TPU backend "
                                    "(RDFIND_TEST_TPU=1 lifts the CPU pin)")


@on_chip
def test_pallas_kernel_noninterpreted_parity():
    """The packed containment kernel compiled by Mosaic (not the
    interpreter) agrees bit-for-bit with the jnp planes path."""
    from rdfind_tpu.ops import sketch

    out = sketch.kernel_selfcheck(n_rows=512, n_bits=2048, backend="tpu",
                                  repeats=1)
    assert out.get("parity") is True, out


@on_chip
@pytest.mark.parametrize("dtype", ["int8", "bf16"])
def test_pallas_kernel_dtype_parity(dtype, monkeypatch):
    from rdfind_tpu.ops import sketch

    monkeypatch.setenv("RDFIND_COOC_DTYPE", dtype)
    out = sketch.kernel_selfcheck(n_rows=256, n_bits=1024, backend="tpu",
                                  repeats=1)
    assert out.get("parity") is True, out


@on_chip
@pytest.mark.parametrize("plane_bits", ["8", "4"])
def test_pallas_kernel_plane_bits_parity(plane_bits, monkeypatch):
    """Nibble-plane (int4) vs int8 planes, compiled by Mosaic: bit-for-bit
    parity with the jnp path at a K-grid shape (nk >= 2 for both widths)."""
    from rdfind_tpu.ops import cooc, sketch

    monkeypatch.setattr(cooc, "PLANE_BITS", plane_bits)
    out = sketch.kernel_selfcheck(n_rows=256, n_bits=32768, backend="tpu",
                                  repeats=1)
    assert out.get("parity") is True, out


@on_chip
@pytest.mark.parametrize("fuse,block_skip", [("0", "0"), ("1", "0"),
                                             ("1", "1")])
def test_fused_verdict_on_chip(fuse, block_skip, monkeypatch):
    """The fused verdict kernel compiled by Mosaic (scalar-prefetch K
    schedule included) equals the materialized sweep on planted CINDs."""
    from rdfind_tpu.models import allatonce
    from rdfind_tpu.ops import cooc
    from rdfind_tpu.utils.synth import generate_planted_cinds

    triples, _ = generate_planted_cinds(3, 10)
    monkeypatch.setattr(cooc, "FUSE_VERDICT", "0")
    want = allatonce.discover(triples, 8).to_rows()
    monkeypatch.setattr(cooc, "FUSE_VERDICT", fuse)
    monkeypatch.setattr(cooc, "BLOCK_SKIP", block_skip)
    assert allatonce.discover(triples, 8).to_rows() == want


@on_chip
def test_end_to_end_golden_on_chip():
    """One whole-pipeline golden on the planted workload: the device path
    (AllAtOnce on TPU) equals the strategy-1 walk and meets the planted
    family bounds — a full-stack regression canary for the chip."""
    from rdfind_tpu.models import allatonce, small_to_large
    from rdfind_tpu.utils.synth import generate_planted_cinds

    triples, expected = generate_planted_cinds(4, 12)
    t0 = allatonce.discover(triples, 10, clean_implied=True)
    t1 = small_to_large.discover(triples, 10, clean_implied=True)
    assert t0.to_rows() == t1.to_rows()
    fc = t0.family_counts()
    for fam, n in expected.items():
        assert fc[fam] >= n, (fam, fc)


@on_chip
def test_parallel_ingest_feeds_device_pipeline(tmp_path):
    """Ingest-to-device smoke: parallel-parsed ids drive the same discovery
    output as serial-parsed ids on the real backend."""
    from rdfind_tpu.io import native
    from rdfind_tpu.models import allatonce

    if not native.available():
        pytest.skip("native library unavailable")
    f = tmp_path / "w.nt"
    f.write_text("".join(
        f"<http://ex/s{i % 37}> <http://ex/p{i % 5}> \"v{i % 23}\" .\n"
        for i in range(5000)))
    ids1, _ = native.ingest_files([str(f)], threads=1)
    ids4, _ = native.ingest_files([str(f)], threads=4, chunk_bytes=1 << 14)
    np.testing.assert_array_equal(ids1, ids4)
    t = allatonce.discover(ids4, 10)
    assert len(t) == len(allatonce.discover(ids1, 10))
