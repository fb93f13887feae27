"""Pallas packed-containment kernel vs. the jnp planes formulation.

Runs the kernel in interpreter mode (CPU); the lowered TPU path is compiled
for v5e in tests/test_tpu_compile.py and run on the chip by chip_smoke.py.
Parity is checked for ALL unpack dtypes (int8 — the TPU default — the int4
nibble and int2 crumb sub-byte modes, and the bf16 fallback), and across the
emit_pipeline knob (off-TPU its =True rows run the grid variant — the
contract that forcing a knob never changes outputs).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from rdfind_tpu.ops import pallas_kernels, sketch

BITS = 256
K = 4


def random_sketches(rng, n, bits):
    return rng.integers(0, 1 << 32, size=(n, bits // 32), dtype=np.uint32)


@pytest.mark.parametrize("unpack_dtype", ["int2", "int4", "int8", "bf16"])
@pytest.mark.parametrize("seed,bits", [(0, BITS), (1, BITS), (0, 16384),
                                       (2, 32768)])
def test_packed_kernel_matches_jnp(seed, bits, unpack_dtype):
    # bits=16384 -> W=512 words > the int8/bf16 WK_MAX entries, exercising
    # the K-grid accumulation (scratch init at k==0, finalize at k==nk-1)
    # with nk >= 2 plus the hoisted dep-plane chunk writes at dynamic K
    # offsets; bits=32768 (W=1024) pushes past int4's doubled WK=512 too,
    # so the nibble mode's widened K step gets a genuine nk=2 grid (and
    # exactly fills int2's quadrupled WK=1024 — its nk=2 case is
    # test_packed_kernel_int2_multi_k below).  On backends without native
    # int4/int2 elements the sub-byte modes run their widened-WK grids
    # with int8 elements — the documented emulation, same arithmetic, so
    # parity must hold everywhere.
    rng = np.random.default_rng(seed)
    d, r = 128, 128
    sketches = random_sketches(rng, d, bits)
    ref_ids = jnp.asarray(rng.integers(0, 500, size=r, dtype=np.int32))
    valid = jnp.ones(r, bool)
    want = np.asarray(sketch._contains_matrix_jnp(
        jnp.asarray(sketches), ref_ids, valid, bits=bits, num_hashes=K))
    ref_packed, popc = sketch.pack_ref_bits(ref_ids, bits=bits, num_hashes=K)
    got = np.asarray(pallas_kernels.packed_contains_matrix(
        jnp.asarray(sketches), ref_packed, popc, interpret=True,
        unpack_dtype=unpack_dtype))
    np.testing.assert_array_equal(got.astype(bool), want)


def test_packed_kernel_int2_multi_k():
    # bits=65536 -> W=2048 words: past even int2's quadrupled WK=1024, so
    # the crumb mode runs a genuine nk=2 K-grid (accumulating scratch +
    # dynamic-offset hoisted chunks) rather than a single widened step.
    rng = np.random.default_rng(4)
    bits, d, r = 65536, 128, 128
    sketches = random_sketches(rng, d, bits)
    ref_ids = jnp.asarray(rng.integers(0, 500, size=r, dtype=np.int32))
    valid = jnp.ones(r, bool)
    want = np.asarray(sketch._contains_matrix_jnp(
        jnp.asarray(sketches), ref_ids, valid, bits=bits, num_hashes=K))
    ref_packed, popc = sketch.pack_ref_bits(ref_ids, bits=bits, num_hashes=K)
    got = np.asarray(pallas_kernels.packed_contains_matrix(
        jnp.asarray(sketches), ref_packed, popc, interpret=True,
        unpack_dtype="int2"))
    np.testing.assert_array_equal(got.astype(bool), want)


@pytest.mark.parametrize("unpack_dtype", ["int2", "int4", "int8", "bf16"])
@pytest.mark.parametrize("emit", [None, False, True])
def test_packed_kernel_emit_knob_is_output_invariant(monkeypatch,
                                                     unpack_dtype, emit):
    # emit_pipeline=True off-TPU exercises the probe-refusal fallback (the
    # emit kernel cannot trace on CPU, even interpreted): all three knob
    # values must be bit-identical, and None must follow the resolver.
    rng = np.random.default_rng(6)
    d, r = 128, 128
    sketches = random_sketches(rng, d, BITS)
    ref_ids = jnp.asarray(rng.integers(0, 500, size=r, dtype=np.int32))
    valid = jnp.ones(r, bool)
    want = np.asarray(sketch._contains_matrix_jnp(
        jnp.asarray(sketches), ref_ids, valid, bits=BITS, num_hashes=K))
    ref_packed, popc = sketch.pack_ref_bits(ref_ids, bits=BITS, num_hashes=K)
    got = np.asarray(pallas_kernels.packed_contains_matrix(
        jnp.asarray(sketches), ref_packed, popc, interpret=True,
        unpack_dtype=unpack_dtype, emit_pipeline=emit))
    np.testing.assert_array_equal(got.astype(bool), want)


@pytest.mark.parametrize("unpack_dtype", ["int2", "int4", "int8", "bf16"])
def test_packed_kernel_multi_tile_hoist(monkeypatch, unpack_dtype):
    # Multiple dep AND ref tiles: the hoisted dep-plane scratch is filled at
    # j == 0 and re-read for every later ref tile, so any staleness across
    # the (i, j) revisit order shows up as off-tile mismatches.
    rng = np.random.default_rng(5)
    d, r = 256, 384
    sketches = random_sketches(rng, d, BITS)
    ref_ids = jnp.asarray(rng.integers(0, 500, size=r, dtype=np.int32))
    valid = jnp.ones(r, bool)
    want = np.asarray(sketch._contains_matrix_jnp(
        jnp.asarray(sketches), ref_ids, valid, bits=BITS, num_hashes=K))
    ref_packed, popc = sketch.pack_ref_bits(ref_ids, bits=BITS, num_hashes=K)
    got = np.asarray(pallas_kernels.packed_contains_matrix(
        jnp.asarray(sketches), ref_packed, popc, interpret=True,
        unpack_dtype=unpack_dtype))
    np.testing.assert_array_equal(got.astype(bool), want)


@pytest.mark.parametrize("unpack_dtype", ["int8", "bf16"])
def test_packed_kernel_padding_and_valid_mask(unpack_dtype):
    # Non-tile-aligned D/R exercise the pad + slice path; padded refs must not
    # produce phantom candidates, and ~valid refs are masked.
    rng = np.random.default_rng(7)
    d, r = 130, 70
    sketches = random_sketches(rng, d, BITS)
    # Some all-ones sketches (contain everything) stress the popc comparison.
    sketches[:5] = 0xFFFFFFFF
    ref_ids = jnp.asarray(rng.integers(0, 100, size=r, dtype=np.int32))
    valid = jnp.asarray(rng.integers(0, 2, size=r).astype(bool))
    want = np.asarray(sketch._contains_matrix_jnp(
        jnp.asarray(sketches), ref_ids, valid, bits=BITS, num_hashes=K,
        contract_dtype=unpack_dtype))
    got = np.asarray(sketch.contains_matrix(
        jnp.asarray(sketches), ref_ids, valid, bits=BITS, num_hashes=K,
        backend="pallas", interpret=True))
    assert got.shape == want.shape == (d, r)
    np.testing.assert_array_equal(got, want)


def test_contains_matrix_jnp_dtype_parity():
    # The int8 (int32-accumulated) and bf16 (f32-accumulated) contractions of
    # the planes formulation are bit-identical — the exactness claim behind
    # int8-by-default.
    rng = np.random.default_rng(9)
    sketches = jnp.asarray(random_sketches(rng, 96, BITS))
    ref_ids = jnp.asarray(rng.integers(0, 300, size=96, dtype=np.int32))
    valid = jnp.ones(96, bool)
    a = np.asarray(sketch._contains_matrix_jnp(
        sketches, ref_ids, valid, bits=BITS, num_hashes=K,
        contract_dtype="int8"))
    b = np.asarray(sketch._contains_matrix_jnp(
        sketches, ref_ids, valid, bits=BITS, num_hashes=K,
        contract_dtype="bf16"))
    np.testing.assert_array_equal(a, b)


def test_pack_ref_bits_matches_planes():
    rng = np.random.default_rng(3)
    ref_ids = jnp.asarray(rng.integers(0, 1000, size=64, dtype=np.int32))
    rows, popc = sketch.pack_ref_bits(ref_ids, bits=BITS, num_hashes=K)
    pos = np.asarray(sketch.bit_positions(ref_ids, bits=BITS, num_hashes=K))
    planes = np.zeros((64, BITS), np.uint8)
    for i in range(64):
        planes[i, pos[i]] = 1
    np.testing.assert_array_equal(np.asarray(sketch.unpack_planes(rows)), planes)
    np.testing.assert_array_equal(np.asarray(popc), planes.sum(axis=1))


def test_tile_alignment_validation():
    z = jnp.zeros((100, 8), jnp.uint32)
    with pytest.raises(ValueError):
        pallas_kernels.packed_contains_matrix(z, z, jnp.zeros(100, jnp.int32))
    with pytest.raises(ValueError):
        pallas_kernels.packed_contains_matrix(
            jnp.zeros((128, 8), jnp.uint32), jnp.zeros((128, 8), jnp.uint32),
            jnp.zeros(128, jnp.int32), unpack_dtype="f64")
