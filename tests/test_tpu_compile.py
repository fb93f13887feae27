"""AOT compiles of the main-path kernels for a described TPU v5e.

Nothing runs here: each test compiles for one chip of a v5e:2x2 topology
that is described, not attached (on-chip-measurement guide, section 2), so
what Mosaic or XLA would refuse on the chip is refused here at no chip
time.  Where a Pallas kernel is expected the compiled text must hold
`tpu_custom_call`.  The topology is described inside the module fixture
only — never at import time — so every xdist worker collects the same tests
and only the worker given this file loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from rdfind_tpu.ops import cooc, pallas_kernels


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _plane_modes(topo):
    """(unpack dtype, plane element) per mode: bf16, int8, and the sub-byte
    mode cooc.SUBBYTE_PLANE_BITS resolves this chip kind to."""
    bits = cooc.SUBBYTE_PLANE_BITS[topo.devices[0].device_kind]
    sub = "int4" if bits == 4 else "int8"
    return {"bf16": ("bf16", "bf16"), "int8": ("int8", "int8"),
            "subbyte": (sub, sub)}


@pytest.mark.parametrize("words", [128, 256])
@pytest.mark.parametrize("mode", ["bf16", "int8", "subbyte"])
def test_packed_containment_compiles(topo, one_chip, mode, words):
    """The packed containment kernel at real widths: 1024 x 1024 rows of
    4096 or 8192 bits (the grid variant; emit_pipeline can only be
    compiled with a chip attached, so chip_smoke.py reports it)."""
    unpack, elem = _plane_modes(topo)[mode]
    n = 1024
    compiled = pallas_kernels._packed_contains_matrix.lower(
        _spec((n, words), jnp.uint32, one_chip),
        _spec((n, words), jnp.uint32, one_chip),
        _spec((n,), jnp.int32, one_chip),
        interpret=False, unpack_dtype=unpack, plane_elem=elem,
        emit=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.int8, jnp.bfloat16])
def test_fused_verdict_compiles(one_chip, dtype):
    """The fused verdict kernel at the r4 headline plan (L=32768 lines,
    C=8192 captures, tile 4096) in both membership dtypes.  Mosaic refused
    its epilogue's boolean select ("Unsupported target bitwidth for
    truncation") until the select became boolean and/or arithmetic."""
    lines, caps, tile = 32768, 8192, 4096
    nk = lines // cooc.line_block_for(lines)
    col = _spec((caps,), jnp.int32, one_chip)
    scalar = _spec((), jnp.int32, one_chip)
    compiled = cooc._fused_cind_tile.lower(
        _spec((lines, caps), dtype, one_chip), scalar, col, col, col, col,
        scalar, _spec((nk,), jnp.int32, one_chip),
        _spec((1,), jnp.int32, one_chip), tile=tile,
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_block_counts_fit_beside_the_membership(one_chip):
    """The sub-tile skip's block counts at the 20M-triple strategy-0 plan
    (708352 lines x 2688 captures, int8): the reduction may not convert the
    whole membership to int32 (7 GB on the chip, which exceeded its HBM
    next to the 1.9 GB membership)."""
    lines, caps = 708352, 2688
    compiled = cooc._stage_block_counts.lower(
        _spec((lines, caps), jnp.int8, one_chip), kl=256,
        tile=128).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < lines * caps


def test_graft_entry_step_compiles(one_chip):
    """The jitted entry() step of __graft_entry__.py (AllAtOnce's fused
    discovery step) compiles for the chip."""
    import __graft_entry__

    fn, args = __graft_entry__.entry()
    specs = [_spec(a.shape, a.dtype, one_chip) for a in args]
    compiled = jax.jit(fn).lower(*specs).compile()
    assert compiled.memory_analysis() is not None
