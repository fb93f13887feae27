"""Dense co-occurrence matmul: the MXU formulation of the quadratic pair phase.

The reference's hot path emits, per join line, every ordered pair of captures
as a CIND evidence and intersects evidence sets per dependent
(CreateAllCindCandidates.scala:106-121, IntersectCindCandidates.scala:14-51).
The count reformulation used across this repo tests `cooc(d, r) == support(d)`
instead.  This module computes the *entire* cooc matrix as one blocked matmul:

    M    : (lines x captures) 0/1 membership in HBM — int8 by default on
           TPU (the MXU's int8 path), bf16 elsewhere or via
           RDFIND_COOC_DTYPE=bf16
    cooc : M^T M on the MXU — int32 accumulation for int8 (exact to int32
           counts), f32 for bf16 (exact while lines < 2^24)

which replaces the sort-dominated chunked pair pipeline (r2 bench: lexsort over
every 4M-pair chunk + a host sync per chunk left the MXU idle and lost 13x to
one Python core).  Skew vanishes by construction — a giant join line is just a
dense row of M, no splitting or rebalancing required on one chip.

The CIND test, support filter, diagonal and trivially-implied-pair masks all
run elementwise on (tile x captures) blocks of cooc, and the surviving boolean
matrix is bit-packed on device so the host pulls C^2/32 bytes, not C^2 ints
(device-to-host transfer volume is what a pull costs).  The host then just
np.unpackbits + nonzero to read off CIND pairs.
"""

from __future__ import annotations

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from .. import conditions as cc
from ..obs import tracer
from ..runtime import dispatch
from . import segments

# Dep-tile rows per cooc block: (DT x C_pad) f32 tile = 16 MB per 1k captures.
DEFAULT_TILE = 4096
# Dense membership budget: M is (L_pad x C_pad) x elem_bytes in HBM (2 for
# bf16, 1 for int8).  v5e has 16 GB; leave room for the cooc tile, capture
# tables, and XLA scratch.
DENSE_M_BUDGET_BYTES = int(os.environ.get("RDFIND_DENSE_M_BUDGET", 6 << 30))
# bf16 mode's f32 accumulation is exact up to 2^24 lines; past that the bf16
# dense plan must fall back (int8 mode accumulates in int32 — no such cap).
MAX_LINES_EXACT_F32 = 1 << 24

# Membership element type for the cooc matmuls.  "auto" (the default) picks
# int8 on TPU (the MXU: int8 halves membership HBM, doubles the v5e peak —
# 394 int8 TOPS vs 197 bf16 TFLOPS — and its int32 accumulation is exact far
# past f32's 2^24-line cap) and bf16 elsewhere (XLA CPU's generic int8 loops
# are slower than bf16).  RDFIND_COOC_DTYPE pins either mode explicitly;
# outputs are bit-identical.
COOC_DTYPE = os.environ.get("RDFIND_COOC_DTYPE", "auto")
if COOC_DTYPE not in ("auto", "bf16", "int8"):
    raise ValueError(f"RDFIND_COOC_DTYPE must be auto, bf16 or int8, "
                     f"got {COOC_DTYPE!r}")

# Tile-schedule padding policy: on (default), dense plans pad to tile
# multiples (occupancy > 0.9 on real workloads) and skip all-padding dep
# tiles; RDFIND_TILE_SCHEDULE=0 restores the legacy pow2-bucketed plan
# (roughly 2x issued FLOPs in the worst case, but maximal compiled-program
# reuse across datasets).  Both policies are bit-identical in output —
# differential-tested across all four traversal strategies.
TILE_SCHEDULE = os.environ.get("RDFIND_TILE_SCHEDULE", "1").lower() \
    not in ("0", "false", "no")

# Membership-plane width of the packed containment kernel
# (ops/pallas_kernels.py).  "auto" (default) resolves on TPU to the
# SUBBYTE_PLANE_BITS entry of the chip's device_kind and to 8 everywhere
# else.  "8" pins the PR-2 int8 planes unconditionally; "4"/"2" force the
# widened-WK modes (where native sub-byte elements are not available — and
# int2 never is — they run with int8 elements: the same widened-WK grid,
# bit-identical, for differential testing).  Exactness is unchanged in
# every mode: planes are 0/1, accumulation stays int32.
PLANE_BITS = os.environ.get("RDFIND_PLANE_BITS", "auto")
if PLANE_BITS not in ("auto", "2", "4", "8"):
    raise ValueError(f"RDFIND_PLANE_BITS must be auto, 2, 4 or 8, "
                     f"got {PLANE_BITS!r}")

# Native plane width of the packed containment kernel by TPU device_kind.
# Decided from AOT compiles against a described v5e:2x2 topology
# (tests/test_tpu_compile.py), never from an in-process probe: on v5e,
# Mosaic compiles the kernel with int4 elements, refuses native int2
# planes ("Unsupported type for matmul: 'vector<128x256xi2>'"), and
# AOT-compiling a plain XLA int2 dot kills the process with SIGSEGV.  A TPU
# kind missing here is an error — run the compile tests for it, then add
# it.
SUBBYTE_PLANE_BITS = {"TPU v5 lite": 4}

# Fused verdict + minimality pre-filter on the dense CIND sweep: compute
# `cooc == support`, the support/diagonal masks, and the trivially-implied
# pair rule inside the Pallas kernel epilogue, so the int32 cooc count
# matrix lives only in VMEM scratch and never lands in HBM.  "auto"
# (default) engages on the TPU backend only (the kernel would run in the
# slow interpreter elsewhere, so the CPU proxy keeps the XLA path and its
# wall clock cannot regress); RDFIND_FUSE_VERDICT=0 restores the
# materialized cooc_cind_tile path, =1 forces the fused kernel (interpreted
# off-TPU — the differential-test configuration).
FUSE_VERDICT = os.environ.get("RDFIND_FUSE_VERDICT", "auto")
if FUSE_VERDICT not in ("auto", "0", "1"):
    raise ValueError(f"RDFIND_FUSE_VERDICT must be auto, 0 or 1, "
                     f"got {FUSE_VERDICT!r}")

# K-step DMA latency hiding in the packed containment kernel: "auto"
# (default) replaces the "arbitrary"-dimension double buffering of the K
# grid with an explicit pltpu.emit_pipeline inner loop — operand DMAs are
# issued by a manual pipeline that overlaps the previous chunk's MXU pass —
# on TPU, after a one-time probe (ops/pallas_kernels.emit_pipeline_supported)
# has run the API end to end; a failed probe raises there.  Off TPU
# emit_pipeline cannot trace (it asserts the TPU backend even under
# interpret=True), so the grid variant runs.  RDFIND_EMIT_PIPELINE=0 pins
# the PR-6 K-grid double buffering; =1 requests the pipelined kernel, which
# off TPU still runs the (byte-identical) grid variant.
EMIT_PIPELINE = os.environ.get("RDFIND_EMIT_PIPELINE", "auto")
if EMIT_PIPELINE not in ("auto", "0", "1"):
    raise ValueError(f"RDFIND_EMIT_PIPELINE must be auto, 0 or 1, "
                     f"got {EMIT_PIPELINE!r}")

# Sub-tile sparsity skipping: per-(dep-tile x line-block) membership
# popcounts drive the dense sweep schedule — dep tiles whose captures occur
# in no line are dropped outright (both backends), and the fused kernel's
# K-step schedule visits only the nonzero line blocks of each dep tile
# (scalar-prefetched block ids).  Costs one small block-count reduction +
# host pull per sweep; RDFIND_BLOCK_SKIP=0 restores the dense full-range
# schedule, =1 forces it (default "auto" = on whenever the plan has more
# than one block or tile to skip).
BLOCK_SKIP = os.environ.get("RDFIND_BLOCK_SKIP", "auto")
if BLOCK_SKIP not in ("auto", "0", "1"):
    raise ValueError(f"RDFIND_BLOCK_SKIP must be auto, 0 or 1, "
                     f"got {BLOCK_SKIP!r}")

# Row padding granule of the membership matrix under the tile schedule: a
# multiple of every dtype's sublane tile (f32 8, bf16 16, int8 32) with
# enough slack that distinct tiny test datasets still bucket together.
LINE_MULT = 256
# Column granule: the MXU lane width and the 32-bit packing word both divide
# 128, and every dep-tile width is a multiple of it.
CAP_MULT = 128


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolved_cooc_dtype() -> str:
    """The membership dtype actually in effect ("bf16" or "int8").

    Reads COOC_DTYPE at call time (tests monkeypatch the module attribute)."""
    if COOC_DTYPE != "auto":
        return COOC_DTYPE
    return "int8" if _on_tpu() else "bf16"


def tpu_plane_bits() -> int:
    """SUBBYTE_PLANE_BITS entry of this chip's device_kind; raises for a TPU
    kind whose sub-byte support has not been compiled for."""
    kind = jax.devices()[0].device_kind
    if kind not in SUBBYTE_PLANE_BITS:
        raise RuntimeError(
            f"cooc.SUBBYTE_PLANE_BITS has no entry for TPU device_kind "
            f"{kind!r}: AOT-compile the packed containment kernel for it "
            f"(tests/test_tpu_compile.py) and add the narrowest plane width "
            f"that compiles")
    return SUBBYTE_PLANE_BITS[kind]


def int4_elements_native() -> bool:
    """Whether jnp.int4 planes live in VMEM natively: on TPU kinds whose
    SUBBYTE_PLANE_BITS entry is 4.  Elsewhere the nibble-WK mode keeps its
    doubled K-step grid but unpacks to int8 elements — bit-identical,
    differential-testable."""
    return _on_tpu() and tpu_plane_bits() == 4


def resolved_plane_bits() -> int:
    """Plane width of the packed containment kernel (2, 4 or 8).

    Reads PLANE_BITS at call time (tests monkeypatch the module attribute).
    Only meaningful when the resolved cooc dtype is int8 — the bf16 mode
    keeps 16-bit planes."""
    if PLANE_BITS != "auto":
        return int(PLANE_BITS)
    return tpu_plane_bits() if _on_tpu() else 8


def resolved_kernel_dtype() -> str:
    """Unpack dtype of the packed Pallas containment kernel: the resolved
    cooc dtype, narrowed to "int4"/"int2" when a sub-byte plane mode is in
    effect.  The jnp planes fallback keeps the plain cooc dtype (XLA has no
    portable sub-byte contraction); all modes are exact and bit-identical."""
    dtype = resolved_cooc_dtype()
    if dtype == "int8":
        bits = resolved_plane_bits()
        if bits == 2:
            return "int2"
        if bits == 4:
            return "int4"
    return dtype


def emit_pipeline_enabled() -> bool:
    """Whether the packed containment kernel runs its explicit
    pltpu.emit_pipeline K-loop instead of the PR-6 "arbitrary"-dimension
    double buffering.  Reads EMIT_PIPELINE at call time (tests monkeypatch
    the module attribute); the availability probe behind both "auto" and
    the =1 force is cached.  Off TPU, where emit_pipeline cannot trace even
    interpreted, it is False; on TPU a failed probe raises."""
    if EMIT_PIPELINE == "0":
        return False
    from . import pallas_kernels

    if not pallas_kernels.emit_pipeline_supported():
        return False
    if EMIT_PIPELINE == "1":
        return True
    return jax.default_backend() == "tpu"


def resolution_report() -> dict:
    """The single describe() surface for every kernel-mode decision: raw
    knob values next to what they resolved to, so plane-bits /
    emit-pipeline / fuse / block-skip choices are visible in one struct
    instead of three scattered gauges, plus the device they were resolved
    for and the kernel paths dispatched so far in this process
    ("kernels_used").  Published through the metrics shims into run stats
    ("kernel_resolution") and rendered on the shared --debug dense-plan
    line."""
    from . import pallas_kernels

    kernel_dtype = resolved_kernel_dtype()
    return {
        "device_kind": jax.devices()[0].device_kind,
        "cooc_dtype": resolved_cooc_dtype(),
        "plane_bits": resolved_plane_bits(),
        "kernel_dtype": kernel_dtype,
        "plane_elem": pallas_kernels._plane_elem(kernel_dtype),
        "emit_pipeline": emit_pipeline_enabled(),
        "fuse_verdict": fuse_verdict_enabled(),
        "block_skip": block_skip_enabled(),
        "knobs": {
            "RDFIND_COOC_DTYPE": COOC_DTYPE,
            "RDFIND_PLANE_BITS": PLANE_BITS,
            "RDFIND_EMIT_PIPELINE": EMIT_PIPELINE,
            "RDFIND_FUSE_VERDICT": FUSE_VERDICT,
            "RDFIND_BLOCK_SKIP": BLOCK_SKIP,
        },
        "kernels_used": dict(pallas_kernels.KERNELS_USED),
    }


def fuse_verdict_enabled() -> bool:
    """Whether the dense CIND sweep runs the fused verdict kernel.  Reads
    FUSE_VERDICT at call time (tests monkeypatch the module attribute)."""
    if FUSE_VERDICT != "auto":
        return FUSE_VERDICT == "1"
    return jax.default_backend() == "tpu"


def block_skip_enabled() -> bool:
    """Whether the dense sweep schedules around all-zero membership blocks.
    Reads BLOCK_SKIP at call time (tests monkeypatch the module attribute)."""
    if BLOCK_SKIP != "auto":
        return BLOCK_SKIP == "1"
    return True


def round_up(n: int, mult: int) -> int:
    """Smallest multiple of `mult` >= max(n, 1)."""
    return -(-max(int(n), 1) // mult) * mult


def tile_for(c_pad: int, tile_max: int = DEFAULT_TILE) -> int:
    """Largest dep-tile width that divides `c_pad`, is a power-of-two
    multiple of CAP_MULT, and stays <= tile_max.

    Divisibility keeps every host-loop tile start exact under dynamic_slice's
    edge clamping (a clamped start would silently recompute earlier rows and
    emit duplicate pairs); the pow2 structure keeps tile widths MXU-friendly.
    """
    assert c_pad % CAP_MULT == 0, c_pad
    m = c_pad // CAP_MULT
    t = CAP_MULT * (m & -m)  # largest pow2 divisor of m, in columns
    return max(CAP_MULT, min(t, tile_max, c_pad))


def line_block_for(l_pad: int, cap: int = 1024) -> int:
    """K-step line-block granule of the fused sweep: the largest pow2
    multiple of LINE_MULT dividing `l_pad`, capped at `cap` rows (a block's
    two operand tiles then stay well inside VMEM); legacy pow2 plans below
    the row granule run as one block.  Divisibility keeps every block start
    exact — the same contract tile_for enforces on the capture axis."""
    if l_pad % LINE_MULT:
        return l_pad
    m = l_pad // LINE_MULT
    return min(LINE_MULT * (m & -m), cap)


def cap_pad(num_caps: int, mult: int = CAP_MULT) -> int:
    """Capture-axis padding under the active policy: tile-multiple (tight)
    when TILE_SCHEDULE is on, pow2-bucketed otherwise.  `mult` raises the
    granule (the sharded sketch path needs device-count divisibility)."""
    if TILE_SCHEDULE:
        return round_up(num_caps, mult)
    return round_up(max(CAP_MULT, segments.pow2_capacity(num_caps)), mult)


@dataclasses.dataclass(frozen=True)
class DensePlan:
    """Shape plan + tile schedule for the dense cooc path.

    The schedule (dep_tile_starts) enumerates the dep tiles that contain at
    least one real capture; all-padding tiles are never dispatched, and the
    occupancy accounting (real_flops / issued_flops) is what benches report
    as occupancy-corrected MFU instead of padded-FLOP MFU.
    """

    l_pad: int
    c_pad: int
    tile: int
    n_lines: int
    num_caps: int
    dtype: str
    # Raw-roofline rungs (ISSUE 6): resolved containment-kernel plane width,
    # whether the verdict sweep fuses (cooc counts stay in VMEM scratch),
    # the K-step line-block granule, and the data-driven block-skip record
    # (filled by discover_pairs_dense via dataclasses.replace once the
    # membership popcounts are known — shape planning alone cannot know it).
    plane_bits: int = 8
    fuse_verdict: bool = False
    line_block: int = 0
    n_blocks_skipped: int = 0

    def __iter__(self):  # legacy (l_pad, c_pad, tile) unpacking
        return iter((self.l_pad, self.c_pad, self.tile))

    @property
    def dep_tile_starts(self) -> tuple:
        """Dep-tile starts whose tile intersects [0, num_caps)."""
        return tuple(lo for lo in range(0, self.c_pad, self.tile)
                     if lo < self.num_caps)

    @property
    def n_tiles(self) -> int:
        return self.c_pad // self.tile

    @property
    def n_tiles_skipped(self) -> int:
        return self.n_tiles - len(self.dep_tile_starts)

    @property
    def n_line_blocks(self) -> int:
        return self.l_pad // self.line_block if self.line_block else 0

    @property
    def n_blocks(self) -> int:
        """(scheduled dep tile x line block) pairs the full-range sweep
        would visit — the denominator of the block-skip accounting."""
        return self.n_line_blocks * len(self.dep_tile_starts)

    @property
    def issued_flops(self) -> int:
        """MACs*2 actually dispatched by the scheduled tile sweep."""
        return 2 * self.l_pad * self.c_pad * self.tile \
            * len(self.dep_tile_starts)

    @property
    def real_flops(self) -> int:
        """MACs*2 the unpadded workload needs."""
        return 2 * self.n_lines * self.num_caps * self.num_caps

    @property
    def occupancy(self) -> float:
        return self.real_flops / max(self.issued_flops, 1)

    def describe(self) -> dict:
        """Occupancy record for run stats / --debug / bench JSON."""
        return {
            "policy": "tile" if TILE_SCHEDULE else "pow2",
            "dtype": self.dtype,
            "plane_bits": self.plane_bits,
            "fuse_verdict": self.fuse_verdict,
            "l_real": self.n_lines, "l_pad": self.l_pad,
            "c_real": self.num_caps, "c_pad": self.c_pad,
            "tile": self.tile,
            "n_tiles": self.n_tiles,
            "n_tiles_skipped": self.n_tiles_skipped,
            "line_block": self.line_block,
            "n_blocks": self.n_blocks,
            "n_blocks_skipped": self.n_blocks_skipped,
            "issued_flops": self.issued_flops,
            "real_flops": self.real_flops,
            "occupancy": round(self.occupancy, 4),
        }


def cooc_dot(a, b, dims=((0,), (0,))):
    """Exact integer counts from a 0/1-matrix product: accumulate in the
    dtype-matched exact accumulator (f32 for bf16, int32 for int8)."""
    acc = jnp.int32 if a.dtype == jnp.int8 else jnp.float32
    return jax.lax.dot_general(
        a, b, (dims, ((), ())), preferred_element_type=acc).astype(jnp.int32)


def pack_bool(x):
    """(R, C) bool/0-1 -> (R, ceil(C/32)) uint32, little bit order per word.

    The single packing implementation shared by every device stage; the host
    inverse is unpack_cind_bits (np.unpackbits bitorder="little").
    """
    r, c = x.shape
    if c % 32:
        x = jnp.pad(x, ((0, 0), (0, 32 - c % 32)))
        c = x.shape[1]
    lanes = x.astype(jnp.uint32).reshape(r, c // 32, 32)
    weights = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    return (lanes * weights[None, None, :]).sum(axis=2, dtype=jnp.uint32)


def dense_plan(n_lines: int, num_caps: int, tile: int = DEFAULT_TILE):
    """DensePlan for the dense path, or None when it does not fit.

    c_pad is always a multiple of CAP_MULT=128 (MXU lanes and 32-bit packing)
    and of the tile (exact dep-tile starts under dynamic_slice clamping).
    Under the default tile-multiple policy l_pad/c_pad hug the real shape
    (occupancy > 0.9 on non-degenerate workloads); RDFIND_TILE_SCHEDULE=0
    restores the legacy pow2 buckets, whose worst case issues ~2x the rows
    and ~2x the columns (the headline workload measured ~56% row occupancy).
    """
    if n_lines == 0 or num_caps == 0:
        return None
    dtype = resolved_cooc_dtype()
    if dtype != "int8" and n_lines >= MAX_LINES_EXACT_F32:
        return None  # int8 accumulates in int32: exact to 2^31 counts
    if TILE_SCHEDULE:
        l_pad = round_up(n_lines, LINE_MULT)
        c_pad = cap_pad(num_caps)
        tile = tile_for(c_pad, tile)
    else:
        # Legacy pow2 buckets: maximal compiled-program reuse across datasets
        # (segments.pow2_capacity); c_pad a pow2 >= 128 is automatically a
        # multiple of the (pow2) tile.
        l_pad = max(8, segments.pow2_capacity(n_lines))
        c_pad = cap_pad(num_caps)
        tile = min(tile, c_pad)
    elem_bytes = 1 if dtype == "int8" else 2
    if l_pad * c_pad * elem_bytes > DENSE_M_BUDGET_BYTES:
        return None
    return DensePlan(l_pad=l_pad, c_pad=c_pad, tile=tile, n_lines=n_lines,
                     num_caps=num_caps, dtype=dtype,
                     plane_bits=resolved_plane_bits(),
                     fuse_verdict=fuse_verdict_enabled(),
                     line_block=line_block_for(l_pad))


@functools.partial(jax.jit, static_argnames=("l_pad", "c_pad", "dtype"))
def _build_membership(line_gid, line_cap, valid, *, l_pad: int, c_pad: int,
                      dtype: str):
    dt = jnp.int8 if dtype == "int8" else jnp.bfloat16
    li = jnp.where(valid, line_gid, l_pad)
    ci = jnp.where(valid, line_cap, c_pad)
    m = jnp.zeros((l_pad, c_pad), dt)
    return m.at[li, ci].set(jnp.asarray(1, dt), mode="drop")


def build_membership(line_gid, line_cap, valid, *, l_pad: int, c_pad: int,
                     dtype: str | None = None):
    """Scatter (line, capture) rows into the (l_pad, c_pad) 0/1 matrix.

    The element type (resolved_cooc_dtype() by default; `dtype` overrides)
    is a STATIC jit key: the inputs' avals don't carry it, so it must key the
    cache explicitly or a dtype flip would silently reuse the other mode's
    compiled program.  Downstream consumers take `m` itself, whose aval
    re-keys them."""
    return _build_membership(line_gid, line_cap, valid, l_pad=l_pad,
                             c_pad=c_pad,
                             dtype=resolved_cooc_dtype() if dtype is None
                             else dtype)


@functools.partial(jax.jit, static_argnames=("tile",))
def cooc_cind_tile(m, dep_lo, dep_count, cap_code, cap_v1, cap_v2,
                   min_support, *, tile: int):
    """One (tile x C_pad) block of the CIND matrix, bit-packed along refs.

    m: (l_pad, c_pad) membership; dep_lo: first dep capture id of this tile;
    dep_count/cap_*: (c_pad,) per-capture support and identity columns.
    Returns (tile, c_pad // 32) uint32 where bit r of word w in row d means
    "capture dep_lo+d is CIND-included in capture 32w+r".

    The elementwise masks mirror _stage_merge (models/allatonce.py): support
    test, min_support, no self-pairs, and the trivially-implied-pair rule of
    data/Condition.scala:35-43.
    """
    c_pad = m.shape[1]
    m_tile = jax.lax.dynamic_slice(m, (0, dep_lo), (m.shape[0], tile))
    cooc = cooc_dot(m_tile, m)

    d_idx = dep_lo + jnp.arange(tile, dtype=jnp.int32)
    d_safe = jnp.clip(d_idx, 0, c_pad - 1)
    support = dep_count[d_safe][:, None]
    is_cind = (cooc == support) & (support >= min_support)
    is_cind &= d_idx[:, None] != jnp.arange(c_pad, dtype=jnp.int32)[None, :]

    d_code = cap_code[d_safe][:, None]
    d_v1 = cap_v1[d_safe][:, None]
    d_v2 = cap_v2[d_safe][:, None]
    r_code = cap_code[None, :]
    implied = cc.is_subcode(r_code, d_code) & jnp.where(
        cc.first_subcapture(d_code) == r_code,
        cap_v1[None, :] == d_v1, cap_v1[None, :] == d_v2)
    return pack_bool(is_cind & ~implied)


@functools.partial(jax.jit, static_argnames=("kl", "tile"))
def _stage_block_counts(m, *, kl: int, tile: int):
    """(l_pad//kl, c_pad//tile) int32 membership popcounts per
    (line-block x dep-tile) pair — the skew record driving the sub-tile
    skip schedule (the same per-line popcounts the join-line rebalancer
    reads for skew, here reduced at block granularity on device).

    The per-(line, dep-tile) counts come from one MXU product with the
    column -> tile one-hot, accumulated exactly (int32, or f32 below 2^24
    per entry since a tile holds at most DEFAULT_TILE columns).  A
    reshape-and-sum would first convert all of m to the accumulator type:
    4x its bytes for int8, 7 GB at 708k lines x 2688 captures, which did
    not fit a v5e next to m (chip run, PR 21)."""
    l_pad, c_pad = m.shape
    nt = c_pad // tile
    onehot = (jnp.arange(c_pad)[:, None] // tile
              == jnp.arange(nt)[None, :]).astype(m.dtype)
    per_line = cooc_dot(m, onehot, dims=((1,), (0,)))
    return per_line.reshape(l_pad // kl, kl, nt).sum(axis=1)


def _fused_ref_chunk(c_pad: int, cap: int = 16384) -> int:
    """Ref-axis chunk of one fused kernel dispatch: bounds the transient
    uint8 verdict block (tile x chunk) while the packed output stays
    c_pad/8 bytes per row.  Divides c_pad by the tile_for contract."""
    return tile_for(c_pad, cap)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _fused_cind_tile(m, dep_lo, dep_count, cap_code, cap_v1, cap_v2,
                     min_support, block_ids, n_real, *, tile: int,
                     interpret: bool):
    """One (tile x c_pad) CIND block via the fused Pallas kernel.

    Same packed-bitmap contract as cooc_cind_tile, computed without ever
    writing the int32 cooc count matrix to HBM: the kernel accumulates each
    (128 x 128) count block in VMEM scratch and emits the verdict (CIND
    test + support filter + diagonal + trivially-implied mask, the
    _stage_merge semantics) plus the per-dep referenced-set popcount.  The
    K (line) dimension walks only the scalar-prefetched `block_ids`
    (padded entries are compute-guarded), which is where the sub-tile
    sparsity skip happens.  Returns (packed, popc, count): popc is the
    (tile, 1) per-dep CIND count the minimality/extraction stages size
    with, count its scalar sum — callers skip the separate packed_count
    dispatch over the bitmap.
    """
    from . import pallas_kernels

    c_pad = m.shape[1]
    rc = _fused_ref_chunk(c_pad)
    dep_count = jnp.asarray(dep_count, jnp.int32)
    code32 = jnp.asarray(cap_code, jnp.int32)
    v1_32 = jnp.asarray(cap_v1, jnp.int32)
    v2_32 = jnp.asarray(cap_v2, jnp.int32)

    m_tile = jax.lax.dynamic_slice(m, (0, dep_lo), (m.shape[0], tile))
    col = lambda a: jax.lax.dynamic_slice(a, (dep_lo,), (tile,)) \
        .reshape(tile, 1)
    sup_col = col(dep_count)
    ok_col = (sup_col >= jnp.int32(min_support)).astype(jnp.int32)
    gid_col = dep_lo + jnp.arange(tile, dtype=jnp.int32).reshape(tile, 1)
    ridx = jnp.arange(c_pad, dtype=jnp.int32).reshape(1, c_pad)

    packed_chunks, popc = [], None
    for rlo in range(0, c_pad, rc):
        verdict, pc = pallas_kernels.fused_cind_blocks(
            m_tile, m, sup_col, ok_col, gid_col, col(code32), col(v1_32),
            col(v2_32), ridx, code32.reshape(1, c_pad),
            v1_32.reshape(1, c_pad), block_ids, n_real, ref_lo=rlo,
            ref_chunk=rc, interpret=interpret)
        packed_chunks.append(pack_bool(verdict))
        popc = pc if popc is None else popc + pc
    packed = (packed_chunks[0] if len(packed_chunks) == 1
              else jnp.concatenate(packed_chunks, axis=1))
    return packed, popc, popc.sum(dtype=jnp.int32)


def _inbounds(packed, rows, cols):
    """Zero out words outside the [0, rows) x [0, cols) bit region.

    rows/cols are TRACED operands (not static jit keys): the compiled
    programs key only on the pow2-bucketed packed shape, preserving the
    repo's program-reuse policy across lattice levels and datasets."""
    word_idx = jnp.arange(packed.shape[1], dtype=jnp.int32)
    partial = jnp.clip(cols - word_idx * 32, 0, 32)
    # Shift stays in [0, 31]: uint32 << 32 is implementation-defined in XLA,
    # so the partial == 32 case selects the full mask without ever evaluating
    # an out-of-range shift (even in an unselected where branch).
    low = (jnp.uint32(1) << jnp.minimum(partial, 31).astype(jnp.uint32)) \
        - jnp.uint32(1)
    col_mask = jnp.where(partial >= 32, jnp.uint32(0xFFFFFFFF), low)
    row_ok = jnp.arange(packed.shape[0], dtype=jnp.int32) < rows
    return jnp.where(row_ok[:, None], packed & col_mask[None, :], 0)


@jax.jit
def packed_count(packed, rows, cols):
    """Set bits in the in-bounds region; int32 is exact under the
    EXTRACT_DEVICE_ELEMS <= 2^28-bit gate callers apply."""
    return jax.lax.population_count(_inbounds(packed, rows, cols)).sum(
        dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("cap",))
def packed_nonzero(packed, rows, cols, *, cap: int):
    """(row, col) indices of the first `cap` in-bounds set bits (row-major)."""
    from . import sketch

    d, r = jnp.nonzero(sketch.unpack_planes(_inbounds(packed, rows, cols)),
                       size=cap, fill_value=0)
    return d.astype(jnp.int32), r.astype(jnp.int32)


# Device extraction materializes the unpacked relation plus nonzero's scan
# intermediates; past this element count a relation decodes in row strips so
# each strip's intermediates stay under the bound.  2^28 bits also keeps
# packed_count's int32 sum exact.
EXTRACT_DEVICE_ELEMS = 1 << 28

# Device bytes pinned by pending sized-nonzero outputs before a batched pull
# flush.  Without it, near-dense relations could pin index pairs proportional
# to the total set-bit count (32 GB for a saturated 4096 x 2^20-bit sweep)
# while waiting for one giant device_get.
PULL_BYTES_BUDGET = 1 << 28


def _flush_pulls(pend):
    """One batched device_get of pending sized-nonzero outputs.

    pend: list of (key, count, (d_dev, r_dev)).  Returns [(key, d, r)] with
    host int64 arrays truncated to their exact counts."""
    with tracer.span("pull-decode-ids", cat=tracer.CAT_PULL):
        flat = iter(jax.device_get([x for _, _, dr in pend for x in dr]))
    out = []
    for key, c, _ in pend:
        d, r = next(flat), next(flat)
        out.append((key, d[:c].astype(np.int64), r[:c].astype(np.int64)))
    return out


def extract_packed(packed, rows: int, cols: int):
    """Decode a packed bool relation -> host (row, col) int64 index arrays.

    Small enough relations decode on device — an exact popcount dispatch,
    then a sized nonzero — so the host pulls one scalar plus exactly the
    set-bit index pairs, never the bit matrix itself (the multi-MB pull +
    host unpackbits scan dominated the lattice's non-matmul wall clock).
    Oversized relations decode in row strips: each strip's
    unpacked planes + nonzero intermediates stay <= EXTRACT_DEVICE_ELEMS
    bits on device, counts and index pulls are batched so the host syncs
    twice total, and the bit matrix still never reaches the host (the r4
    host fallback pulled C^2/32 bytes per oversized tile — strategy 2's
    second measured bottleneck)."""
    words = packed.shape[1]
    total_bits = packed.shape[0] * words * 32
    if total_bits <= EXTRACT_DEVICE_ELEMS:
        return extract_packed_iter([lambda: (packed, rows, cols)],
                                   total_bits)[0]
    # Strips are just same-shaped small tiles decoded through the shared
    # batched iterator; a partial final strip compiles its own (smaller)
    # program, which the iterator's per-shape thunks already allow (the
    # tile-multiple c_pad policy means words need not be pow2).  tile_bits
    # is clamped for the pathological one-row-over-budget shape
    # (words*32 > EXTRACT_DEVICE_ELEMS), where a single row must decode in
    # one shot anyway and clamping avoids bouncing back into this strip path.
    h = max(1, EXTRACT_DEVICE_ELEMS // (words * 32))
    los = list(range(0, min(rows, packed.shape[0]), h))

    def make(lo):
        return lambda: (packed[lo:lo + h], min(rows - lo, h), cols)

    pairs = extract_packed_iter([make(lo) for lo in los],
                                min(h * words * 32, EXTRACT_DEVICE_ELEMS))
    out_d = [d + lo for lo, (d, _) in zip(los, pairs) if d.size]
    out_r = [r for _, (d, r) in zip(los, pairs) if d.size]
    if not out_d:
        z = np.zeros(0, np.int64)
        return z, z
    return np.concatenate(out_d), np.concatenate(out_r)


def extract_packed_iter(thunks, tile_bits: int):
    """Decode a stream of packed tiles with batched, pipelined host syncs.

    thunks: callables dispatching one tile each, returning (packed, rows,
    cols).  Tiles MAY differ in shape (small_to_large batches its mixed
    lattice relations through one call); `tile_bits` must be an UPPER BOUND
    on any tile's packed bits — it is what bounds how many tiles sit on
    device awaiting decode (EXTRACT_DEVICE_ELEMS per batch), so an
    underestimate breaks the residency math (ADVICE r5).  Each batch costs
    one counts sync; index pulls flush under PULL_BYTES_BUDGET.

    Pipelined schedule (unless RDFIND_SYNC_PASSES forces the serial one):
    batch i+1's tiles are dispatched BEFORE batch i's counts are pulled, so
    tile compute overlaps the count readback and index pulls; the batch size
    is halved to keep the two-batches-in-flight residency inside the same
    EXTRACT_DEVICE_ELEMS budget.  Oversized tiles fall through to
    extract_packed's strip decode.  Returns [(d, r)] host int64 arrays in
    thunk order — the shared decode behind the dense strategy-0 sweep and
    strategy 2's candidate generation.
    """
    if tile_bits > EXTRACT_DEVICE_ELEMS:
        return [extract_packed(*t()[:3]) for t in thunks]
    out = [None] * len(thunks)
    pipelined = not dispatch.sync_passes_forced() and len(thunks) > 1
    batch = max(1, EXTRACT_DEVICE_ELEMS // tile_bits // (2 if pipelined
                                                         else 1))
    empty = (np.zeros(0, np.int64), np.zeros(0, np.int64))

    def launch(lo):
        # A thunk may return a 4th element: the tile's set-bit count already
        # computed on device (the fused kernel's per-dep popcount summed),
        # which replaces the separate packed_count pass over the bitmap.
        group, counts = [], []
        for j, t in enumerate(thunks[lo:lo + batch]):
            res = t()
            p, r, c = res[:3]
            group.append((lo + j, p, r, c))
            counts.append(res[3] if len(res) > 3 else
                          packed_count(p, jnp.int32(r), jnp.int32(c)))
        dispatch.stage_to_host(counts)
        return group, counts

    def drain_batch(group, counts):
        with tracer.span("pull-decode-counts", cat=tracer.CAT_PULL):
            counts = jax.device_get(counts)
        pend, pend_bytes = [], 0

        def drain():
            nonlocal pend, pend_bytes
            for k, d, r in _flush_pulls(pend):
                out[k] = (d, r)
            pend, pend_bytes = [], 0

        for n, (k, p, rows, cols) in zip(counts, group):
            n = int(n)
            if not n:
                out[k] = empty
                continue
            cap = segments.pow2_capacity(n)
            pend.append((k, n, packed_nonzero(p, jnp.int32(rows),
                                              jnp.int32(cols), cap=cap)))
            dispatch.stage_to_host(pend[-1][2])
            pend_bytes += 8 * cap
            if pend_bytes >= PULL_BYTES_BUDGET:
                drain()
        drain()

    prev = None
    for lo in range(0, len(thunks), batch):
        cur = launch(lo)
        if not pipelined:
            drain_batch(*cur)
            continue
        if prev is not None:
            drain_batch(*prev)
        prev = cur
    if prev is not None:
        drain_batch(*prev)
    return out


def unpack_cind_bits(packed: np.ndarray, c_pad: int) -> np.ndarray:
    """(tile, c_pad//32) uint32 -> (tile, c_pad) 0/1 uint8 on host."""
    return np.unpackbits(
        np.ascontiguousarray(packed).view(np.uint8),
        axis=1, bitorder="little")[:, :c_pad]


def discover_pairs_dense(m, dep_count, cap_code, cap_v1, cap_v2, min_support,
                         num_caps: int, tile: int, starts=None, plan=None,
                         stats=None):
    """Run the tiled cooc pass; return (dep_id, ref_id, support) numpy arrays.

    m: (l_pad, c_pad) device membership matrix.  The host loops over the
    scheduled dep tiles (`starts`, default: every tile intersecting
    [0, num_caps) — all-padding tiles are never dispatched) sending the
    packed CIND blocks, then decodes them on device: one batched pull of all
    tile popcounts, one batched pull of the sized nonzeros — only the
    set-bit index pairs ever reach the host (same two-phase decode as
    extract_packed, batched across tiles).

    Under the fused-verdict policy (`plan.fuse_verdict` /
    fuse_verdict_enabled) each tile runs the fused Pallas kernel instead of
    the materialized cooc_cind_tile, and its in-kernel popcount replaces the
    packed_count dispatch.  With block skipping on, per-(dep-tile x
    line-block) membership popcounts prune the schedule first: dep tiles
    whose captures occur in no line are dropped outright (both backends),
    and the fused kernel's K steps visit only each tile's nonzero line
    blocks.  `stats` (via the obs shims) records the skip accounting into
    the dense_plan struct.
    """
    import math

    l_pad, c_pad = m.shape
    dep_count_d = jnp.asarray(dep_count, jnp.int32)
    code_d = jnp.asarray(cap_code, jnp.int32)
    v1_d = jnp.asarray(cap_v1, jnp.int32)
    v2_d = jnp.asarray(cap_v2, jnp.int32)
    ms = jnp.int32(min_support)

    los = list(starts) if starts is not None else list(range(0, num_caps,
                                                             tile))

    kl = (plan.line_block if plan is not None and plan.line_block
          else line_block_for(l_pad))
    n_line_blocks = l_pad // kl
    from . import pallas_kernels

    fused = (plan.fuse_verdict if plan is not None else fuse_verdict_enabled())
    fused = fused and tile % 128 == 0 and c_pad % 128 == 0 \
        and l_pad % kl == 0 and l_pad % 8 == 0
    interp = jax.default_backend() != "tpu"

    # Sub-tile skip schedule: one small device reduction + host pull of the
    # (n_line_blocks x n_tiles) popcount grid, amortized against the sweep.
    block_counts = None
    if block_skip_enabled() and l_pad % kl == 0 and c_pad % tile == 0 \
            and (n_line_blocks > 1 or len(los) > 1):
        with tracer.span("pull-block-counts", cat=tracer.CAT_PULL):
            block_counts = np.asarray(_stage_block_counts(m, kl=kl,
                                                          tile=tile))
    n_blocks_total = n_line_blocks * len(los)
    n_blocks_skipped = n_tiles_data_skipped = 0
    tile_blocks = {}
    if block_counts is not None:
        kept = []
        for lo in los:
            col = block_counts[:, lo // tile]
            nz = np.flatnonzero(col).astype(np.int32)
            if nz.size == 0:
                # All-zero dep tile: its captures occur in no line, so no
                # verdict bit can set — drop it from the schedule entirely.
                n_tiles_data_skipped += 1
                n_blocks_skipped += n_line_blocks
                continue
            kept.append(lo)
            if fused:
                tile_blocks[lo] = nz
                n_blocks_skipped += n_line_blocks - nz.size
        los = kept
    if stats is not None:
        from ..obs import datastats, metrics
        metrics.gauge_set(stats, "n_blocks_skipped", n_blocks_skipped)
        metrics.struct_update(stats, "dense_plan",
                              n_blocks_skipped=n_blocks_skipped,
                              n_tiles_data_skipped=n_tiles_data_skipped)
        if datastats.enabled():
            datastats.publish_block_skip(stats, n_blocks=n_blocks_total,
                                         n_blocks_skipped=n_blocks_skipped)

    def make(lo):
        def thunk():
            pallas_kernels.KERNELS_USED["materialized_verdict"] += 1
            return (cooc_cind_tile(m, jnp.int32(lo), dep_count_d, code_d,
                                   v1_d, v2_d, ms, tile=tile),
                    min(num_caps - lo, tile), num_caps)

        return thunk

    def make_fused(lo):
        nz = tile_blocks.get(lo)
        if nz is None:
            nz = np.arange(n_line_blocks, dtype=np.int32)
        # Bucket the K grid to a pow2 so retraces stay logarithmic in the
        # block count; padded steps fetch block 0 and are compute-guarded.
        bucket = 1 << max(0, math.ceil(math.log2(nz.size)))
        bids = jnp.asarray(np.pad(nz, (0, bucket - nz.size)))
        nr = jnp.asarray(np.full(1, nz.size, np.int32))

        def thunk():
            pallas_kernels.KERNELS_USED["fused_verdict"] += 1
            packed, _, count = _fused_cind_tile(
                m, jnp.int32(lo), dep_count_d, code_d, v1_d, v2_d, ms,
                bids, nr, tile=tile, interpret=interp)
            return packed, min(num_caps - lo, tile), num_caps, count

        return thunk

    pairs = extract_packed_iter(
        [(make_fused if fused else make)(lo) for lo in los], tile * c_pad)
    deps = [d + lo for lo, (d, _) in zip(los, pairs) if d.size]
    refs = [r for _, (d, r) in zip(los, pairs) if d.size]
    dep_id = np.concatenate(deps) if deps else np.zeros(0, np.int64)
    ref_id = np.concatenate(refs) if refs else np.zeros(0, np.int64)
    support = np.asarray(dep_count)[dep_id] if dep_id.size else np.zeros(0, np.int64)
    return dep_id, ref_id, support
