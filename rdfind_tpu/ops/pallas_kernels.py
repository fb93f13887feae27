"""Pallas TPU kernels for the packed-bitset hot path.

The reference's compute-critical "native" surface is its forked-Guava Bloom
filter (SURVEY.md §2: exportBits/intersect + SpectralBloomFilter) — the bit
twiddling under every approximate strategy.  Here that surface is the packed
(rows × bits/32) uint32 sketch matrix (ops/sketch.py), and its hot op is the
containment matmul: "which hash-bit sets are fully contained in which sketch
rows", for all (dep, ref) pairs at once.

The jnp path (sketch.contains_matrix) unpacks both sides to full 0/1 planes in
HBM — a 32x write + read amplification of pure memory traffic — before the MXU
contraction.  The kernel below never materializes planes: each grid step DMAs a
packed (TILE, WK) uint32 tile into VMEM, unpacks it in-register, and feeds the
MXU with a (TILE, WK*32) contraction, accumulating across word chunks in a VMEM
scratch.  HBM traffic drops to the packed bytes.

MXU-rate notes (the round-6 rework):

  * planes unpack to **int8 by default** (int32 accumulation — exact, counts
    <= bits): half the VMEM per unpacked operand and 2x the MXU rate of the
    bf16 fallback on int8-capable chips (v5e: 394 int8 TOPS vs 197 bf16
    TFLOPS).  `unpack_dtype="bf16"` keeps the old formulation for backends
    whose MXU has no int8 path — both modes are bit-exact vs the jnp planes
    path (counts are small integers either way).
  * **int4 nibble planes** (`unpack_dtype="int4"`, the round-7 rung via
    RDFIND_PLANE_BITS) halve the element again: WK widens to 512 words =
    16384 contraction lanes per K step, so each MXU pass covers twice
    int8's K-dim at the same VMEM budget.  Accumulation stays int32 (still
    exact).  Native int4 elements are used on TPU kinds listed in
    cooc.SUBBYTE_PLANE_BITS; elsewhere the same widened-WK grid runs with
    int8 elements (bit-identical — the emulation the CPU parity tests
    exercise, since XLA CPU rejects sub-byte conversion outright).
  * **int2 crumb planes** (`unpack_dtype="int2"`, RDFIND_PLANE_BITS=2, the
    round-12 rung) keep a quadrupled WK of 1024 words but always store int8
    elements: Mosaic refuses native int2 matmuls on v5e (`Unsupported type
    for matmul: 'vector<128x256xi2>'`, AOT compile) and AOT-compiling the
    plain XLA int2 dot kills the compiler with SIGSEGV, so no int2 element
    type is reachable.
  * the **dep-tile unpack is hoisted out of the ref-tile grid dimension**:
    the ref (j) dimension revisits the same dep tile nj times, so the shifted
    planes are computed once at j == 0 into a persistent VMEM scratch and
    re-read for every later j — the per-step VPU work drops to one ref-tile
    unpack.  The hoist is skipped (per-step unpack, as before) only when the
    full-width dep planes would not fit the scratch budget.
  * WK (words per K step) widens with the int8 VMEM savings, so each K-grid
    DMA moves a larger packed block and the MXU sees longer contractions.
  * the K grid dimension is marked "arbitrary" (sequential revisiting) in
    dimension_semantics, which is what lets Mosaic double-buffer the K-step
    operand DMAs against the matmul of the previous chunk; the ref-tile (j)
    dimension is also "arbitrary" because the hoisted scratch carries state
    across it.
  * **explicit K-step pipelining** (RDFIND_EMIT_PIPELINE, the round-12
    rung): on TPU, where a one-time probe runs `pltpu.emit_pipeline` end
    to end (it asserts the TPU backend even under interpret=True, so off
    TPU it is never selected), the K grid dimension moves into a manual
    inner pipeline: the ref-side packed chunks stay HBM-resident
    (memory_space=ANY) and the
    pipeline's own double-buffered DMAs overlap each chunk's copy-in with
    the previous chunk's MXU pass, replacing Mosaic's implicit
    "arbitrary"-dimension buffering with an explicitly scheduled one.  The
    dep tile is fetched full-width once per (i, j) step and its planes
    hoisted exactly as in the grid variant, so outputs are bit-identical
    across emit on/off — the parity matrix asserts it.

Layout notes (see /opt/skills/guides/pallas_guide.md): Mosaic cannot slice the
lane dimension at non-128-aligned offsets, so the unpack avoids slicing
entirely: `pltpu.repeat(x, 32, axis=1)` tiles the packed words 32x along lanes
(np.tile semantics on the installed jax: lane j holds word j % WK), and the
per-lane shift is j // WK.  That yields planes in *bit-major* lane order — a
fixed permutation of the contraction dimension, harmless because both
operands unpack identically and the dot product is permutation-invariant.  Narrowing casts out of uint32
go through int32 (Mosaic has no direct uint32->bf16 lowering, r2 bench
failure; the int8 path keeps the same two-step shape for symmetry).
"""

from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_D = 128
TILE_R = 128
# Host-side dispatch counts per kernel path ("packed_contains",
# "packed_contains_emit", "fused_verdict", "materialized_verdict"):
# cooc.resolution_report() publishes them so a run shows which kernels it
# actually used, not only which ones the policy resolved to.
KERNELS_USED = collections.Counter()
# Words per K grid step, by unpack dtype: the unpacked operand tile is
# (TILE, WK*32) elements in VMEM, so int8's 1-byte planes afford twice the
# chunk of bf16 at the same budget (256 words = 8192 contraction lanes = 1 MB
# per int8 operand tile) — larger K-step DMAs, longer MXU contractions.
# int4 nibble planes (RDFIND_PLANE_BITS=4) halve the element again: 512
# words = 16384 contraction lanes per step, so each MXU pass covers twice
# int8's K-dim at the same VMEM budget; int2 crumb planes
# (RDFIND_PLANE_BITS=2) halve once more to 1024 words = 32768 lanes.
# Exactness is untouched — planes are 0/1 in every width and accumulation
# stays int32.
WK_MAX = {"int2": 1024, "int4": 512, "int8": 256, "bf16": 128}
# Bits per unpacked plane element, keyed by unpack dtype (the VMEM/hoist
# budget arithmetic; int4/int2 planes may be stored as int8 *elements* —
# see _plane_elem — but keep their widened WK grid either way).
PLANE_ELEM_BITS = {"int2": 2, "int4": 4, "int8": 8, "bf16": 16}
# VMEM budget for the hoisted full-width dep planes (TILE_D x bits x elem
# bytes).  4 MB covers bits <= 65536 in int4 / 32768 in int8 / 16384 in
# bf16 and leaves the double-buffered operand tiles + accumulator well
# inside the ~16 MB core budget; wider sketches fall back to the per-step
# unpack.
HOIST_PLANE_BUDGET = 4 << 20


def _default_unpack_dtype() -> str:
    """The resolved kernel dtype: int4 nibble planes where the plane-bits
    policy engages, else int8 wherever the backend's int8 matmul path pays
    off (the cooc probes), bf16 elsewhere or when pinned via
    RDFIND_COOC_DTYPE — one policy for every containment contraction."""
    from . import cooc

    return cooc.resolved_kernel_dtype()


def _plane_elem(dtype: str) -> str:
    """Resolved element type the planes are actually stored/contracted in.

    "int4" planes use native jnp.int4 elements where cooc says the chip
    supports them (cooc.int4_elements_native); elsewhere, and always for
    "int2", the sub-byte mode keeps its widened-WK grid but stores int8
    elements — the arithmetic is identical (0/1 planes, int32
    accumulation), so outputs are bit-identical and every mode stays
    differential-testable on CPU, whose XLA rejects sub-byte conversions
    outright.  The result is a STATIC jit key alongside unpack_dtype."""
    from . import cooc

    if dtype == "int4":
        return "int4" if cooc.int4_elements_native() else "int8"
    if dtype == "int2":
        return "int8"
    return dtype


_PLANE_JNP = {"bf16": jnp.bfloat16, "int8": jnp.int8, "int4": jnp.int4}


def _unpack_tile(x, plane_dt):
    """(TILE, WK) packed uint32 -> (TILE, WK*32) 0/1 planes in `plane_dt`.

    Lane j of the result is bit (j // WK) of word (j % WK) — a fixed
    permutation of the bits, harmless because both operands unpack
    identically and the dot product is permutation-invariant.  Only
    full-tile ops: repeat, iota, shift, compare — no lane slicing (Mosaic
    requires lane-dim slice offsets to be 128-aligned, which word steps are
    not).
    """
    wk = x.shape[1]
    rep = pltpu.repeat(x, 32, axis=1)
    lane = jax.lax.broadcasted_iota(jnp.uint32, rep.shape, 1)
    shifts = jax.lax.div(lane, jnp.uint32(wk))
    bits = ((rep >> shifts) & jnp.uint32(1)).astype(jnp.int32)
    return bits.astype(plane_dt)


def _contains_kernel(s_ref, r_ref, popc_ref, out_ref, s_plane_ref, acc_ref, *,
                     nk: int, wk: int, plane_dt, hoist: bool, acc_dt):
    """One (TILE_D, TILE_R) tile of the containment matrix.

    s_ref: (TILE_D, WK) packed dep sketches; r_ref: (TILE_R, WK) packed ref bit
    sets; popc_ref: (1, TILE_R) per-ref set bit counts.  out[d, r] = 1 iff every
    set bit of ref r is set in sketch d, tested as <unpacked s, unpacked r> ==
    popcount(r) — the same MXU formulation as the jnp path, minus the HBM
    planes.  The K grid dim accumulates word chunks into acc_ref; with `hoist`,
    s_plane_ref carries the full-width unpacked dep planes across the ref (j)
    dimension, filled once per (i, k) while j == 0.
    """
    k = pl.program_id(2)
    j = pl.program_id(1)
    wk32 = wk * 32

    @pl.when(k == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    if hoist:
        # nk == 1 keeps the chunk offset static; otherwise wk32 is a
        # 128-multiple (wk == WK_MAX there), so the dynamic lane offset stays
        # Mosaic-aligned.
        chunk = (slice(0, wk32) if nk == 1
                 else pl.ds(k * wk32, wk32))

        @pl.when(j == 0)
        def _fill():
            s_plane_ref[:, chunk] = _unpack_tile(s_ref[:], plane_dt)

        s_b = s_plane_ref[:, chunk]
    else:
        s_b = _unpack_tile(s_ref[:], plane_dt)
    r_b = _unpack_tile(r_ref[:], plane_dt)
    acc_ref[:] += jax.lax.dot_general(
        s_b, r_b, (((1,), (1,)), ((), ())),
        preferred_element_type=acc_dt)

    @pl.when(k == nk - 1)
    def _finalize():
        out_ref[:] = (acc_ref[:].astype(jnp.int32) == popc_ref[:]).astype(jnp.uint8)


class KernelProbeError(RuntimeError):
    """A kernel-mode probe failed on the TPU.  Raised instead of quietly
    selecting another kernel, so a chip run never swaps kernels unseen."""


@functools.lru_cache(maxsize=1)
def emit_pipeline_supported() -> bool:
    """Whether pltpu.emit_pipeline traces AND runs here.

    Off TPU it never can (the API asserts the TPU backend at trace time
    even under interpret=True), so the answer there is False without a
    probe.  On TPU a minimal two-step accumulation pipeline runs end to
    end once; a failure or a wrong sum raises KernelProbeError naming the
    probe — it never falls back in silence."""
    if jax.default_backend() != "tpu":
        return False

    def kern(x_hbm, o_ref, acc_ref):
        acc_ref[:] = jnp.zeros_like(acc_ref)

        def body(x_ref):
            acc_ref[:] += x_ref[:]

        pltpu.emit_pipeline(
            body, grid=(2,),
            in_specs=[pl.BlockSpec((8, 128), lambda k: (k, 0))])(x_hbm)
        o_ref[:] = acc_ref[:]

    def probe(x):
        return pl.pallas_call(
            kern,
            out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((8, 128), jnp.float32)],
        )(x)

    try:
        # Lowered and compiled on its own, then run on a host array: the
        # first call can come from inside an outer jit trace, whose tracers
        # must not reach (or be captured by) the probe kernel.
        x = np.ones((16, 128), np.float32)
        compiled = jax.jit(probe).lower(
            jax.ShapeDtypeStruct(x.shape, x.dtype)).compile()
        got = float(np.asarray(compiled(x))[0, 0])
    except Exception as e:
        raise KernelProbeError(
            f"emit_pipeline_supported: the emit_pipeline probe kernel "
            f"failed on {jax.devices()[0].device_kind}: "
            f"{type(e).__name__}: {e}") from e
    if got != 2.0:
        raise KernelProbeError(
            f"emit_pipeline_supported: the probe kernel summed to {got}, "
            f"want 2.0")
    return True


def _contains_kernel_emit(s_ref, r_hbm, popc_ref, out_ref, s_plane_ref,
                          acc_ref, step_ref, *, nk: int, wk: int, plane_dt,
                          hoist: bool, acc_dt):
    """The emit-pipeline variant of _contains_kernel: outer grid (i, j)
    only; the K dimension runs as an explicit pltpu.emit_pipeline whose
    double-buffered DMAs stream the packed ref chunks out of HBM
    (memory_space=ANY) while the previous chunk's MXU pass runs.  The dep
    tile arrives full-width in VMEM once per (i, j) step; its planes are
    hoisted into scratch at j == 0 exactly as in the grid variant (chunked
    unpack — the uint32 repeat intermediate must stay one chunk wide).
    step_ref (SMEM) tracks the inner step because the pipeline body runs
    under its own grid env, where pl.program_id no longer names the outer
    axes."""
    j = pl.program_id(1)
    wk32 = wk * 32
    acc_ref[:] = jnp.zeros_like(acc_ref)
    step_ref[0] = 0

    if hoist:
        @pl.when(j == 0)
        def _fill():
            for kk in range(nk):  # static unroll: one chunk-wide unpack each
                s_plane_ref[:, kk * wk32:(kk + 1) * wk32] = _unpack_tile(
                    s_ref[:, kk * wk:(kk + 1) * wk], plane_dt)

    def body(r_ref):
        k = step_ref[0]
        if hoist:
            # nk == 1 keeps the chunk offset static; otherwise wk32 is a
            # 128-multiple (wk == WK_MAX there), so the dynamic lane offset
            # stays Mosaic-aligned — same contract as the grid variant.
            chunk = (slice(0, wk32) if nk == 1 else pl.ds(k * wk32, wk32))
            s_b = s_plane_ref[:, chunk]
        else:
            pchunk = (slice(0, wk) if nk == 1 else pl.ds(k * wk, wk))
            s_b = _unpack_tile(s_ref[:, pchunk], plane_dt)
        r_b = _unpack_tile(r_ref[:], plane_dt)
        acc_ref[:] += jax.lax.dot_general(
            s_b, r_b, (((1,), (1,)), ((), ())),
            preferred_element_type=acc_dt)
        step_ref[0] = k + 1

    pltpu.emit_pipeline(
        body, grid=(nk,),
        in_specs=[pl.BlockSpec((popc_ref.shape[1], wk),
                               lambda k: (j, k))])(r_hbm)
    out_ref[:] = (acc_ref[:].astype(jnp.int32) == popc_ref[:]).astype(jnp.uint8)


def packed_contains_matrix(sketch_packed, ref_packed, ref_popc, *,
                           interpret: bool = False,
                           unpack_dtype: str | None = None,
                           emit_pipeline: bool | None = None):
    """(D, R) uint8 containment matrix from packed uint32 rows.

    sketch_packed: (D, W) packed dep sketches; ref_packed: (R, W) packed ref bit
    sets; ref_popc: (R,) int32 popcounts of each ref row.  D and R must be
    multiples of the 128-lane tile; W a power-of-two number of words (bits a
    power of two >= 32, as ops/sketch.py enforces).  `interpret=True` runs the
    kernel in the Pallas interpreter (CPU tests).  `unpack_dtype` selects the
    in-register plane type ("int8" wherever int8 matmul lowers — the default —
    else "bf16"); every mode is exact and bit-identical.  `emit_pipeline`
    selects the explicit K-step pipeline (default: the resolved
    RDFIND_EMIT_PIPELINE policy) where the K-step block is a whole number
    of 128-lane tiles; off TPU, where the API cannot run, and for narrower
    sketches the request takes the grid variant — same outputs.
    """
    if unpack_dtype is None:
        unpack_dtype = _default_unpack_dtype()
    if unpack_dtype not in WK_MAX:
        raise ValueError(f"unpack_dtype must be int2, int4, int8 or bf16, "
                         f"got {unpack_dtype!r}")
    if emit_pipeline is None:
        from . import cooc

        emit_pipeline = cooc.emit_pipeline_enabled()
    # The resolved plane element type and the emit-pipeline resolution key
    # the jit cache (PR-2's static-key discipline extended to plane width
    # and K-step schedule): a monkeypatched flip must retrace the kernel,
    # not reuse the other mode's program.
    # The emit variant DMAs (TILE_R, wk)-word ref blocks itself, and Mosaic
    # refuses a block narrower than the 128-lane tiling ("Slice shape along
    # dimension 1 must be aligned to tiling (128), but is 64", chip run,
    # PR 21); narrower sketches take the grid variant, whose full-width
    # blocks need no slice.
    wk = min(sketch_packed.shape[1], WK_MAX[unpack_dtype])
    emit = bool(emit_pipeline) and wk % 128 == 0 \
        and emit_pipeline_supported()
    KERNELS_USED["packed_contains_emit" if emit else "packed_contains"] += 1
    return _packed_contains_matrix(sketch_packed, ref_packed, ref_popc,
                                   interpret=interpret,
                                   unpack_dtype=unpack_dtype,
                                   plane_elem=_plane_elem(unpack_dtype),
                                   emit=emit)


@functools.partial(jax.jit, static_argnames=("interpret", "unpack_dtype",
                                             "plane_elem", "emit"))
def _packed_contains_matrix(sketch_packed, ref_packed, ref_popc, *,
                            interpret: bool, unpack_dtype: str,
                            plane_elem: str, emit: bool = False):
    d, w = sketch_packed.shape
    r = ref_packed.shape[0]
    wk = min(w, WK_MAX[unpack_dtype])
    if d % TILE_D or r % TILE_R or w % wk:
        raise ValueError(f"shapes must be tile-aligned, got D={d} R={r} W={w}")
    nk = w // wk
    # Budget arithmetic follows the unpack *mode* (int4/int2 plan for
    # sub-byte VMEM even when elements emulate as int8 — the WK grid must
    # not depend on the emulation fallback or the two would compile
    # different K steps).
    elem_bits = PLANE_ELEM_BITS[unpack_dtype]
    plane_dt = _PLANE_JNP.get(plane_elem, jnp.int8)
    acc_dt = jnp.float32 if unpack_dtype == "bf16" else jnp.int32
    hoist = TILE_D * w * 32 * elem_bits // 8 <= HOIST_PLANE_BUDGET
    if emit:
        kernel = functools.partial(_contains_kernel_emit, nk=nk, wk=wk,
                                   plane_dt=plane_dt,
                                   hoist=hoist, acc_dt=acc_dt)
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((d, r), jnp.uint8),
            grid=(d // TILE_D, r // TILE_R),
            in_specs=[
                # Dep tile full-width in VMEM (packed words are 4 bytes x W
                # <= 8 KB per row — far under the plane scratch itself);
                # ref side stays HBM-resident, chunks DMAed by the inner
                # pipeline.
                pl.BlockSpec((TILE_D, w), lambda i, j: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((1, TILE_R), lambda i, j: (0, j),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((TILE_D, TILE_R), lambda i, j: (i, j),
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((TILE_D, (w if hoist else wk) * 32), plane_dt),
                pltpu.VMEM((TILE_D, TILE_R), acc_dt),
                pltpu.SMEM((1,), jnp.int32),
            ],
            # j is "arbitrary": the hoisted dep planes carry state across
            # the ref tiles; the K dimension lives inside the kernel now.
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
        )(sketch_packed, ref_packed, ref_popc.reshape(1, r))
    grid = (d // TILE_D, r // TILE_R, nk)
    kernel = functools.partial(_contains_kernel, nk=nk, wk=wk,
                               plane_dt=plane_dt,
                               hoist=hoist, acc_dt=acc_dt)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((d, r), jnp.uint8),
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE_D, wk), lambda i, j, k: (i, k),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((TILE_R, wk), lambda i, j, k: (j, k),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, TILE_R), lambda i, j, k: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((TILE_D, TILE_R), lambda i, j, k: (i, j),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            # Hoisted dep planes (full width when hoisting, one chunk's worth
            # of scratch otherwise so the allocation stays tiny and unused).
            pltpu.VMEM((TILE_D, (w if hoist else wk) * 32), plane_dt),
            pltpu.VMEM((TILE_D, TILE_R), acc_dt),
        ],
        # j and k are "arbitrary": j carries the hoisted-scratch state
        # sequentially, and k's sequential revisiting is what Mosaic
        # double-buffers the K-step operand DMAs across.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(sketch_packed, ref_packed, ref_popc.reshape(1, r))


# ---------------------------------------------------------------------------
# Fused verdict + minimality pre-filter kernel (ISSUE 6 rung 2): the dense
# CIND sweep without materializing the cooc count matrix in HBM.
#
# The materialized path (cooc.cooc_cind_tile) computes a (tile x c_pad)
# int32 count matrix as one XLA dot — which lands in HBM between the dot
# and the elementwise verdict/mask ops — then compares, masks, and packs.
# Here each (128 x 128) count block only ever exists in a VMEM scratch
# accumulator; the epilogue applies the full verdict in-register (CIND test,
# support filter, diagonal, and the trivially-implied-pair rule of
# data/Condition.scala:35-43 — the same masks _stage_merge applies) and
# emits a uint8 verdict tile (4x smaller than the counts; packing to 32-bit
# words happens in the enclosing jit — Mosaic exposes no lane-group
# reduction to pack in-kernel) plus the per-dep referenced-set popcount the
# minimality/extraction stages size with.
#
# K-step streaming (rungs 3+4): the line dimension walks a scalar-prefetched
# block-id schedule, so all-zero (dep-tile x line-block) pairs — per-block
# membership popcounts, the join-line skew record — are never fetched, and
# the j/k grid dims are "arbitrary" so Mosaic double-buffers the K-step
# operand DMAs against the previous block's matmul.  Of the two explicit
# K-step mechanisms the roofline plan names, this kernel rides the
# scalar-prefetched grid (its K schedule is data-dependent — the block-id
# list IS the prefetched scalar, already an explicitly scheduled,
# double-buffered K loop), while the containment kernel above carries the
# pltpu.emit_pipeline variant (RDFIND_EMIT_PIPELINE).  Padded schedule
# entries fetch block 0 and are compute-guarded by the prefetched
# real-block count.
# ---------------------------------------------------------------------------

CIND_BLOCK_D = 128
CIND_BLOCK_R = 128


def _fused_cind_kernel(bids_ref, nreal_ref, md_ref, mr_ref, sup_ref, ok_ref,
                       gid_ref, dcode_ref, dv1_ref, dv2_ref, ridx_ref,
                       rcode_ref, rv1_ref, verdict_ref, popc_ref, acc_ref, *,
                       nk: int, acc_dt):
    from .. import conditions as cc

    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(k < nreal_ref[0])
    def _accum():
        acc_ref[:] += jax.lax.dot_general(
            md_ref[:], mr_ref[:], (((0,), (0,)), ((), ())),
            preferred_element_type=acc_dt)

    @pl.when(k == nk - 1)
    def _finalize():
        # f32 accumulation (bf16 membership) is exact below 2^24 lines —
        # the same bound the dense plan enforces — so the cast is exact.
        cooc = acc_ref[:].astype(jnp.int32)
        sup = sup_ref[:]                      # (BLOCK_D, 1) broadcasts
        is_cind = (cooc == sup) & (ok_ref[:] != 0)
        is_cind &= gid_ref[:] != ridx_ref[:]  # no self-pairs
        d_code = dcode_ref[:]
        r_code = rcode_ref[:]
        first = cc.first_subcapture(d_code) == r_code
        implied = cc.is_subcode(r_code, d_code) & (
            (first & (rv1_ref[:] == dv1_ref[:]))
            | (~first & (rv1_ref[:] == dv2_ref[:])))
        v = is_cind & ~implied
        verdict_ref[:] = v.astype(jnp.uint8)
        row = jnp.sum(v.astype(jnp.int32), axis=1, keepdims=True)

        @pl.when(j == 0)
        def _set():
            popc_ref[:] = row

        @pl.when(j != 0)
        def _add():
            popc_ref[:] += row


def fused_cind_blocks(m_dep, m, sup_col, ok_col, gid_col, dcode_col, dv1_col,
                      dv2_col, ridx_row, rcode_row, rv1_row, block_ids,
                      n_real, *, ref_lo: int, ref_chunk: int,
                      interpret: bool = False):
    """(tile x ref_chunk) fused CIND verdict + (tile, 1) per-dep popcount.

    m_dep: (l_pad, tile) dep-slice of the membership matrix; m: (l_pad,
    c_pad) the full matrix (the ref side reads blocks at a static `ref_lo`
    column offset through the index map — no slice copy).  The *_col
    operands are (tile, 1) per-dep columns (support, support>=min_support,
    global capture id, code, v1, v2); the *_row operands (1, c_pad)
    per-ref rows.  block_ids/n_real: the scalar-prefetched K schedule —
    int32 (nk,) line-block ids (entries past n_real are padding) and the
    (1,) real count.
    """
    l_pad, tile = m_dep.shape
    c_pad = m.shape[1]
    nk = block_ids.shape[0]
    kl = _fused_kl(l_pad)
    if tile % CIND_BLOCK_D or ref_chunk % CIND_BLOCK_R or l_pad % kl:
        raise ValueError(f"fused tile not block-aligned: tile={tile} "
                         f"ref_chunk={ref_chunk} l_pad={l_pad}")
    acc_dt = jnp.float32 if m.dtype == jnp.bfloat16 else jnp.int32
    grid = (tile // CIND_BLOCK_D, ref_chunk // CIND_BLOCK_R, nk)
    rb = ref_lo // CIND_BLOCK_R
    kernel = functools.partial(_fused_cind_kernel, nk=nk, acc_dt=acc_dt)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((kl, CIND_BLOCK_D),
                         lambda i, j, k, b, n: (b[k], i)),
            pl.BlockSpec((kl, CIND_BLOCK_R),
                         lambda i, j, k, b, n: (b[k], rb + j)),
            pl.BlockSpec((CIND_BLOCK_D, 1), lambda i, j, k, b, n: (i, 0)),
            pl.BlockSpec((CIND_BLOCK_D, 1), lambda i, j, k, b, n: (i, 0)),
            pl.BlockSpec((CIND_BLOCK_D, 1), lambda i, j, k, b, n: (i, 0)),
            pl.BlockSpec((CIND_BLOCK_D, 1), lambda i, j, k, b, n: (i, 0)),
            pl.BlockSpec((CIND_BLOCK_D, 1), lambda i, j, k, b, n: (i, 0)),
            pl.BlockSpec((CIND_BLOCK_D, 1), lambda i, j, k, b, n: (i, 0)),
            pl.BlockSpec((1, CIND_BLOCK_R), lambda i, j, k, b, n: (0, rb + j)),
            pl.BlockSpec((1, CIND_BLOCK_R), lambda i, j, k, b, n: (0, rb + j)),
            pl.BlockSpec((1, CIND_BLOCK_R), lambda i, j, k, b, n: (0, rb + j)),
        ],
        out_specs=[
            pl.BlockSpec((CIND_BLOCK_D, CIND_BLOCK_R),
                         lambda i, j, k, b, n: (i, j)),
            pl.BlockSpec((CIND_BLOCK_D, 1), lambda i, j, k, b, n: (i, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((CIND_BLOCK_D, CIND_BLOCK_R), acc_dt)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((tile, ref_chunk), jnp.uint8),
                   jax.ShapeDtypeStruct((tile, 1), jnp.int32)],
        # i is parallel; j carries the popc accumulation and k the VMEM
        # count accumulator, both sequential ("arbitrary") — which is also
        # what lets Mosaic double-buffer the K-step operand DMAs.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(block_ids, n_real, m_dep, m, sup_col, ok_col, gid_col, dcode_col,
      dv1_col, dv2_col, ridx_row, rcode_row, rv1_row)


def _fused_kl(l_pad: int) -> int:
    """K-step rows per block of the fused sweep — delegated to the plan's
    line-block granule so the kernel and the skip schedule agree."""
    from . import cooc

    return cooc.line_block_for(l_pad)
