"""Bitset sketches: the TPU-native form of the reference's extended Bloom filters.

The reference's approximate strategies lean on a forked Guava library — BloomFilter
with exportBits/wrap/intersect (CreateAllHalfApproximateCindCandidates.scala:110-116,
IntersectHalfApproximateCindCandidates.scala:40-44) and SpectralBloomFilter, a
counting filter (ExtractBalancedHalfApproximateUnaryUnaryOverlapCandidates.scala:
24-37).  Here those become fixed-width **bitset rows in HBM**:

  * a join line's capture set   -> one `bits`-wide Bloom row (scatter-OR build);
  * a dependent's refset sketch -> bitwise AND of the Bloom rows of every join line
    containing the dependent (segment-AND) — a conservative superset of the exact
    refset, because AND of Blooms ⊇ Bloom of the intersection;
  * candidate generation        -> "are all k hash bits of capture r set in
    sketch[d]?" for every (d, r) at once, phrased as a bf16 matmul on the MXU:
    (deps × bits) @ (bits × refs) == popcount(bits of r);
  * the spectral filter         -> a count-min sketch (saturating scatter-add,
    min-of-k query).

Everything is fixed-shape and jittable; rows are packed 32 bits/uint32 lane for
storage (`bits/32` words) and unpacked to 0/1 planes only inside a stage, where
elementwise min/max on {0,1} plays bitwise AND/OR.  A Pallas kernel can later run
the packed AND directly; the planes layout is already the MXU-friendly one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import hashing

DEFAULT_BITS = 2048
DEFAULT_HASHES = 4

# Row budgets for host-side chunking of the build stages (see models/approximate).
BUILD_ROW_BUDGET = 1 << 18


def bit_positions(ids, *, bits: int, num_hashes: int):
    """(n, k) int32 hash-bit positions in [0, bits) for dense int32 ids.

    The double-hashing scheme (h1 + i*h2, as in Guava's BloomFilterStrategies):
    two mixed 32-bit hashes generate all k positions.

    `bits` must be a power of two >= 32: positions are masked with `bits - 1`
    (a non-pow2 width would silently dead-zone part of the filter) and rows are
    packed 32 bits per uint32 lane.
    """
    if bits < 32 or bits & (bits - 1):
        raise ValueError(f"sketch bits must be a power of two >= 32, got {bits}")
    h1 = hashing.hash_cols([ids], seed=1)
    h2 = hashing.hash_cols([ids], seed=2) | jnp.uint32(1)  # odd => full period
    i = jnp.arange(num_hashes, dtype=jnp.uint32)
    pos = h1[:, None] + i[None, :] * h2[:, None]
    return (pos & jnp.uint32(bits - 1)).astype(jnp.int32)


def pack_planes(planes):
    """(m, bits) 0/1 uint8 planes -> (m, bits//32) uint32 packed rows."""
    m, bits = planes.shape
    lanes = planes.reshape(m, bits // 32, 32).astype(jnp.uint32)
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    return (lanes * weights[None, None, :]).sum(axis=2, dtype=jnp.uint32)


def unpack_planes(packed):
    """(m, W) uint32 packed rows -> (m, 32*W) 0/1 uint8 planes."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (packed[:, :, None] >> shifts[None, None, :]) & jnp.uint32(1)
    return bits.reshape(packed.shape[0], packed.shape[1] * 32).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("num_lines", "bits", "num_hashes"))
def build_line_blooms(line_gid, cap_id, valid, *, num_lines: int, bits: int,
                      num_hashes: int):
    """Packed Bloom row per join line from (line, capture) membership rows.

    line_gid: dense line id per row; cap_id: capture id per row.  Invalid rows are
    dropped.  Returns (num_lines, bits//32) uint32.
    """
    pos = bit_positions(cap_id, bits=bits, num_hashes=num_hashes)
    li = jnp.where(valid, line_gid, num_lines)[:, None]
    planes = jnp.zeros((num_lines, bits), jnp.uint8)
    planes = planes.at[li, pos].max(jnp.uint8(1), mode="drop")
    return pack_planes(planes)


@functools.partial(jax.jit, static_argnames=("num_caps", "bits"))
def intersect_dep_sketches(cap_id, line_bloom_rows, valid, *, num_caps: int,
                           bits: int):
    """Per-dependent refset sketch: AND of the line Blooms the dependent occurs in.

    cap_id: capture id per row; line_bloom_rows: (n_rows, W) packed Bloom of each
    row's line.  Returns (num_caps, W) uint32; captures with no valid rows keep the
    all-ones sketch (empty AND), which callers must mask by support anyway.
    """
    planes = unpack_planes(line_bloom_rows)
    ci = jnp.where(valid, cap_id, num_caps)
    acc = jnp.ones((num_caps, bits), jnp.uint8)
    acc = acc.at[ci].min(planes, mode="drop")
    return pack_planes(acc)


@jax.jit
def intersect_dep_sketches_acc(acc, cap_id, line_bloom_rows, valid):
    """AND-accumulate one chunk's per-dependent sketches into `acc`.

    acc: (num_caps, W) packed sketches resident on device.  Equivalent to
    `acc & intersect_dep_sketches(...)` fused into one program with no host
    round trip — the r4 build pulled every chunk's partial sketch matrix to
    host and ANDed in numpy, which was strategy 2's first measured bottleneck
    (the AND of Blooms itself is the reference's BloomFilter.intersect,
    IntersectHalfApproximateCindCandidates.scala:40-44).
    """
    num_caps = acc.shape[0]
    planes = unpack_planes(line_bloom_rows)
    ci = jnp.where(valid, cap_id, num_caps)
    accp = unpack_planes(acc)
    accp = accp.at[ci].min(planes, mode="drop")
    return pack_planes(accp)


@functools.partial(jax.jit, static_argnames=("bits", "num_hashes"))
def pack_ref_bits(ref_ids, *, bits: int, num_hashes: int):
    """Packed (R, bits//32) uint32 bit sets of each ref id's k hash positions,
    plus (R,) int32 popcounts — the ref-side operand of the packed kernel."""
    r = ref_ids.shape[0]
    pos = bit_positions(ref_ids, bits=bits, num_hashes=num_hashes)  # (R, k)
    word, bit = pos >> 5, (pos & 31).astype(jnp.uint32)
    rows = jnp.zeros((r, bits // 32), jnp.uint32)
    ar = jnp.arange(r)
    for i in range(pos.shape[1]):  # k is tiny; sequential read-OR-write per hash
        prev = rows[ar, word[:, i]]
        rows = rows.at[ar, word[:, i]].set(prev | (jnp.uint32(1) << bit[:, i]))
    popc = jax.lax.population_count(rows).sum(axis=1).astype(jnp.int32)
    return rows, popc


def _pallas_backend_default() -> str:
    import os
    env = os.environ.get("RDFIND_PALLAS")
    if env is not None:
        return "jnp" if env.lower() in ("0", "false", "no") else "pallas"
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def pallas_eligible(bits: int, backend: str | None = None) -> bool:
    """Whether contains_matrix will dispatch to the Pallas kernel — the single
    eligibility rule, shared with callers that pre-pack the ref side."""
    if backend is None:
        backend = _pallas_backend_default()
    return backend == "pallas" and bits % 128 == 0


def _contract_dtype() -> str:
    """Element type of the jnp planes contraction: the resolved cooc dtype
    (int8 by default — int32 accumulation, exact; bf16 where int8 matmul
    does not lower).  Lazy import: cooc owns the probe and the env knob."""
    from . import cooc

    return cooc.resolved_cooc_dtype()


def _kernel_dtype() -> str:
    """Unpack dtype of the packed Pallas kernel: narrows to int4 nibble or
    int2 crumb planes under the plane-bits policy (RDFIND_PLANE_BITS) —
    each MXU pass then covers 2x/4x the K-dim — while the jnp fallback
    keeps the plain cooc dtype (XLA has no portable sub-byte contraction).
    All modes exact."""
    from . import cooc

    return cooc.resolved_kernel_dtype()


def contains_matrix(sketch_tile, ref_ids, ref_valid, *, bits: int,
                    num_hashes: int, backend: str | None = None,
                    interpret: bool = False, ref_pack=None):
    """(deps_tile × refs_tile) membership test on the MXU.

    sketch_tile: (D, W) packed dep sketches; ref_ids: (R,) capture ids.  Returns
    bool (D, R): True where every hash bit of ref r is set in sketch d — the
    candidate matrix of the approximate strategies.  The contraction runs in
    the resolved cooc dtype: int8 with int32 accumulation by default (exact —
    counts <= bits), bf16 with f32 accumulation as the fallback (counts <=
    num_hashes, exactly representable).

    backend: "pallas" (packed fused kernel, default on TPU — see
    ops/pallas_kernels.py) or "jnp" (unpacked-planes formulation, default
    elsewhere); `interpret` runs the Pallas kernel in interpreter mode (CPU
    tests).  `ref_pack` optionally supplies a precomputed pack_ref_bits result
    so callers looping over dep tiles pack the shared ref side once.
    """
    if pallas_eligible(bits, backend):
        from . import pallas_kernels

        d = sketch_tile.shape[0]
        r = ref_ids.shape[0]
        dp = -d % pallas_kernels.TILE_D
        rp = -r % pallas_kernels.TILE_R
        ref_packed, popc = (ref_pack if ref_pack is not None else
                            pack_ref_bits(ref_ids, bits=bits,
                                          num_hashes=num_hashes))
        if dp:
            sketch_tile = jnp.pad(sketch_tile, ((0, dp), (0, 0)))
        if rp:
            ref_packed = jnp.pad(ref_packed, ((0, rp), (0, 0)))
            # Padded refs get popc 0 while their row is empty => hits==popc
            # would hold; pin popc to an unreachable value instead.
            popc = jnp.pad(popc, (0, rp), constant_values=jnp.int32(-1))
        out = pallas_kernels.packed_contains_matrix(
            sketch_tile, ref_packed, popc, interpret=interpret,
            unpack_dtype=_kernel_dtype())
        return (out[:d, :r] == 1) & ref_valid[None, :]
    return _contains_matrix_jnp(sketch_tile, ref_ids, ref_valid, bits=bits,
                                num_hashes=num_hashes,
                                contract_dtype=_contract_dtype())


@functools.partial(jax.jit,
                   static_argnames=("bits", "num_hashes", "contract_dtype"))
def _contains_matrix_jnp(sketch_tile, ref_ids, ref_valid, *, bits: int,
                         num_hashes: int, contract_dtype: str = "bf16"):
    planes = unpack_planes(sketch_tile)  # (D, bits)
    r = ref_ids.shape[0]
    pos = bit_positions(ref_ids, bits=bits, num_hashes=num_hashes)  # (R, k)
    ref_planes = jnp.zeros((r, bits), jnp.uint8)
    ref_planes = ref_planes.at[jnp.arange(r)[:, None], pos].max(jnp.uint8(1))
    popc = ref_planes.sum(axis=1, dtype=jnp.int32)  # <= k (hash collisions)
    # contract_dtype is a STATIC jit key (the 0/1 planes' aval is uint8
    # either way): a dtype flip must retrace, not reuse the other program.
    dt = jnp.int8 if contract_dtype == "int8" else jnp.bfloat16
    acc = jnp.int32 if contract_dtype == "int8" else jnp.float32
    hits = jax.lax.dot_general(
        planes.astype(dt), ref_planes.astype(dt),
        (((1,), (1,)), ((), ())), preferred_element_type=acc)
    return (hits.astype(jnp.int32) == popc[None, :]) & ref_valid[None, :]


# ---------------------------------------------------------------------------
# Spectral filter analog: count-min sketch (saturating counts, min-of-k query).
# ---------------------------------------------------------------------------

MAX_COUNT_MIN_CAP = (1 << 16) - 1
_CM_CHUNK = 1 << 14


@functools.partial(jax.jit, static_argnames=("bits", "num_hashes", "cap"))
def count_min_add(keys, counts, valid, *, bits: int, num_hashes: int,
                  cap: int = MAX_COUNT_MIN_CAP):
    """Build a count-min sketch row: (bits,) int32 counters saturating at `cap`.

    The reference's SpectralBloomFilter (MultiunionHalfApproximateOverlap
    Candidates.scala:40-47) uses small fixed-width counters that saturate by
    design; `cap` (<= 2^16-1) is that width.  Each counter ends at exactly
    min(true_sum, cap): contributions are clipped to cap and accumulated in
    chunks of 2^14 rows with a clamp between chunks, so partial sums stay
    below 2^30 and int32 never wraps (x64 is disabled in this stack, so an
    int64 accumulator would silently truncate).
    """
    if not 0 < cap <= MAX_COUNT_MIN_CAP:
        raise ValueError(f"cap must be in (0, {MAX_COUNT_MIN_CAP}]")
    pos = bit_positions(keys, bits=bits, num_hashes=num_hashes)
    c = jnp.clip(jnp.where(valid, counts, 0), 0, cap).astype(jnp.int32)
    n = keys.shape[0]
    n_chunks = max(1, -(-n // _CM_CHUNK))
    padded = n_chunks * _CM_CHUNK
    pos = jnp.pad(pos, ((0, padded - n), (0, 0)))
    c = jnp.pad(c, (0, padded - n))

    def body(table, xs):
        p, cc = xs
        inc = jnp.zeros(bits, jnp.int32).at[p].add(cc[:, None])
        return jnp.minimum(table + inc, cap), None

    table, _ = jax.lax.scan(
        body, jnp.zeros(bits, jnp.int32),
        (pos.reshape(n_chunks, _CM_CHUNK, -1), c.reshape(n_chunks, _CM_CHUNK)))
    return table


def count_min_partial(keys, counts, valid, *, bits: int, num_hashes: int,
                      cap: int = MAX_COUNT_MIN_CAP, table=None):
    """One shard's partial count-min table, optionally folded into `table`.

    The shard-clean build contract: `bit_positions` is row-pure, so rows
    hash to the same counters no matter which device (or which dep-slice
    pass) holds them, and a partial table built with the same STATIC
    (bits, num_hashes, cap) is summable with any other — sum-then-cap over
    per-device partials is bit-identical to one `count_min_add` over the
    concatenated rows.  (The per-row clip at `cap` commutes with sharding
    because it is per-row, not per-shard-total.)

    Saturation contract (the all-reduce correctness lemma): for
    non-negative partial sums s_i,

        min(sum_i min(s_i, cap), cap) == min(sum_i s_i, cap)

    — if every s_i <= cap the inner min is the identity; otherwise some
    s_i > cap forces both sides to cap.  The lemma nests, so saturating
    after EVERY reduction level — the per-chunk clamp inside
    `count_min_add`'s scan, the per-pass fold here, the intra-host psum
    and the inter-host psum of `exchange.sketch_allreduce`, and the host
    `merge_count_min` — yields the same bits as one global sum-then-cap,
    while bounding every wire operand at `cap` (<= 2^16-1), so the int32
    psum cannot wrap below 2^15 participants per level.

    `table=None` returns this shard's partial; otherwise the partial is
    folded into the running table with the same saturating rule (the
    per-pass accumulation of the sharded two-round's round 1).
    """
    part = count_min_add(keys, counts, valid, bits=bits,
                         num_hashes=num_hashes, cap=cap)
    if table is None:
        return part
    # Both operands are <= cap <= 2^16-1, so the int32 sum cannot wrap.
    return jnp.minimum(table + part, cap)


@functools.partial(jax.jit, static_argnames=("bits", "num_hashes"))
def count_min_query(table, keys, *, bits: int, num_hashes: int):
    """Upper bound on each key's count: min over its k counters (getCount analog)."""
    pos = bit_positions(keys, bits=bits, num_hashes=num_hashes)
    return table[pos].min(axis=1)


def kernel_selfcheck(n_rows: int = 1024, n_bits: int = 4096,
                     backend: str | None = None, num_hashes: int = DEFAULT_HASHES,
                     repeats: int = 5) -> dict:
    """Bit-parity + timing of the Pallas packed kernel vs the jnp planes path.

    On TPU both paths run natively and the returned dict includes the speedup;
    on CPU the Pallas kernel runs in interpreter mode (parity only, timing of
    the interpreter would be meaningless).  Used by bench.py to report
    `pallas_vs_jnp` (VERDICT r1: the kernel had never been validated on
    hardware).
    """
    import time as _time

    if backend is None:
        backend = jax.default_backend()
    on_tpu = backend == "tpu"

    key = np.random.default_rng(11)
    dep_ids = jnp.asarray(key.integers(0, 1 << 30, n_rows, dtype=np.int32))
    ref_ids = jnp.asarray(key.integers(0, 1 << 30, n_rows, dtype=np.int32))
    ref_valid = jnp.ones(n_rows, bool)
    # Dep sketches: Bloom rows of random capture sets (one line per dep).
    line_gid = jnp.arange(n_rows, dtype=jnp.int32)
    sketches = build_line_blooms(line_gid, dep_ids, jnp.ones(n_rows, bool),
                                 num_lines=n_rows, bits=n_bits,
                                 num_hashes=num_hashes)

    def run(be, interpret=False, salt=0):
        out = contains_matrix(sketches, ref_ids + salt, ref_valid, bits=n_bits,
                              num_hashes=num_hashes, backend=be,
                              interpret=interpret)
        return jax.block_until_ready(out)

    out_jnp = run("jnp")
    out_pallas = run("pallas", interpret=not on_tpu)
    parity = bool(jnp.array_equal(out_jnp, out_pallas))

    from . import cooc
    result = {"parity": parity, "n_rows": n_rows, "bits": n_bits,
              "backend": backend,
              # The resolved kernel mode this selfcheck actually ran — the
              # provenance the bench kernel-mode rows key on (one row per
              # knob set is meaningless without it).
              "kernel_dtype": _kernel_dtype(),
              "plane_bits": cooc.resolved_plane_bits(),
              "emit_pipeline": cooc.emit_pipeline_enabled()}
    # Content hash of the kernel output: lets bench rows taken under
    # DIFFERENT knob sets (plane bits, emit_pipeline) assert bit-identity
    # across rows, not just within-row jnp-vs-pallas parity.
    import hashlib
    result["out_hash"] = hashlib.sha1(
        np.asarray(out_pallas).tobytes()).hexdigest()[:16]
    # HBM traffic model of the packed kernel (ops/pallas_kernels.py grid):
    # each packed operand tile is re-read once per opposite-side tile, plus
    # the uint8 output write — the measured-bandwidth denominator for the
    # roofline (VERDICT r4 item 7: is 0.27% dense-peak actually BW-bound?).
    w = n_bits // 32
    from . import pallas_kernels as pk
    # Padded dims: contains_matrix pads both operands to tile multiples, so
    # real traffic scales with the padded shapes.
    d_pad = -(-n_rows // pk.TILE_D) * pk.TILE_D
    r_pad = -(-n_rows // pk.TILE_R) * pk.TILE_R
    result["hbm_bytes_model"] = int(
        (r_pad // pk.TILE_R) * d_pad * w * 4        # dep side re-reads
        + (d_pad // pk.TILE_D) * r_pad * w * 4      # ref side re-reads
        + d_pad * r_pad)                            # uint8 output
    if on_tpu:
        # Timing methodology: each repeat uses a *different* input (salted ids)
        # and the loop is drained by one scalar readback at the end — identical
        # repeated dispatches get streamlined by the runtime and report
        # physically-impossible per-call times (r2's 0.979x "speedup" artifact).
        for name, be in (("jnp_ms", "jnp"), ("pallas_ms", "pallas")):
            int(run(be, salt=-1).sum())  # compile, incl. the drain sum/add ops
            t0 = _time.perf_counter()
            acc = None
            for i in range(repeats):
                out = contains_matrix(sketches, ref_ids + (i + 1), ref_valid,
                                      bits=n_bits, num_hashes=num_hashes,
                                      backend=be)
                s = out.sum()
                acc = s if acc is None else acc + s
            int(acc)  # forces the whole chain to finish
            result[name] = round((_time.perf_counter() - t0) / repeats * 1e3, 3)
        result["speedup"] = round(result["jnp_ms"] / result["pallas_ms"], 3)
        # Kernel-only bandwidth: refs pre-packed outside the timed loop (the
        # end-to-end pallas_ms above keeps packing for a fair jnp speedup
        # comparison) and the drain's n^2 uint8 read added to the model, so
        # pallas_gbps reflects the kernel's real HBM rate.
        packs = [pack_ref_bits(ref_ids + (i + 1), bits=n_bits,
                               num_hashes=num_hashes) for i in range(repeats)]
        jax.block_until_ready(packs)
        # Warm with a DISTINCT pack: a warm dispatch identical to timed
        # iteration 0 would let the runtime streamline it (the r2 artifact
        # the salted loop above exists to avoid).
        warm_pack = pack_ref_bits(ref_ids - 1, bits=n_bits,
                                  num_hashes=num_hashes)
        int(contains_matrix(sketches, ref_ids - 1, ref_valid, bits=n_bits,
                            num_hashes=num_hashes, backend="pallas",
                            ref_pack=warm_pack).sum())
        t0 = _time.perf_counter()
        acc = None
        for i in range(repeats):
            out = contains_matrix(sketches, ref_ids + (i + 1), ref_valid,
                                  bits=n_bits, num_hashes=num_hashes,
                                  backend="pallas", ref_pack=packs[i])
            s = out.sum()
            acc = s if acc is None else acc + s
        int(acc)
        kernel_ms = (_time.perf_counter() - t0) / repeats * 1e3
        result["pallas_kernel_ms"] = round(kernel_ms, 3)
        result["pallas_gbps"] = round(
            (result["hbm_bytes_model"] + d_pad * r_pad)
            / (kernel_ms / 1e3) / 1e9, 1)
    return result


def merge_count_min(tables, cap: int = MAX_COUNT_MIN_CAP):
    """Sum of count-min tables (the combiner-tree merge), saturating.

    Host reference for the device-side saturating reduction
    (`exchange.sketch_allreduce`): this computes the exact int64 sum first
    and caps ONCE at the end, while the device path caps after every psum
    level — `count_min_partial`'s saturation lemma proves the two agree bit
    for bit whenever every input table is itself <= cap (which
    `count_min_add` guarantees).  Pinned by the differential test in
    tests/test_sketch_saturation.py, at and past the cap.
    """
    acc = np.zeros_like(np.asarray(tables[0]), dtype=np.int64)
    for t in tables:
        acc += np.asarray(t, np.int64)
    return np.minimum(acc, cap).astype(np.int32)
