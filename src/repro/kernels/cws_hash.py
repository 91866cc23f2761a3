"""Pallas TPU kernel for 0-bit/full CWS hashing and fused featurization.

Computes, for every (row, hash) pair, the argmin over dimensions of

    log a_i = log c_i - r_i (floor(log u_i / r_i + beta_i) - beta_i + 1)

TPU adaptation (vs the paper's per-vector CPU loop):
  * grid (rows/BN, hashes/BK, D/BD) with the D axis innermost — a running
    (best log_a, best index, best t) accumulator lives in VMEM scratch and
    is written to HBM once per (row, hash) tile at the last D step;
  * inside a grid step a fori_loop folds the BD dimensions into the
    accumulators U at a time (``dims_per_trip``, a static function of the
    tile, the carries and the trip count), each dimension rank-2
    (BN x BK) VPU math (its log u column broadcast against the parameter
    row) — no rank-3 temporaries.  log u is staged once per grid step as
    a transposed (BD, BN) tile; a trip reads its U dimensions as one
    (U, BN) sublane slab (Mosaic refuses a dynamic lane slice) and
    relays it out to (BN, U) once, so the cross-lane transpose that one
    dimension a trip paid every time is paid once per U, and the U
    independent candidate tiles fill the VLIW bundles that one
    dimension's latency chain left empty.  The candidates fold in
    increasing d with the strict ``<``, so every output bit is as with
    one dimension a trip.  The loop runs over the real dimensions only:
    where BD does not divide D, the last D step's loop stops at D
    (``tail_dims``), so the zero pad columns cost no iterations; a count
    that U does not divide ends in one static trip of the rest;
  * the kernel is VPU- and cross-lane-bound (floor/mul/select on 8x128
    lanes, one lane broadcast of log u per 8 rows and dimension; the
    divide lowers to one reciprocal of the parameter row and a
    multiply) and HBM-traffic-dominated by the 3 parameter matrices
    (DESIGN.md §2).

One kernel body (``_cws_kernel``) behind one wrapper (``cws_pallas``),
with two static choices:
  * the PARAMETER SOURCE, from the type of the launch state: stored
    ``CWSParams`` matrices read from HBM, or two PRNG key words from which
    each grid step regenerates its parameter tile in VMEM (DESIGN.md §7);
  * the EMIT: ``"hash"`` writes raw (i*, t*), two (n, k) int32 arrays;
    ``"index"`` applies b_i/b_t bit-masking, sentinel handling and the
    per-hash feature offset inside the emit step and writes final
    embedding-bag indices, ONE (n, k) int32 array — for the paper's 0-bit
    scheme (b_t = 0) t* is not even tracked in scratch; ``"packed"`` packs
    the b = b_i + b_t bit codes of each grid step's BK hashes into uint32
    words in VMEM (b in {1, 2, 4, 8}, word-aligned per row, by exact
    0/1-selection matmuls — no gathers) in the ``core.hashing.pack_codes``
    layout, so output traffic drops from 4·BN·BK bytes per tile to
    b/8·BN·BK.

Zero entries (log u = -inf) never win the argmin; all-zero rows return the
sentinel i* = -1 (matching repro.core.cws semantics), which the encoding
emits map to bucket 0 of their hash (matching
core.hashing.feature_indices).

Each (source, emit) pair names its Mosaic kernel from ``KERNEL_NAMES``
(``cws_encode_pallas``, ``cws_encode_rng_packed_pallas``, ...), not after
the kernel body's Python function, so a variant keeps its name in a
profile while the body is refactored.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.cws import CWSParams
from repro.core.hashing import check_packed_bits, packed_width
from repro.core.regen import key_words, regen_tile

NEG_SENTINEL = -1


def _packed_bk(bk: int, k: int, b: int) -> int:
    """Legal hash-block size for the packed emit: a multiple of the
    32/b codes-per-word (so every grid step packs whole words), no
    larger than k rounded up to a whole word."""
    cpw = check_packed_bits(b)
    bk = min(bk, -(-k // cpw) * cpw)
    return -(-bk // cpw) * cpw


def _stage_logu(x_ref, lut_ref):
    """log u of the (BN, BD) x tile, stored transposed as (BD, BN): the
    accumulation loop then reads dimension d as a sublane row
    (``lut_ref[pl.ds(d, 1), :]``).  Mosaic lowers that read; it refuses
    a dynamic lane slice ``logu[:, d]`` of the untransposed tile."""
    x = x_ref[...]
    lut_ref[...] = jnp.transpose(
        jnp.where(x > 0, jnp.log(jnp.maximum(x, 1e-38)), -jnp.inf))


def tail_dims(d: int, bd: int) -> int:
    """Real dimensions in the last D step of a (row, hash) tile: the
    accumulation loop's trip count there.  ``bd`` is clamped to ``d`` as
    the wrappers clamp it, so a D that ``bd`` divides, or a D below
    ``bd``, gives the whole block: no tail."""
    bd = min(bd, d)
    return d - (-(-d // bd) - 1) * bd


def loop_share(d: int, bd: int) -> float:
    """Real D over the padded D: the share of the padded dimension loop
    that the kernels run (1.0 where ``bd`` divides D)."""
    bd = min(bd, d)
    return d / (-(-d // bd) * bd)


# The accumulation loop folds U dimensions a trip (``dims_per_trip``).
# Its per-dimension chain (the load of log u, its cross-lane relayout and
# lane broadcast, the compares) is long in latency and short in work, so
# one dimension a trip leaves the VLIW bundles nearly empty; U
# independent candidate tiles a trip fill them.  A v5e core has 64
# vector registers of (8, 128) 32-bit words; what a trip holds beyond
# them the LLO scheduler spills to VMEM.  A trip's candidates and
# carries are bounded at five register files, and U at 16: for a
# (128, 128) tile the bundles a dimension fall from 150 at U = 1 to 60 at
# U = 16, and to only 57 at U = 32, which doubles the trip's code
# (PERF.md §6).
VREGS = 64
TRIP_VREGS = 5 * VREGS
MAX_DIMS_PER_TRIP = 16


def tile_vregs(bn: int, bk: int) -> int:
    """Vector registers one (bn, bk) float32 tile takes."""
    return -(-bn // 8) * -(-bk // 128)


def trip_vregs(bn: int, bk: int, carries: int, u: int) -> int:
    """Vector registers of a trip's U candidate tiles and its carries."""
    return (u + carries) * tile_vregs(bn, bk)


def dims_per_trip(bn: int, bk: int, carries: int, trips: int) -> int:
    """U: the dimensions each trip of the accumulation loop folds over a
    (bn, bk) tile with ``carries`` accumulators, for a loop of ``trips``
    dimensions.  The largest power of two up to ``MAX_DIMS_PER_TRIP``
    whose trip keeps within ``TRIP_VREGS``, and at most ``trips``."""
    u = MAX_DIMS_PER_TRIP
    while u > 1 and trip_vregs(bn, bk, carries, u) > TRIP_VREGS:
        u //= 2
    return min(u, trips)


def _fold(lu, r, lc, be, d_global, best):
    """The argmin update of one dimension: ``lu`` its log u broadcast
    over the (BN, BK) tile, ``r``/``lc``/``be`` its parameter rows,
    ``best`` the (best_a, best_i[, best_t]) values it updates."""
    a, i = best[0], best[1]
    tt = jnp.floor(lu / r + be)                      # (BN, BK)
    la = lc - r * (tt - be + 1.0)
    la = jnp.where(jnp.isfinite(lu), la, jnp.inf)
    upd = la < a
    a = jnp.where(upd, la, a)
    i = jnp.where(upd, d_global, i)
    if len(best) == 3:
        return a, i, jnp.where(upd, tt, best[2])
    return a, i


def _trip(lut_ref, r_ref, logc_ref, beta_ref, best, d_base, d0, u):
    """Fold the grid step's dimensions d0 .. d0 + u - 1 into the
    accumulators ``best``, in increasing d.  Their log u is read as one
    (u, BN) slab of the staged transposed tile and relaid out once, to
    (BN, u); each dimension's column is then broadcast over the tile, so
    ``isfinite`` reads the broadcast and needs no relayout of its own."""
    lus = jnp.transpose(lut_ref[pl.ds(d0, u), :])    # (BN, u)
    vals = tuple(ref[...] for ref in best)
    for j in range(u):
        d = d0 + j
        vals = _fold(jnp.broadcast_to(lus[:, j:j + 1], vals[0].shape),
                     r_ref[pl.ds(d, 1), :], logc_ref[pl.ds(d, 1), :],
                     beta_ref[pl.ds(d, 1), :],
                     (d_base + d).astype(jnp.int32), vals)
    for ref, v in zip(best, vals):
        ref[...] = v


def _accum_loop(lut_ref, r_ref, logc_ref, beta_ref, best, d_step, bd,
                dims):
    """Fold the grid step's first ``dims`` dimensions into ``best``:
    ``dims // U`` trips of U dimensions, then the ``dims % U`` left as
    one static trip.  No trip count is dynamic."""
    bn, bk = best[0].shape
    u = dims_per_trip(bn, bk, len(best), dims)
    trips, rest = divmod(dims, u)
    d_base = d_step * bd

    def body(t, carry):
        _trip(lut_ref, r_ref, logc_ref, beta_ref, best, d_base,
              pl.multiple_of(t * u, u), u)
        return carry

    jax.lax.fori_loop(0, trips, body, 0)
    if rest:
        _trip(lut_ref, r_ref, logc_ref, beta_ref, best, d_base,
              trips * u, rest)


def _accumulate(lut_ref, r_ref, logc_ref, beta_ref, best, *, bd: int,
                n_d_steps: int, tail: int):
    """One grid step's argmin update on the scratch accumulators
    ``best`` (best_a, best_i[, best_t]).

    The last D step loops over its ``tail`` real dimensions only: a pad
    column (log u = -inf) can never win the strict ``<``, so leaving it
    out changes no output bit.  Where ``bd`` divides D (``tail == bd``)
    every step runs one loop over ``bd`` dimensions, with no branch."""
    d_step = pl.program_id(2)

    def run(dims):
        _accum_loop(lut_ref, r_ref, logc_ref, beta_ref, best, d_step, bd,
                    dims)

    if tail == bd:
        run(bd)
        return
    pl.when(d_step < n_d_steps - 1)(lambda: run(bd))
    pl.when(d_step == n_d_steps - 1)(lambda: run(tail))


def _pack_words(code, b):
    """(BN, BK) b-bit codes -> (BN, BK*b/32) uint32 words in the
    core.hashing.pack_codes layout: code column w*(32/b)+m lands in word
    w at bit offset m*b.

    Mosaic lowers no strided lane slice, so the columns are gathered by
    two 0/1-selection matmuls, one per 16-bit half of the word: the
    selection matrix holds 2^(m*b - half) where column j belongs to
    word w.  Every operand is an integer below 2^16 and each output is a
    sum of disjoint bit fields below 2^16, so the f32 products and sums
    are exact at any MXU precision (bf16 passes included)."""
    cpw = 32 // b
    bk = code.shape[1]
    bw = bk // cpw
    col = jax.lax.broadcasted_iota(jnp.int32, (bk, bw), 0)
    word = jax.lax.broadcasted_iota(jnp.int32, (bk, bw), 1)
    c = code.astype(jnp.float32)                   # < 2^b <= 2^8: exact
    halves = []
    for half in (0, 16):
        sel = jnp.zeros((bk, bw), jnp.float32)
        for m in range(cpw):
            if half <= m * b < half + 16:
                sel = jnp.where(col == word * cpw + m,
                                float(1 << (m * b - half)), sel)
        part = jnp.dot(c, sel, preferred_element_type=jnp.float32)
        halves.append(part.astype(jnp.int32).astype(jnp.uint32))
    return halves[0] | (halves[1] << jnp.uint32(16))


def _encode_emit(i, best_t, hash_block, bk, b_i, b_t, *, packed=False,
                 num_hashes=0):
    """b-bit code + sentinel handling + per-hash offset: the shared emit
    step of the fused featurization kernels (stored and rng variants).

    ``packed=True`` skips the per-hash offset, zeroes the codes of pad
    hash columns (>= num_hashes — their packed bits share words with
    real codes, so they must be deterministic), and packs the
    b = b_i + b_t bit codes into uint32 words."""
    code = i if b_i == 0 else jnp.bitwise_and(i, (1 << b_i) - 1)
    if b_t:
        t = jnp.clip(best_t, -2 ** 30, 2 ** 30).astype(jnp.int32)
        code = code * (1 << b_t) + jnp.bitwise_and(t, (1 << b_t) - 1)
    code = jnp.where(i < 0, 0, code)               # sentinel -> bucket 0
    col = jax.lax.broadcasted_iota(jnp.int32, code.shape, 1)
    hash_id = hash_block * bk + col                # global hash index
    if packed:
        code = jnp.where(hash_id < num_hashes, code, 0)
        return _pack_words(code, b_i + b_t)
    width = jnp.int32(1 << (b_i + b_t))
    return hash_id * width + code


def _regen_step(key_ref, d_step, bd, bk, r_s, c_s, b_s):
    """Fill the (BD, BK) parameter scratch for this grid step from the
    counter stream at global offsets (d_step*BD, hash_block*BK)."""
    r, lc, be = regen_tile(key_ref[0], key_ref[1],
                           d_step * bd, pl.program_id(1) * bk, bd, bk)
    r_s[...] = r
    c_s[...] = lc
    b_s[...] = be


def _cws_kernel(*refs, source: str, emit: str, bd: int, bk: int,
                n_d_steps: int, tail: int, b_i: int, b_t: int,
                num_hashes: int):
    """The one CWS kernel body.  ``refs`` are the inputs (x and the three
    (BD, BK) parameter tiles for stored parameters; x and the SMEM key
    words for regenerated ones), the outputs ((i*, t*) for ``"hash"``,
    one array otherwise), then the scratch: the transposed log u tile,
    the three regenerated parameter tiles (regen only) and the
    accumulators (best_a, best_i[, best_t]).  best_t exists for
    ``"hash"`` and wherever b_t keeps t* bits."""
    n_in = 4 if source == "stored" else 2
    n_out = 2 if emit == "hash" else 1
    x_ref, outs = refs[0], refs[n_in:n_in + n_out]
    lut, *scratch = refs[n_in + n_out:]
    if source == "stored":
        params, best = refs[1:4], tuple(scratch)
    else:
        params, best = tuple(scratch[:3]), tuple(scratch[3:])
    d_step = pl.program_id(2)
    hash_block = None if emit == "hash" else pl.program_id(1)

    @pl.when(d_step == 0)
    def _init():
        best[0][...] = jnp.full_like(best[0][...], jnp.inf)
        best[1][...] = jnp.full_like(best[1][...], NEG_SENTINEL)
        if len(best) == 3:
            best[2][...] = jnp.zeros_like(best[2][...])

    if source == "regen":
        _regen_step(refs[1], d_step, bd, bk, *params)
    _stage_logu(x_ref, lut)
    _accumulate(lut, *params, best, bd=bd, n_d_steps=n_d_steps, tail=tail)

    @pl.when(d_step == n_d_steps - 1)
    def _emit():
        if emit == "hash":
            outs[0][...] = best[1][...]
            outs[1][...] = jnp.clip(best[2][...], -2 ** 30,
                                    2 ** 30).astype(jnp.int32)
            return
        emitted = _encode_emit(best[1][...], best[2][...] if b_t else None,
                               hash_block, bk, b_i, b_t,
                               packed=emit == "packed",
                               num_hashes=num_hashes)
        outs[0][...] = emitted.reshape(outs[0].shape)


# ---------------------------------------------------------------------------
# the one wrapper: pad, grid, blocks, scratch, output, slice
# ---------------------------------------------------------------------------
#
# Stored parameters arrive as three (D, k) operands tiled (BD, BK) next to
# the (BN, BD) x tile.  Regenerated ones arrive as two uint32 key words in
# SMEM; each grid step derives its (BD, BK) parameter tile from the
# counter-based threefry spec (repro.core.regen) keyed on the GLOBAL
# (d, hash) coordinates — so tiles are order-independent and bit-identical
# to the `cws_hash_regen` oracle.  Input traffic per (row, hash) tile
# drops from 4·BN·BD + 12·BD·BK bytes to 4·BN·BD (DESIGN.md §7); the price
# is ~3 threefry evaluations per (d, hash) element per row-block sweep.
#
# The packed emit writes, per (BN, BK) tile, (BN, BK·b/32) uint32 words:
# output traffic drops 32/b x.  BK is legalized to a multiple of the 32/b
# codes-per-word so every grid step owns whole words, and pad hash columns
# (>= k) zero their bits (they share words with real codes at ragged k·b).
# Its output is 3-D, (hash blocks, rows, BK·b/32), with block
# (1, BN, BK·b/32): Mosaic needs a block's last dim to be a multiple of 128
# or the whole array dim, and BK·b/32 words is neither in 2-D.  The wrapper
# puts each row's hash blocks back side by side.

# (parameter source, emit) -> the Mosaic kernel's name.  Profiles and
# their readers match these names; a new variant adds its rows here, one
# branch in _cws_kernel and one block family (registry.cws_family).
KERNEL_NAMES = {
    ("stored", "hash"): "cws_hash_pallas",
    ("stored", "index"): "cws_encode_pallas",
    ("stored", "packed"): "cws_encode_packed_pallas",
    ("regen", "hash"): "cws_hash_rng_pallas",
    ("regen", "index"): "cws_encode_rng_pallas",
    ("regen", "packed"): "cws_encode_rng_packed_pallas",
}


def param_source(state) -> str:
    """Where a launch's parameters come from, by the type of its state:
    ``"stored"`` for ``CWSParams``, ``"regen"`` for PRNG key words."""
    return "stored" if isinstance(state, CWSParams) else "regen"


def hash_count(state, num_hashes: int | None) -> int:
    """k of a launch: stored parameters carry theirs; regenerated ones
    take ``num_hashes``."""
    if isinstance(state, CWSParams):
        if num_hashes not in (None, state.num_hashes):
            raise ValueError(f"num_hashes={num_hashes} but the stored "
                             f"parameters carry {state.num_hashes} hashes")
        return state.num_hashes
    if num_hashes is None:
        raise ValueError("regenerated parameters need num_hashes")
    return num_hashes


def _tile(n, d, k, emit, b, bn, bk, bd):
    """The launch's (bn, bk, bd): each clamped to its dimension, and
    bk legalized for the packed emit."""
    bk = _packed_bk(bk, k, b) if emit == "packed" else min(bk, k)
    return min(bn, n), bk, min(bd, d)


def _carries(emit, b_t):
    """Accumulators of a launch: (best_a, best_i), and best_t where the
    emit keeps t* (the ``"hash"`` emit, or b_t > 0)."""
    return 3 if emit == "hash" or b_t else 2


def launch_dims_per_trip(n: int, d: int, k: int, *, emit: str,
                         b_i: int = 0, b_t: int = 0, bn: int, bk: int,
                         bd: int) -> int:
    """U of a ``cws_pallas`` launch's main D steps (its ``bd``
    dimensions a step), from the same static function the kernel uses."""
    bn, bk, bd = _tile(n, d, k, emit, b_i + b_t, bn, bk, bd)
    return dims_per_trip(bn, bk, _carries(emit, b_t), bd)


def _packed_out(np_, kp_, bn, bk, b):
    """(out_spec, out_shape) of the packed emit: one (1, BN, BK·b/32)
    block per (row block, hash block)."""
    bw = bk * b // 32                       # packed words per hash block
    spec = pl.BlockSpec((1, bn, bw), lambda i, j, s: (j, i, 0))
    return spec, jax.ShapeDtypeStruct((kp_ // bk, np_, bw), jnp.uint32)


def _row_words(blocks, n, k, b):
    """(hash blocks, rows, words) -> (n, ceil(k·b/32)) row-major words."""
    hb, np_, bw = blocks.shape
    words = jnp.transpose(blocks, (1, 0, 2)).reshape(np_, hb * bw)
    return words[:n, :packed_width(k, b)]


@functools.partial(jax.jit,
                   static_argnames=("num_hashes", "emit", "b_i", "b_t", "bn",
                                    "bk", "bd", "interpret"))
def cws_pallas(x: jax.Array, state, num_hashes: int | None = None, *,
               emit: str, b_i: int = 0, b_t: int = 0, bn: int = 128,
               bk: int = 128, bd: int = 256, interpret: bool = False):
    """CWS of x (n, D) nonneg, one kernel launch.

    ``state`` is stored ``CWSParams`` ((D, k) matrices) or regenerated
    PRNG key words (then ``num_hashes`` gives k).  ``emit`` says what
    comes back:

      * ``"hash"``   — (i*, t*), each (n, k) int32; bit-identical to
        ``cws_hash`` / ``cws_hash_regen``;
      * ``"index"``  — embedding-bag indices (n, k) int32 into the
        k·2^{b_i+b_t} feature space: ``feature_indices(encode(...))``
        with one HBM output and no (i*, t*) intermediates;
      * ``"packed"`` — (n, ceil(k·b/32)) uint32 words, b = b_i + b_t in
        {1, 2, 4, 8}: ``pack_codes(encode(...))`` bit for bit.
    """
    source = param_source(state)
    name = KERNEL_NAMES[(source, emit)]
    n, d = x.shape
    k = hash_count(state, num_hashes)
    b = b_i + b_t
    bn, bk, bd = _tile(n, d, k, emit, b, bn, bk, bd)
    pad_n, pad_d, pad_k = (-n) % bn, (-d) % bd, (-k) % bk
    np_, dp_, kp_ = n + pad_n, d + pad_d, k + pad_k
    n_d_steps = dp_ // bd

    x_spec = pl.BlockSpec((bn, bd), lambda i, j, s: (i, s))
    scratch = [pltpu.VMEM((bd, bn), jnp.float32)]   # transposed log u
    if source == "regen":
        k0, k1 = key_words(state)
        operands = [jnp.stack([k0, k1])]
        in_specs = [x_spec, pl.BlockSpec(memory_space=pltpu.SMEM)]
        scratch += [pltpu.VMEM((bd, bk), jnp.float32)] * 3  # r, log_c, beta
    # zero-padded x columns are masked by construction (log 0 = -inf);
    # padded params are never selected for real columns, r=1 avoids div-0.
    xp = jnp.pad(x.astype(jnp.float32), ((0, pad_n), (0, pad_d)))
    if source == "stored":
        operands = [
            jnp.pad(state.r, ((0, pad_d), (0, pad_k)), constant_values=1.0),
            jnp.pad(state.log_c, ((0, pad_d), (0, pad_k))),
            jnp.pad(state.beta, ((0, pad_d), (0, pad_k)))]
        in_specs = [x_spec] + [pl.BlockSpec((bd, bk), lambda i, j, s: (s, j))
                               ] * 3
    scratch += [pltpu.VMEM((bn, bk), jnp.float32),     # best log_a
                pltpu.VMEM((bn, bk), jnp.int32)]       # best index
    if _carries(emit, b_t) == 3:
        scratch.append(pltpu.VMEM((bn, bk), jnp.float32))   # best t

    tile = pl.BlockSpec((bn, bk), lambda i, j, s: (i, j))
    if emit == "hash":
        out_specs = [tile, tile]
        out_shape = [jax.ShapeDtypeStruct((np_, kp_), jnp.int32)] * 2
    elif emit == "index":
        out_specs, out_shape = tile, jax.ShapeDtypeStruct((np_, kp_),
                                                          jnp.int32)
    else:
        out_specs, out_shape = _packed_out(np_, kp_, bn, bk, b)

    kernel = functools.partial(_cws_kernel, source=source, emit=emit, bd=bd,
                               bk=bk, n_d_steps=n_d_steps,
                               tail=tail_dims(d, bd), b_i=b_i, b_t=b_t,
                               num_hashes=k)
    out = pl.pallas_call(
        kernel,
        name=name,
        grid=(np_ // bn, kp_ // bk, n_d_steps),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(xp, *operands)
    if emit == "hash":
        return out[0][:n, :k], out[1][:n, :k]
    if emit == "index":
        return out[:n, :k]
    return _row_words(out, n, k, b)


# ---------------------------------------------------------------------------
# numerics-analysis sites (repro.analysis / tools/kernel_lint.py)
# ---------------------------------------------------------------------------
# Interval proofs over the emit arithmetic the kernels share: the b-bit
# code build (mask / clip / sentinel fold), the per-hash offset, and the
# selection-matmul word packing — seeded with the hostile ranges the
# accumulator actually produces (best_i carries the -1 sentinel, best_t
# is an unbounded float before its clip).

from repro.kernels import registry as _registry  # noqa: E402


@_registry.register_numerics_site("kernels.pack_words")
def _numerics_site_pack_words():
    from repro.analysis.intervals import unknown_ival
    code = unknown_ival((8, 32), jnp.int32, lo=0, hi=255)
    return {"fn": lambda code: _pack_words(code, 8), "args": (code,)}


@_registry.register_numerics_site("kernels.encode_emit")
def _numerics_site_encode_emit():
    from repro.analysis.intervals import unknown_ival
    # best_i: NEG_SENTINEL or a global dim index (up to 2^20-dim data);
    # best_t: any finite float (clipped inside); hash_block: grid id.
    i = unknown_ival((8, 32), jnp.int32, lo=NEG_SENTINEL, hi=2 ** 20 - 1)
    t = unknown_ival((8, 32), jnp.float32)
    hb = unknown_ival((), jnp.int32, lo=0, hi=2 ** 11 - 1)

    def fn(i, t, hb):
        unpacked = _encode_emit(i, t, hb, 32, 4, 4)
        packed = _encode_emit(i, t, hb, 32, 4, 4, packed=True,
                              num_hashes=1000)
        return unpacked, packed
    return {"fn": fn, "args": (i, t, hb)}
