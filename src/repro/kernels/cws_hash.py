"""Pallas TPU kernels for 0-bit/full CWS hashing and fused featurization.

Computes, for every (row, hash) pair, the argmin over dimensions of

    log a_i = log c_i - r_i (floor(log u_i / r_i + beta_i) - beta_i + 1)

TPU adaptation (vs the paper's per-vector CPU loop):
  * grid (rows/BN, hashes/BK, D/BD) with the D axis innermost — a running
    (best log_a, best index, best t) accumulator lives in VMEM scratch and
    is written to HBM once per (row, hash) tile at the last D step;
  * inside a grid step we loop over the BD dimensions with a fori_loop,
    each iteration doing rank-2 (BN x BK) VPU math (broadcast of the
    column log u against the parameter row) — no rank-3 temporaries.
    log u is staged once per grid step as a transposed (BD, BN) tile so
    the loop reads dimension d as a sublane row (Mosaic refuses a
    dynamic lane slice);
  * the kernel is VPU-bound (log/floor/mul on 8x128 lanes) and
    HBM-traffic-dominated by the 3 parameter matrices (DESIGN.md §2).

Two emit variants share the accumulation loop:
  * ``cws_hash_pallas``   — writes raw (i*, t*), two (n, k) int32 arrays;
  * ``cws_encode_pallas`` — the FUSED featurization kernel: applies b_i/b_t
    bit-masking, sentinel handling and the per-hash feature offset inside
    the emit step and writes final embedding-bag indices, ONE (n, k) int32
    array.  For the paper's 0-bit scheme (b_t = 0) this halves output
    traffic (t* is never materialized — it is not even tracked in scratch)
    and eliminates the separate encode + feature_indices passes.

Zero entries (log u = -inf) never win the argmin; all-zero rows return the
sentinel i* = -1 (matching repro.core.cws semantics), which the fused
kernel maps to bucket 0 of its hash (matching core.hashing.feature_indices).

PACKED emit variants (``cws_encode_packed_pallas`` /
``cws_encode_rng_packed_pallas``) share the same accumulation loop and
``_encode_emit`` body but pack the b = b_i + b_t bit codes of each grid
step's BK hashes into uint32 words in VMEM (b in {1, 2, 4, 8},
word-aligned per row, by exact 0/1-selection matmuls — no gathers):
output traffic drops from 4·BN·BK bytes per tile to b/8·BN·BK.  Hash
columns past the real k are zeroed before packing so pad bits are
deterministic zeros, and the word layout matches
``core.hashing.pack_codes`` bit-for-bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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


def _accum_loop(lut_ref, r_ref, logc_ref, beta_ref, d_step, bd, carry):
    """Run the BD-dimension argmin update on a (best_a, best_i[, best_t])
    carry; t tracking is skipped when the carry has no t slot."""
    track_t = len(carry) == 3

    def body(d, carry):
        a, i = carry[0], carry[1]
        lu = jnp.transpose(lut_ref[pl.ds(d, 1), :])  # (BN, 1)
        r = r_ref[pl.ds(d, 1), :]                    # (1, BK)
        lc = logc_ref[pl.ds(d, 1), :]
        be = beta_ref[pl.ds(d, 1), :]
        tt = jnp.floor(lu / r + be)                  # (BN, BK)
        la = lc - r * (tt - be + 1.0)
        la = jnp.where(jnp.isfinite(lu), la, jnp.inf)
        upd = la < a
        d_global = (d_step * bd + d).astype(jnp.int32)
        a = jnp.where(upd, la, a)
        i = jnp.where(upd, d_global, i)
        if track_t:
            return a, i, jnp.where(upd, tt, carry[2])
        return a, i

    return jax.lax.fori_loop(0, bd, body, carry)


def _logu_scratch(bn, bd):
    return pltpu.VMEM((bd, bn), jnp.float32)    # transposed log u tile


def _cws_kernel(x_ref, r_ref, logc_ref, beta_ref, istar_ref, tstar_ref,
                lut, best_a, best_i, best_t, *, bd: int, n_d_steps: int):
    d_step = pl.program_id(2)

    @pl.when(d_step == 0)
    def _init():
        best_a[...] = jnp.full_like(best_a[...], jnp.inf)
        best_i[...] = jnp.full_like(best_i[...], NEG_SENTINEL)
        best_t[...] = jnp.zeros_like(best_t[...])

    _stage_logu(x_ref, lut)
    a1, i1, t1 = _accum_loop(lut, r_ref, logc_ref, beta_ref, d_step, bd,
                             (best_a[...], best_i[...], best_t[...]))
    best_a[...] = a1
    best_i[...] = i1
    best_t[...] = t1

    @pl.when(d_step == n_d_steps - 1)
    def _emit():
        istar_ref[...] = best_i[...]
        tstar_ref[...] = jnp.clip(best_t[...], -2 ** 30, 2 ** 30).astype(jnp.int32)


def _cws_encode_kernel(x_ref, r_ref, logc_ref, beta_ref, idx_ref, lut,
                       *scratch,
                       bd: int, n_d_steps: int, b_i: int, b_t: int, bk: int,
                       packed: bool = False, num_hashes: int = 0):
    """Fused CWS -> b-bit code -> embedding-bag index.  ``scratch`` is
    (best_a, best_i) for the 0-bit scheme (b_t == 0) and
    (best_a, best_i, best_t) when t* bits are kept.  ``packed=True``
    emits bit-packed uint32 words instead of int32 indices."""
    d_step = pl.program_id(2)
    hash_block = pl.program_id(1)
    best_a, best_i = scratch[0], scratch[1]
    best_t = scratch[2] if b_t else None

    @pl.when(d_step == 0)
    def _init():
        best_a[...] = jnp.full_like(best_a[...], jnp.inf)
        best_i[...] = jnp.full_like(best_i[...], NEG_SENTINEL)
        if b_t:
            best_t[...] = jnp.zeros_like(best_t[...])

    _stage_logu(x_ref, lut)
    carry = (best_a[...], best_i[...]) + ((best_t[...],) if b_t else ())
    out = _accum_loop(lut, r_ref, logc_ref, beta_ref, d_step, bd, carry)
    best_a[...] = out[0]
    best_i[...] = out[1]
    if b_t:
        best_t[...] = out[2]

    @pl.when(d_step == n_d_steps - 1)
    def _emit():
        emitted = _encode_emit(best_i[...], best_t[...] if b_t else None,
                               hash_block, bk, b_i, b_t,
                               packed=packed, num_hashes=num_hashes)
        idx_ref[...] = emitted.reshape(idx_ref.shape)


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


def _pad_operands(x, r, log_c, beta, bn, bk, bd):
    n, d = x.shape
    k = r.shape[1]
    pad_n, pad_d, pad_k = (-n) % bn, (-d) % bd, (-k) % bk
    # zero-padded x columns are masked by construction (log 0 = -inf);
    # padded params are never selected for real columns, r=1 avoids div-0.
    xp = jnp.pad(x.astype(jnp.float32), ((0, pad_n), (0, pad_d)))
    rp = jnp.pad(r, ((0, pad_d), (0, pad_k)), constant_values=1.0)
    lcp = jnp.pad(log_c, ((0, pad_d), (0, pad_k)))
    bep = jnp.pad(beta, ((0, pad_d), (0, pad_k)))
    return xp, rp, lcp, bep


def _cws_specs(bn, bk, bd):
    in_specs = [
        pl.BlockSpec((bn, bd), lambda i, j, s: (i, s)),
        pl.BlockSpec((bd, bk), lambda i, j, s: (s, j)),
        pl.BlockSpec((bd, bk), lambda i, j, s: (s, j)),
        pl.BlockSpec((bd, bk), lambda i, j, s: (s, j)),
    ]
    out_spec = pl.BlockSpec((bn, bk), lambda i, j, s: (i, j))
    return in_specs, out_spec


@functools.partial(jax.jit,
                   static_argnames=("bn", "bk", "bd", "interpret"))
def cws_hash_pallas(x: jax.Array, r: jax.Array, log_c: jax.Array,
                    beta: jax.Array, *, bn: int = 128, bk: int = 128,
                    bd: int = 256, interpret: bool = False):
    """x: (n, D) nonneg fp32; params (D, k) fp32 -> (i*, t*) each (n, k) i32."""
    n, d = x.shape
    k = r.shape[1]
    bn, bk, bd = min(bn, n), min(bk, k), min(bd, d)
    xp, rp, lcp, bep = _pad_operands(x, r, log_c, beta, bn, bk, bd)
    np_, dp_, kp_ = xp.shape[0], xp.shape[1], rp.shape[1]
    n_d_steps = dp_ // bd

    in_specs, out_spec = _cws_specs(bn, bk, bd)
    kernel = functools.partial(_cws_kernel, bd=bd, n_d_steps=n_d_steps)
    i_star, t_star = pl.pallas_call(
        kernel,
        grid=(np_ // bn, kp_ // bk, n_d_steps),
        in_specs=in_specs,
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((np_, kp_), jnp.int32),
                   jax.ShapeDtypeStruct((np_, kp_), jnp.int32)],
        scratch_shapes=[
            _logu_scratch(bn, bd),
            pltpu.VMEM((bn, bk), jnp.float32),   # best log_a
            pltpu.VMEM((bn, bk), jnp.int32),     # best index
            pltpu.VMEM((bn, bk), jnp.float32),   # best t (cast on emit)
        ],
        interpret=interpret,
    )(xp, rp, lcp, bep)
    return i_star[:n, :k], t_star[:n, :k]


@functools.partial(jax.jit,
                   static_argnames=("b_i", "b_t", "bn", "bk", "bd",
                                    "interpret"))
def cws_encode_pallas(x: jax.Array, r: jax.Array, log_c: jax.Array,
                      beta: jax.Array, *, b_i: int, b_t: int = 0,
                      bn: int = 128, bk: int = 128, bd: int = 256,
                      interpret: bool = False) -> jax.Array:
    """Fused featurization: x (n, D) nonneg -> embedding-bag indices
    (n, k) int32 into the k * 2^{b_i+b_t} feature space.

    Bit-exact vs ``feature_indices(encode(cws_hash(...)))`` but with a
    single HBM output array and no (i*, t*) intermediates.
    """
    n, d = x.shape
    k = r.shape[1]
    bn, bk, bd = min(bn, n), min(bk, k), min(bd, d)
    xp, rp, lcp, bep = _pad_operands(x, r, log_c, beta, bn, bk, bd)
    np_, dp_, kp_ = xp.shape[0], xp.shape[1], rp.shape[1]
    n_d_steps = dp_ // bd

    scratch = [_logu_scratch(bn, bd),
               pltpu.VMEM((bn, bk), jnp.float32),    # best log_a
               pltpu.VMEM((bn, bk), jnp.int32)]      # best index
    if b_t:
        scratch.append(pltpu.VMEM((bn, bk), jnp.float32))   # best t

    in_specs, out_spec = _cws_specs(bn, bk, bd)
    kernel = functools.partial(_cws_encode_kernel, bd=bd,
                               n_d_steps=n_d_steps, b_i=b_i, b_t=b_t, bk=bk)
    idx = pl.pallas_call(
        kernel,
        grid=(np_ // bn, kp_ // bk, n_d_steps),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((np_, kp_), jnp.int32),
        scratch_shapes=scratch,
        interpret=interpret,
    )(xp, rp, lcp, bep)
    return idx[:n, :k]


# ---------------------------------------------------------------------------
# zero-parameter-traffic variants: (r, log_c, beta) regenerated in-kernel
# ---------------------------------------------------------------------------
#
# The three (D, k) parameter operands disappear; each grid step derives its
# (BD, BK) parameter tile from the counter-based threefry spec
# (repro.core.regen) keyed on the GLOBAL (d, hash) coordinates — so tiles
# are order-independent and bit-identical to the `cws_hash_regen` oracle.
# Input traffic per (row, hash) tile drops from 4·BN·BD + 12·BD·BK bytes
# to 4·BN·BD (DESIGN.md §7); the price is ~3 threefry evaluations per
# (d, hash) element per row-block sweep, regenerated into VMEM scratch at
# every grid step (the scratch tile is reused as the accumulation loop's
# parameter refs, so the VPU loop itself is unchanged).


def _regen_step(key_ref, d_step, bd, bk, r_s, c_s, b_s):
    """Fill the (BD, BK) parameter scratch for this grid step from the
    counter stream at global offsets (d_step*BD, hash_block*BK)."""
    r, lc, be = regen_tile(key_ref[0], key_ref[1],
                           d_step * bd, pl.program_id(1) * bk, bd, bk)
    r_s[...] = r
    c_s[...] = lc
    b_s[...] = be


def _cws_hash_rng_kernel(x_ref, key_ref, istar_ref, tstar_ref,
                         lut, r_s, c_s, b_s, best_a, best_i, best_t,
                         *, bd: int, n_d_steps: int, bk: int):
    d_step = pl.program_id(2)

    @pl.when(d_step == 0)
    def _init():
        best_a[...] = jnp.full_like(best_a[...], jnp.inf)
        best_i[...] = jnp.full_like(best_i[...], NEG_SENTINEL)
        best_t[...] = jnp.zeros_like(best_t[...])

    _regen_step(key_ref, d_step, bd, bk, r_s, c_s, b_s)
    _stage_logu(x_ref, lut)
    a1, i1, t1 = _accum_loop(lut, r_s, c_s, b_s, d_step, bd,
                             (best_a[...], best_i[...], best_t[...]))
    best_a[...] = a1
    best_i[...] = i1
    best_t[...] = t1

    @pl.when(d_step == n_d_steps - 1)
    def _emit():
        istar_ref[...] = best_i[...]
        tstar_ref[...] = jnp.clip(best_t[...], -2 ** 30, 2 ** 30).astype(jnp.int32)


def _cws_encode_rng_kernel(x_ref, key_ref, idx_ref, lut, r_s, c_s, b_s,
                           *scratch,
                           bd: int, n_d_steps: int, b_i: int, b_t: int,
                           bk: int, packed: bool = False,
                           num_hashes: int = 0):
    d_step = pl.program_id(2)
    hash_block = pl.program_id(1)
    best_a, best_i = scratch[0], scratch[1]
    best_t = scratch[2] if b_t else None

    @pl.when(d_step == 0)
    def _init():
        best_a[...] = jnp.full_like(best_a[...], jnp.inf)
        best_i[...] = jnp.full_like(best_i[...], NEG_SENTINEL)
        if b_t:
            best_t[...] = jnp.zeros_like(best_t[...])

    _regen_step(key_ref, d_step, bd, bk, r_s, c_s, b_s)
    _stage_logu(x_ref, lut)
    carry = (best_a[...], best_i[...]) + ((best_t[...],) if b_t else ())
    out = _accum_loop(lut, r_s, c_s, b_s, d_step, bd, carry)
    best_a[...] = out[0]
    best_i[...] = out[1]
    if b_t:
        best_t[...] = out[2]

    @pl.when(d_step == n_d_steps - 1)
    def _emit():
        emitted = _encode_emit(best_i[...], best_t[...] if b_t else None,
                               hash_block, bk, b_i, b_t,
                               packed=packed, num_hashes=num_hashes)
        idx_ref[...] = emitted.reshape(idx_ref.shape)


def _rng_setup(x, num_hashes, bn, bk, bd):
    """Pad x, size the padded (n, k) output grid, build the rng in_specs
    (x tile + whole-key in SMEM)."""
    n, d = x.shape
    bn, bk, bd = min(bn, n), min(bk, num_hashes), min(bd, d)
    pad_n, pad_d = (-n) % bn, (-d) % bd
    xp = jnp.pad(x.astype(jnp.float32), ((0, pad_n), (0, pad_d)))
    kp_ = num_hashes + ((-num_hashes) % bk)
    in_specs = [
        pl.BlockSpec((bn, bd), lambda i, j, s: (i, s)),
        pl.BlockSpec(memory_space=pltpu.SMEM),
    ]
    out_spec = pl.BlockSpec((bn, bk), lambda i, j, s: (i, j))
    return xp, kp_, bn, bk, bd, in_specs, out_spec


def _param_scratch(bd, bk):
    return [pltpu.VMEM((bd, bk), jnp.float32),   # regenerated r
            pltpu.VMEM((bd, bk), jnp.float32),   # regenerated log_c
            pltpu.VMEM((bd, bk), jnp.float32)]   # regenerated beta


@functools.partial(jax.jit,
                   static_argnames=("num_hashes", "bn", "bk", "bd",
                                    "interpret"))
def cws_hash_rng_pallas(x: jax.Array, key: jax.Array, num_hashes: int, *,
                        bn: int = 128, bk: int = 128, bd: int = 256,
                        interpret: bool = False):
    """Zero-parameter-traffic CWS: x (n, D) nonneg + PRNG key ->
    (i*, t*) each (n, num_hashes) int32.  Bit-identical to
    ``cws_hash_regen(x, key, num_hashes)``."""
    n, d = x.shape
    k0, k1 = key_words(key)
    kw = jnp.stack([k0, k1])
    xp, kp_, bn, bk, bd, in_specs, out_spec = _rng_setup(
        x, num_hashes, bn, bk, bd)
    np_, dp_ = xp.shape
    n_d_steps = dp_ // bd

    kernel = functools.partial(_cws_hash_rng_kernel, bd=bd,
                               n_d_steps=n_d_steps, bk=bk)
    i_star, t_star = pl.pallas_call(
        kernel,
        grid=(np_ // bn, kp_ // bk, n_d_steps),
        in_specs=in_specs,
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((np_, kp_), jnp.int32),
                   jax.ShapeDtypeStruct((np_, kp_), jnp.int32)],
        scratch_shapes=[_logu_scratch(bn, bd)] + _param_scratch(bd, bk) + [
            pltpu.VMEM((bn, bk), jnp.float32),   # best log_a
            pltpu.VMEM((bn, bk), jnp.int32),     # best index
            pltpu.VMEM((bn, bk), jnp.float32),   # best t (cast on emit)
        ],
        interpret=interpret,
    )(xp, kw)
    return i_star[:n, :num_hashes], t_star[:n, :num_hashes]


@functools.partial(jax.jit,
                   static_argnames=("num_hashes", "b_i", "b_t", "bn", "bk",
                                    "bd", "interpret"))
def cws_encode_rng_pallas(x: jax.Array, key: jax.Array, num_hashes: int, *,
                          b_i: int, b_t: int = 0, bn: int = 128,
                          bk: int = 128, bd: int = 256,
                          interpret: bool = False) -> jax.Array:
    """Fused zero-parameter-traffic featurization: x (n, D) nonneg + PRNG
    key -> embedding-bag indices (n, num_hashes) int32 into the
    num_hashes * 2^{b_i+b_t} feature space.

    Bit-exact vs ``feature_indices(encode(cws_hash_regen(...)))`` with a
    single HBM output array, no (i*, t*) intermediates, and NO parameter
    operands at all — the only HBM input is x.
    """
    n, d = x.shape
    k0, k1 = key_words(key)
    kw = jnp.stack([k0, k1])
    xp, kp_, bn, bk, bd, in_specs, out_spec = _rng_setup(
        x, num_hashes, bn, bk, bd)
    np_, dp_ = xp.shape
    n_d_steps = dp_ // bd

    scratch = [_logu_scratch(bn, bd)] + _param_scratch(bd, bk) + [
        pltpu.VMEM((bn, bk), jnp.float32),       # best log_a
        pltpu.VMEM((bn, bk), jnp.int32)]         # best index
    if b_t:
        scratch.append(pltpu.VMEM((bn, bk), jnp.float32))    # best t

    kernel = functools.partial(_cws_encode_rng_kernel, bd=bd,
                               n_d_steps=n_d_steps, b_i=b_i, b_t=b_t, bk=bk)
    idx = pl.pallas_call(
        kernel,
        grid=(np_ // bn, kp_ // bk, n_d_steps),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((np_, kp_), jnp.int32),
        scratch_shapes=scratch,
        interpret=interpret,
    )(xp, kw)
    return idx[:n, :num_hashes]


# ---------------------------------------------------------------------------
# bit-packed emit variants: b = b_i + b_t bit codes -> uint32 words
# ---------------------------------------------------------------------------
#
# Same grid, same accumulation loop, same scratch as the unpacked encode
# kernels — only the emit differs: per (BN, BK) tile the codes pack into
# (BN, BK·b/32) uint32 words in VMEM before the single HBM write, so
# output traffic drops 32/b x.  BK is legalized to a multiple of the
# 32/b codes-per-word so every grid step owns whole words, and pad hash
# columns (>= num_hashes) zero their bits (they share words with real
# codes at ragged k·b).  The row dimension needs no care: rows pack
# independently (word-aligned), pad rows slice off as usual.
#
# The output is 3-D, (hash blocks, rows, BK·b/32), with block
# (1, BN, BK·b/32): Mosaic needs a block's last dim to be a multiple of
# 128 or the whole array dim, and BK·b/32 words is neither in 2-D.  The
# wrapper puts each row's hash blocks back side by side.


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
                   static_argnames=("b_i", "b_t", "bn", "bk", "bd",
                                    "interpret"))
def cws_encode_packed_pallas(x: jax.Array, r: jax.Array, log_c: jax.Array,
                             beta: jax.Array, *, b_i: int, b_t: int = 0,
                             bn: int = 128, bk: int = 128, bd: int = 256,
                             interpret: bool = False) -> jax.Array:
    """Fused featurization with bit-packed output: x (n, D) nonneg ->
    (n, ceil(k·b/32)) uint32 words, b = b_i + b_t in {1, 2, 4, 8}.

    Bit-exact vs ``pack_codes(encode(cws_hash(...)))``: word w of a row
    holds codes [w·32/b, (w+1)·32/b) at bit offsets (j mod 32/b)·b, and
    ``core.hashing.unpack_codes`` recovers the unpacked codes exactly.
    """
    n, d = x.shape
    k = r.shape[1]
    b = b_i + b_t
    bn, bd = min(bn, n), min(bd, d)
    bk = _packed_bk(bk, k, b)
    xp, rp, lcp, bep = _pad_operands(x, r, log_c, beta, bn, bk, bd)
    np_, dp_, kp_ = xp.shape[0], xp.shape[1], rp.shape[1]
    n_d_steps = dp_ // bd

    scratch = [_logu_scratch(bn, bd),
               pltpu.VMEM((bn, bk), jnp.float32),    # best log_a
               pltpu.VMEM((bn, bk), jnp.int32)]      # best index
    if b_t:
        scratch.append(pltpu.VMEM((bn, bk), jnp.float32))   # best t

    in_specs, _ = _cws_specs(bn, bk, bd)
    out_spec, out_shape = _packed_out(np_, kp_, bn, bk, b)
    kernel = functools.partial(_cws_encode_kernel, bd=bd,
                               n_d_steps=n_d_steps, b_i=b_i, b_t=b_t,
                               bk=bk, packed=True, num_hashes=k)
    words = pl.pallas_call(
        kernel,
        grid=(np_ // bn, kp_ // bk, n_d_steps),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(xp, rp, lcp, bep)
    return _row_words(words, n, k, b)


@functools.partial(jax.jit,
                   static_argnames=("num_hashes", "b_i", "b_t", "bn", "bk",
                                    "bd", "interpret"))
def cws_encode_rng_packed_pallas(x: jax.Array, key: jax.Array,
                                 num_hashes: int, *, b_i: int, b_t: int = 0,
                                 bn: int = 128, bk: int = 128, bd: int = 256,
                                 interpret: bool = False) -> jax.Array:
    """Zero-parameter-traffic fused featurization with bit-packed output:
    x (n, D) nonneg + PRNG key -> (n, ceil(num_hashes·b/32)) uint32.  The
    only HBM input is x and the only HBM output is the packed words."""
    n, d = x.shape
    b = b_i + b_t
    k0, k1 = key_words(key)
    kw = jnp.stack([k0, k1])
    bk = _packed_bk(bk, num_hashes, b)
    xp, kp_, bn, bk, bd, in_specs, _ = _rng_setup(
        x, num_hashes + ((-num_hashes) % bk), bn, bk, bd)
    np_, dp_ = xp.shape
    n_d_steps = dp_ // bd

    scratch = [_logu_scratch(bn, bd)] + _param_scratch(bd, bk) + [
        pltpu.VMEM((bn, bk), jnp.float32),       # best log_a
        pltpu.VMEM((bn, bk), jnp.int32)]         # best index
    if b_t:
        scratch.append(pltpu.VMEM((bn, bk), jnp.float32))    # best t

    out_spec, out_shape = _packed_out(np_, kp_, bn, bk, b)
    kernel = functools.partial(_cws_encode_rng_kernel, bd=bd,
                               n_d_steps=n_d_steps, b_i=b_i, b_t=b_t,
                               bk=bk, packed=True, num_hashes=num_hashes)
    words = pl.pallas_call(
        kernel,
        grid=(np_ // bn, kp_ // bk, n_d_steps),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(xp, kw)
    return _row_words(words, n, num_hashes, b)


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
