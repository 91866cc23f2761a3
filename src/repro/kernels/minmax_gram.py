"""Pallas TPU kernel for the min-sum Gram tile: S[m,n] = sum_d min(x[m,d], y[n,d]).

With nonnegative data the full min-max Gram follows from row sums:
    K_MM = S / (rowsum(x)[:,None] + rowsum(y)[None,:] - S)
so the kernel only accumulates S (half the naive FLOPs — the max-side sum
is algebraically free). Matmul-shaped tiling: grid (M/BM, N/BN, D/BD) with
D innermost and an (BM, BN) fp32 accumulator in VMEM scratch. The inner
loop is rank-2 VPU min+add per dimension (no rank-3 temporaries), i.e. the
MXU is idle by construction — this kernel's roofline is the VPU, not the
systolic array, which DESIGN.md §2 discusses.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import registry


def _minsum_kernel(x_ref, y_ref, out_ref, xt, yt, acc, *, bd: int,
                   n_d_steps: int):
    d_step = pl.program_id(2)

    @pl.when(d_step == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc[...])

    # Both tiles are stored transposed, (BD, BM) and (BD, BN), so that
    # dimension d is a sublane row: Mosaic lowers a dynamic sublane read
    # and refuses the dynamic lane slice x[:, d].
    xt[...] = jnp.transpose(x_ref[...])
    yt[...] = jnp.transpose(y_ref[...])

    def body(d, a):
        xc = jnp.transpose(xt[pl.ds(d, 1), :])     # (BM, 1)
        return a + jnp.minimum(xc, yt[pl.ds(d, 1), :])

    acc[...] = jax.lax.fori_loop(0, bd, body, acc[...])

    @pl.when(d_step == n_d_steps - 1)
    def _emit():
        out_ref[...] = acc[...]


def _resolve_blocks(x, y, bm, bn, bd):
    """Fill unset block sizes from ``registry.choose_blocks`` (the
    "min_sum" family: autotune table, then the x+y+acc VMEM model).
    Runs OUTSIDE jit so a table update (registry.load_block_table) takes
    effect on the next call instead of being baked into a cached trace."""
    m, d = x.shape
    n = y.shape[0]
    if bm is None or bn is None or bd is None:
        hm, hn, hd = registry.choose_blocks(m, d, n, op="min_sum")
        bm, bn, bd = bm or hm, bn or hn, bd or hd
    return bm, bn, bd


def min_sum_pallas(x: jax.Array, y: jax.Array, *, bm: int | None = None,
                   bn: int | None = None, bd: int | None = None,
                   interpret: bool = False) -> jax.Array:
    """x: (m, D), y: (n, D) nonneg -> (m, n) fp32 min-sums."""
    bm, bn, bd = _resolve_blocks(x, y, bm, bn, bd)
    return _min_sum_pallas(x, y, bm=bm, bn=bn, bd=bd, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bd", "interpret"))
def _min_sum_pallas(x: jax.Array, y: jax.Array, *, bm: int, bn: int,
                    bd: int, interpret: bool = False) -> jax.Array:
    m, d = x.shape
    n = y.shape[0]
    bm, bn, bd = min(bm, m), min(bn, n), min(bd, d)
    pad_m, pad_n, pad_d = (-m) % bm, (-n) % bn, (-d) % bd
    # zero-padding D adds min(0,0)=0 to the sum: harmless.
    xp = jnp.pad(x.astype(jnp.float32), ((0, pad_m), (0, pad_d)))
    yp = jnp.pad(y.astype(jnp.float32), ((0, pad_n), (0, pad_d)))
    mp, np_, dp_ = xp.shape[0], yp.shape[0], xp.shape[1]
    n_d_steps = dp_ // bd

    out = pl.pallas_call(
        functools.partial(_minsum_kernel, bd=bd, n_d_steps=n_d_steps),
        grid=(mp // bm, np_ // bn, n_d_steps),
        in_specs=[
            pl.BlockSpec((bm, bd), lambda i, j, s: (i, s)),
            pl.BlockSpec((bn, bd), lambda i, j, s: (j, s)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bd, bm), jnp.float32),
                        pltpu.VMEM((bd, bn), jnp.float32),
                        pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(xp, yp)
    return out[:m, :n]


def minmax_gram_pallas(x: jax.Array, y: jax.Array, *, bm: int | None = None,
                       bn: int | None = None, bd: int | None = None,
                       interpret: bool = False) -> jax.Array:
    bm, bn, bd = _resolve_blocks(x, y, bm, bn, bd)
    return _minmax_gram_pallas(x, y, bm=bm, bn=bn, bd=bd,
                               interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bd", "interpret"))
def _minmax_gram_pallas(x: jax.Array, y: jax.Array, *, bm: int, bn: int,
                        bd: int, interpret: bool = False) -> jax.Array:
    x = jnp.maximum(x.astype(jnp.float32), 0.0)
    y = jnp.maximum(y.astype(jnp.float32), 0.0)
    mins = _min_sum_pallas(x, y, bm=bm, bn=bn, bd=bd, interpret=interpret)
    maxs = jnp.sum(x, -1)[:, None] + jnp.sum(y, -1)[None, :] - mins
    return mins / jnp.maximum(maxs, 1e-30)
