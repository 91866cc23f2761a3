"""Consistent Weighted Sampling (Ioffe 2010, Alg. 1 of the paper) in JAX.

For a nonnegative vector u and one hash j with random draws
``r, c ~ Gamma(2,1)``, ``beta ~ U(0,1)`` (one triple per (dimension, hash)):

    t_i   = floor(log u_i / r_i + beta_i)
    y_i   = exp(r_i (t_i - beta_i))
    a_i   = c_i / (y_i exp(r_i))
    i*    = argmin_i a_i          t* = t_{i*}

and ``Pr[(i*_u, t*_u) = (i*_v, t*_v)] = K_MM(u, v)``.

We work entirely in log space:  ``log a_i = log c_i - r_i (t_i - beta_i + 1)``
which is overflow-free and preserves the argmin. Zero entries are masked to
+inf (they can never be sampled). The same (r, log c, beta) matrices are
shared by every data vector — that is what makes the samples *consistent*.

This module is the reference/pure-JAX path; ``repro.kernels.cws_hash`` is
the Pallas TPU kernel with identical semantics (tested allclose against
``cws_hash_reference`` here).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

Array = jax.Array


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class CWSParams:
    """The shared random matrices, each of shape (D, k)."""

    r: Array       # Gamma(2,1)
    log_c: Array   # log of Gamma(2,1)
    beta: Array    # Uniform(0,1)

    @property
    def dim(self) -> int:
        return self.r.shape[0]

    @property
    def num_hashes(self) -> int:
        return self.r.shape[1]

    def tree_flatten(self):
        return (self.r, self.log_c, self.beta), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def slice_hashes(self, start: int, size: int) -> "CWSParams":
        sl = lambda m: jax.lax.dynamic_slice_in_dim(m, start, size, axis=1)
        return CWSParams(sl(self.r), sl(self.log_c), sl(self.beta))


def _gamma21(key: Array, shape) -> Array:
    """Gamma(2,1) == Exp(1) + Exp(1): exact and ~30x cheaper than the
    rejection sampler in jax.random.gamma (matters for the Monte-Carlo
    benchmarks, which draw billions of these)."""
    k1, k2 = jax.random.split(key)
    return (jax.random.exponential(k1, shape, dtype=jnp.float32) +
            jax.random.exponential(k2, shape, dtype=jnp.float32))


def make_cws_params(key: Array, dim: int, num_hashes: int,
                    dtype=jnp.float32) -> CWSParams:
    kr, kc, kb = jax.random.split(key, 3)
    shape = (dim, num_hashes)
    r = _gamma21(kr, shape)
    c = _gamma21(kc, shape)
    beta = jax.random.uniform(kb, shape, dtype=jnp.float32)
    return CWSParams(r.astype(dtype), jnp.log(c).astype(dtype),
                     beta.astype(dtype))


def _cws_block(logu: Array, params: CWSParams):
    """Core CWS math. logu: (n, D) with -inf at zeros; params (D, k).

    Returns (i_star, t_star): each (n, k) int32.
    """
    r = params.r[None, :, :]          # (1, D, k)
    beta = params.beta[None, :, :]
    log_c = params.log_c[None, :, :]
    lu = logu[:, :, None]             # (n, D, 1)

    t = jnp.floor(lu / r + beta)                       # (n, D, k)
    log_a = log_c - r * (t - beta + 1.0)
    log_a = jnp.where(jnp.isfinite(lu), log_a, jnp.inf)

    i_star = jnp.argmin(log_a, axis=1).astype(jnp.int32)          # (n, k)
    t_star = jnp.take_along_axis(t, i_star[:, None, :], axis=1)[:, 0, :]
    t_star = jnp.clip(t_star, -2**30, 2**30).astype(jnp.int32)

    all_zero = ~jnp.any(jnp.isfinite(logu), axis=1)               # (n,)
    i_star = jnp.where(all_zero[:, None], -1, i_star)
    t_star = jnp.where(all_zero[:, None], 0, t_star)
    return i_star, t_star


def cws_hash_reference(x: Array, params: CWSParams):
    """Unchunked oracle: x (n, D) nonneg -> (i_star, t_star) each (n, k)."""
    x = x.astype(jnp.float32)
    logu = jnp.where(x > 0, jnp.log(jnp.maximum(x, 1e-38)), -jnp.inf)
    return _cws_block(logu, params)


@functools.partial(jax.jit, static_argnames=("row_block", "hash_block"))
def cws_hash(x: Array, params: CWSParams, *, row_block: int = 128,
             hash_block: int = 128):
    """Chunked CWS over rows and hashes; bounded peak memory.

    x: (n, D) nonnegative. Returns (i_star, t_star), each (n, k) int32.
    """
    n, d = x.shape
    k = params.num_hashes
    x = x.astype(jnp.float32)
    logu = jnp.where(x > 0, jnp.log(jnp.maximum(x, 1e-38)), -jnp.inf)

    row_block = min(row_block, n)
    hash_block = min(hash_block, k)
    pad_n = (-n) % row_block
    pad_k = (-k) % hash_block
    logu_p = jnp.pad(logu, ((0, pad_n), (0, 0)), constant_values=-jnp.inf)
    params_p = CWSParams(
        jnp.pad(params.r, ((0, 0), (0, pad_k)), constant_values=1.0),
        jnp.pad(params.log_c, ((0, 0), (0, pad_k))),
        jnp.pad(params.beta, ((0, 0), (0, pad_k))),
    )
    n_rb = logu_p.shape[0] // row_block
    n_kb = params_p.num_hashes // hash_block

    def per_rowblock(lu_b):
        def per_hashblock(kb, _):
            p = params_p.slice_hashes(kb * hash_block, hash_block)
            return _cws_block(lu_b, p)

        i_s, t_s = jax.lax.map(lambda kb: per_hashblock(kb, None),
                               jnp.arange(n_kb))
        # (n_kb, row_block, hash_block) -> (row_block, k_padded)
        return (jnp.transpose(i_s, (1, 0, 2)).reshape(row_block, -1),
                jnp.transpose(t_s, (1, 0, 2)).reshape(row_block, -1))

    lu_blocks = logu_p.reshape(n_rb, row_block, d)
    i_star, t_star = jax.lax.map(per_rowblock, lu_blocks)
    i_star = i_star.reshape(-1, params_p.num_hashes)[:n, :k]
    t_star = t_star.reshape(-1, params_p.num_hashes)[:n, :k]
    return i_star, t_star


# ---------------------------------------------------------------------------
# regenerated-parameter variant (beyond-paper memory optimization)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("num_hashes", "hash_block",
                                             "row_block"))
def cws_hash_regen(x: Array, key: Array, num_hashes: int, *,
                   hash_block: int = 128, row_block: int = 256):
    """CWS with (r, c, beta) regenerated per hash-block from a counter key.

    The paper stores three D x k fp32 matrices (3*D*k*4 bytes of HBM reads
    per data block).  Here each hash block's parameters are derived on the
    fly from the counter-based spec in :mod:`repro.core.regen` — the
    parameter working set is O(D * hash_block) and never round-trips HBM.

    This is the ORACLE for the CWS kernel on regenerated parameters
    (``ops.cws`` on key words): both evaluate
    the same elementwise (key, d, k) -> params map, so (i*, t*) are
    bit-identical per the §3 contract, and the result is independent of
    ``hash_block``/``row_block`` (tile-order independence of the counter
    stream).  Identical statistics to `make_cws_params`; different (but
    equally valid) draws.
    """
    from repro.core.regen import key_words, regen_tile

    n, d = x.shape
    x = x.astype(jnp.float32)
    logu = jnp.where(x > 0, jnp.log(jnp.maximum(x, 1e-38)), -jnp.inf)
    hash_block = min(hash_block, num_hashes)
    row_block = min(row_block, n)
    pad_k = (-num_hashes) % hash_block
    n_kb = (num_hashes + pad_k) // hash_block
    k0, k1 = key_words(key)

    def per_hashblock(kb):
        p = CWSParams(*regen_tile(k0, k1, 0, kb * hash_block, d, hash_block))
        pad_n = (-n) % row_block
        lu = jnp.pad(logu, ((0, pad_n), (0, 0)), constant_values=-jnp.inf)
        blocks = lu.reshape(-1, row_block, d)
        i_s, t_s = jax.lax.map(lambda b: _cws_block(b, p), blocks)
        return i_s.reshape(-1, hash_block)[:n], t_s.reshape(-1, hash_block)[:n]

    i_star, t_star = jax.lax.map(per_hashblock, jnp.arange(n_kb, dtype=jnp.int32))
    i_star = jnp.transpose(i_star, (1, 0, 2)).reshape(n, -1)[:, :num_hashes]
    t_star = jnp.transpose(t_star, (1, 0, 2)).reshape(n, -1)[:, :num_hashes]
    return i_star, t_star
