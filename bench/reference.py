"""The plain reference: 0-bit CWS features and the linear embedding-bag
head in straightforward ``jax.numpy``, float32 by default.

It imports nothing of the program.  It follows arXiv:1503.01737
(Ioffe's consistent weighted sampling with the t* part dropped) in log
space, and the program's stated conventions:

  * CWS, per nonzero entry u_d and hash j with (r, c, beta) drawn per
    (d, j):  t = floor(log u / r + beta),
             log a = log c - r (t - beta + 1),  i* = argmin_d log a
    (the first d on ties); an all-zero row has no sample (sentinel -1).
  * parameters: "stored" draws (r, c, beta) with ``jax.random`` as
    r, c ~ Gamma(2, 1) = Exp(1) + Exp(1), beta ~ U[0, 1), from the key
    split three ways; "regen" draws them per (d, j) from a counter-based
    Threefry-2x32 stream (below), so no matrix has to be stored.
  * code: the low b_i bits of i* (sentinel -> bucket 0); feature index
    j * 2^b_i + code; packed words hold 32 / b codes, code j at bit
    (j mod 32/b) * b of word j div (32/b).
  * head: logits = sum_j W[index_j] + bias; loss = mean over rows of the
    one-vs-rest squared hinge, plus l2 * |W|^2; the optimizer clips the
    global gradient norm to 10, then takes AdamW steps (b1 0.9, b2 0.95,
    eps 1e-8) at a cosine rate from lr down to lr / 10 over the fit.

``dtype`` selects the arithmetic: float32 is the reference, bfloat16 the
lower-precision control.  Rows go through in blocks so that the
(rows, D, k) intermediate fits beside the program's state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
ROW_BLOCK = 16

# -- parameters ----------------------------------------------------------


def stored_params(key, dim: int, k: int):
    """(r, log c, beta), each (dim, k) float32, drawn with jax.random."""
    kr, kc, kb = jax.random.split(key, 3)

    def gamma21(kk):
        k1, k2 = jax.random.split(kk)
        return (jax.random.exponential(k1, (dim, k), dtype=F32) +
                jax.random.exponential(k2, (dim, k), dtype=F32))

    return (gamma21(kr), jnp.log(gamma21(kc)),
            jax.random.uniform(kb, (dim, k), dtype=F32))


# Threefry-2x32, 20 rounds, over the counter (d, j); one key word is
# tweaked per stream so r, c and beta come from three streams.
_TWEAK_R, _TWEAK_C, _TWEAK_BETA = 0x243F6A89, 0x85A308D3, 0x13198A2F
_PARITY = 0x1BD11BDA
_ROT = ((13, 15, 26, 6), (17, 29, 16, 24))


def _threefry(k0, k1, x0, x1):
    u = jnp.uint32
    ks = (k0, k1, k0 ^ k1 ^ u(_PARITY))
    x0, x1 = x0 + ks[0], x1 + ks[1]
    for i in range(5):
        for r in _ROT[i % 2]:
            x0 = x0 + x1
            x1 = ((x1 << u(r)) | (x1 >> u(32 - r))) ^ x0
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + u(i + 1)
    return x0, x1


def _unit(bits):
    """The top 24 bits as a float32 in [0, 1)."""
    return (bits >> jnp.uint32(8)).astype(jnp.int32).astype(F32) * F32(2 ** -24)


def regen_params(key, dim: int, k: int):
    """(r, log c, beta) of the counter stream for the raw uint32[2] key."""
    kw = jax.random.key_data(key) if jnp.issubdtype(
        key.dtype, jax.dtypes.prng_key) else key
    kw = jnp.asarray(kw).astype(jnp.uint32).reshape(-1)
    k0, k1 = kw[0], kw[1]
    d = jax.lax.broadcasted_iota(jnp.int32, (dim, k), 0).astype(jnp.uint32)
    j = jax.lax.broadcasted_iota(jnp.int32, (dim, k), 1).astype(jnp.uint32)

    def exp1(bits):
        return -jnp.log1p(-_unit(bits))

    a, b = _threefry(k0, k1 ^ jnp.uint32(_TWEAK_R), d, j)
    r = jnp.maximum(exp1(a) + exp1(b), F32(1e-12))
    a, b = _threefry(k0, k1 ^ jnp.uint32(_TWEAK_C), d, j)
    log_c = jnp.log(jnp.maximum(exp1(a) + exp1(b), F32(1e-38)))
    a, _ = _threefry(k0, k1 ^ jnp.uint32(_TWEAK_BETA), d, j)
    return r, log_c, _unit(a)


def cws_params(cfg: dict, key):
    fn = regen_params if cfg["params"] == "regen" else stored_params
    return fn(key, cfg["dim"], cfg["num_hashes"])


# -- features ------------------------------------------------------------


def _istar_block(x, r, log_c, beta, dtype):
    """(m, D) rows -> (m, k) int32 i*, -1 for an all-zero row."""
    x = x.astype(F32)
    lu = jnp.where(x > 0, jnp.log(jnp.maximum(x, F32(1e-38))), -jnp.inf)
    lu = lu.astype(dtype)[:, :, None]
    r, log_c, beta = (a.astype(dtype)[None] for a in (r, log_c, beta))
    t = jnp.floor(lu / r + beta)
    log_a = log_c - r * (t - beta + jnp.asarray(1, dtype))
    log_a = jnp.where(jnp.isfinite(lu), log_a, jnp.asarray(jnp.inf, dtype))
    i_star = jnp.argmin(log_a, axis=1).astype(jnp.int32)
    empty = ~jnp.any(x > 0, axis=1)
    return jnp.where(empty[:, None], -1, i_star)


@functools.partial(jax.jit, static_argnames=("b_i", "dtype"))
def codes(x, r, log_c, beta, *, b_i: int, dtype=F32):
    """(n, D) rows -> (n, k) int32 codes in [0, 2^b_i), in row blocks;
    an all-zero row's codes are 0."""
    n, dim = x.shape
    pad = (-n) % ROW_BLOCK
    xb = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, ROW_BLOCK, dim)
    i_star = jax.lax.map(
        lambda b: _istar_block(b, r, log_c, beta, dtype), xb)
    i_star = i_star.reshape(-1, r.shape[1])[:n]
    return jnp.where(i_star < 0, 0, i_star & ((1 << b_i) - 1))


def indices(code, b_i: int):
    """Codes -> global embedding-bag indices j * 2^b_i + code."""
    k = code.shape[-1]
    return jnp.arange(k, dtype=jnp.int32) * (1 << b_i) + code


def unpack(words, k: int, b: int):
    """(n, ceil(k b / 32)) uint32 words -> (n, k) int32 codes."""
    per = 32 // b
    w = np.asarray(words, np.uint32)
    j = np.arange(k)
    return ((w[:, j // per] >> ((j % per) * b).astype(np.uint32)) &
            np.uint32((1 << b) - 1)).astype(np.int32)


# -- head ----------------------------------------------------------------


def logits(w, bias, idx):
    return jnp.take(w, idx, axis=0).sum(axis=1) + bias


def loss(params, idx, y, *, n_classes: int, l2: float):
    w, bias = params
    z = logits(w, bias, idx)
    sign = jnp.where(jax.nn.one_hot(y, n_classes, dtype=z.dtype) > 0, 1, -1)
    margins = jnp.maximum(0, 1 - sign.astype(z.dtype) * z)
    return jnp.mean(jnp.sum(margins * margins, axis=-1)) + l2 * jnp.sum(w * w)


def clipped_grad(params, idx, y, *, n_classes: int, l2: float,
                 max_norm: float = 10.0):
    g = jax.grad(loss)(params, idx, y, n_classes=n_classes, l2=l2)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(a.astype(F32))) for a in g))
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-9))
    return tuple((a.astype(F32) * scale).astype(a.dtype) for a in g)


def cosine_lr(lr: float, total: int, step: int) -> float:
    t = min(step / max(total, 1), 1.0)
    return lr * (0.1 + 0.9 * 0.5 * (1.0 + np.cos(np.pi * t)))


@functools.partial(jax.jit, static_argnames=("n_classes", "l2"))
def adam_step(params, m, v, idx, y, lr, count, *, n_classes: int, l2: float):
    """One clipped AdamW step; returns (params, m, v, clipped grads)."""
    g = clipped_grad(params, idx, y, n_classes=n_classes, l2=l2)
    dt = params[0].dtype
    c1 = 1 - 0.9 ** count
    c2 = 1 - 0.95 ** count
    m = tuple((0.9 * a + 0.1 * b).astype(dt) for a, b in zip(m, g))
    v = tuple((0.95 * a + 0.05 * b * b).astype(dt) for a, b in zip(v, g))
    new = tuple((p - lr * (a / c1) / (jnp.sqrt(b / c2) + 1e-8)).astype(dt)
                for p, a, b in zip(params, m, v))
    return new, m, v, g


def train(params, batches, *, lr: float, total_steps: int, n_classes: int,
          l2: float, early: int = 1):
    """Follow the program's fit: ``batches`` yields (indices, labels) for
    each step.  Returns {"last": params after the last step, "early":
    params after ``early`` steps, "grad1": clipped grads of the first}."""
    dt = params[0].dtype
    m = tuple(jnp.zeros_like(p) for p in params)
    v = tuple(jnp.zeros_like(p) for p in params)
    out = {}
    for i, (idx, y) in enumerate(batches):
        rate = jnp.asarray(cosine_lr(lr, total_steps, i), dt)
        count = jnp.asarray(i + 1, F32)
        params, m, v, g = adam_step(params, m, v, idx, y, rate, count,
                                    n_classes=n_classes, l2=l2)
        if i == 0:
            out["grad1"] = g
        if i + 1 == early:
            out["early"] = params
    out["last"] = params
    return out
