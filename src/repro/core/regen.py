"""Counter-based regeneration spec for CWS parameters (DESIGN.md §7).

The stored-parameter path keeps three (D, k) fp32 matrices resident and
pays 12·BD·BK bytes of HBM reads per kernel tile.  This module defines the
ONE deterministic function

    (key, d, k)  ->  (r[d,k], log_c[d,k], beta[d,k])

that every regenerated-parameter implementation — the Pallas kernel body
(`kernels/cws_hash.py:cws_*_rng_pallas`), its interpret-mode run, and the
pure-JAX oracle (`core/cws.py:cws_hash_regen`) — evaluates elementwise, so
all three are bit-identical by construction and any tile decomposition of
the (D, k) grid yields the same parameters (tile-order independence).

Design (see DESIGN.md §7 for the full derivation):

  * PRNG: Threefry-2x32, the standard 20-round rotation schedule —
    pure uint32 add/xor/rotate, so it runs unchanged inside a Pallas TPU
    kernel body, under the Pallas interpreter, and in plain JAX.  The
    counter is the *global* (d, k) coordinate pair; the key is the user's
    PRNG key with one word XOR-tweaked per stream (r / c / beta), giving
    three independent 2x32 streams per coordinate.
  * Distributions by inverse-CDF: a uniform comes from the top 24 bits of
    a counter word (exact in fp32); Exp(1) = -log1p(-u); Gamma(2,1) =
    Exp(1) + Exp(1) (the two words of one threefry call); beta = u.
    No rejection sampling, so the draw count per (d, k) is static — a
    hard requirement inside a Pallas kernel.

NOTE: this stream is intentionally NOT the same as `make_cws_params`
(which uses jax.random's key-split tree); it is a *parallel* parameter
universe with identical statistics.  Consistency only requires every
vector to be hashed under the same (key -> params) map.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

# Distinct key tweaks per parameter stream.  Arbitrary odd constants;
# fixed forever (changing them changes every regenerated hash).
STREAM_R = np.uint32(0x243F6A89)     # pi fractional bits
STREAM_C = np.uint32(0x85A308D3)
STREAM_BETA = np.uint32(0x13198A2F)

_THREEFRY_PARITY = np.uint32(0x1BD11BDA)
_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))


def _rotl(x: Array, r: int) -> Array:
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def threefry2x32(k0: Array, k1: Array, x0: Array, x1: Array):
    """Threefry-2x32 (20 rounds), bit-identical to jax.random's core PRNG.

    Keys are uint32 scalars, counters uint32 arrays (any shape); returns
    two uint32 arrays of the counter shape.  Only add/xor/rotate — safe in
    Pallas kernel bodies.
    """
    ks = (k0, k1, k0 ^ k1 ^ _THREEFRY_PARITY)
    x0 = x0 + ks[0]
    x1 = x1 + ks[1]
    for i in range(5):
        for r in _ROTATIONS[i % 2]:
            x0 = x0 + x1
            x1 = _rotl(x1, r) ^ x0
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + np.uint32(i + 1)
    return x0, x1


def _uniform(bits: Array) -> Array:
    """Top 24 bits -> fp32 uniform in [0, 1) (exact: 24-bit mantissa).
    The cast goes through int32 (the value is below 2^24, so nothing
    changes): Mosaic lowers no direct uint32 -> float32 conversion."""
    top = (bits >> np.uint32(8)).astype(jnp.int32)
    return top.astype(jnp.float32) * np.float32(2.0 ** -24)


def _exp1(u: Array) -> Array:
    """Inverse-CDF Exp(1); u in [0, 1) keeps the argument of log1p in
    (-1, 0], so the result is finite and nonnegative."""
    return -jnp.log1p(-u)


def key_words(key: Array) -> Tuple[Array, Array]:
    """Two uint32 key words from a jax PRNG key (typed or raw uint32[2])."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    key = jnp.asarray(key).astype(jnp.uint32).reshape(-1)
    return key[0], key[1]


def regen_tile(k0: Array, k1: Array, d0, kh0, bd: int, bk: int):
    """(r, log_c, beta) fp32 tiles of shape (bd, bk) for the global
    coordinate window [d0, d0+bd) x [kh0, kh0+bk).

    ``d0``/``kh0`` may be traced scalars (grid offsets inside a kernel) or
    Python ints (the oracle).  Elementwise in the global coordinates, so
    any tiling of the (D, k) grid produces identical values.
    """
    d = (jnp.asarray(d0, jnp.int32) +
         jax.lax.broadcasted_iota(jnp.int32, (bd, bk), 0)).astype(jnp.uint32)
    kh = (jnp.asarray(kh0, jnp.int32) +
          jax.lax.broadcasted_iota(jnp.int32, (bd, bk), 1)).astype(jnp.uint32)

    u0, u1 = threefry2x32(k0, k1 ^ STREAM_R, d, kh)
    r = _exp1(_uniform(u0)) + _exp1(_uniform(u1))          # Gamma(2,1)
    r = jnp.maximum(r, np.float32(1e-12))                  # div-safe (p~2^-48)

    u0, u1 = threefry2x32(k0, k1 ^ STREAM_C, d, kh)
    c = _exp1(_uniform(u0)) + _exp1(_uniform(u1))          # Gamma(2,1)
    log_c = jnp.log(jnp.maximum(c, np.float32(1e-38)))

    u0, _ = threefry2x32(k0, k1 ^ STREAM_BETA, d, kh)
    beta = _uniform(u0)                                    # U[0,1)
    return r, log_c, beta


# ---------------------------------------------------------------------------
# numerics-analysis site (repro.analysis / tools/kernel_lint.py)
# ---------------------------------------------------------------------------
# The one blessed-wraparound site: threefry's add/xor/rotate arithmetic is
# modular by design, so the interval audit runs with allow_wrap=True —
# which still enforces shift amounts in [0, 31], the exactness of the
# (bits >> 8) -> fp32 uniform conversion (2^24 mantissa contract), and
# gather bounds; only the intended mod-2^32 adds are waived.

from repro.kernels import registry as _registry  # noqa: E402


@_registry.register_numerics_site("regen.threefry_tile")
def _numerics_site_regen_tile():
    from repro.analysis.intervals import unknown_ival
    k0 = unknown_ival((), jnp.uint32)
    k1 = unknown_ival((), jnp.uint32)
    return {"fn": lambda k0, k1: regen_tile(k0, k1, 0, 0, 8, 16),
            "args": (k0, k1), "allow_wrap": True}


def regen_params(key: Array, dim: int, num_hashes: int):
    """Materialize the full (dim, num_hashes) parameter matrices of the
    counter stream — the oracle/reference form (CWSParams), bit-identical
    to what the rng kernels derive tile by tile."""
    from repro.core.cws import CWSParams
    k0, k1 = key_words(key)
    r, log_c, beta = regen_tile(k0, k1, 0, 0, dim, num_hashes)
    return CWSParams(r, log_c, beta)
