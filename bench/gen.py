"""Inputs made from ``--seed``: dataset rows on the device, and request
schedules for open-loop traffic.

Rows follow ``repro.data.synthetic.make_template_classification`` (class
templates, multiplicative noise, spikes), copied here so that the
benchmark's inputs cannot move with the program.  Two changes: the whole
set is drawn in one jitted call on the device, and values are squashed
into [0, 1) by ``v / (1 + v)`` (pixel intensities, normalised counts).
The template density and the spike rate set the nonzero share, which a
configuration file states.

A request schedule takes its sequence of gaps from the traffic file's
``base_seed``; the run's seed only rotates that sequence (and draws the
rows sent), so every seed offers the same work, shifted in time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

MAX_SEED = 2 ** 63


def root_key(seed: int) -> jax.Array:
    """A raw uint32[2] key holding all 64 bits of ``seed``
    (``jax.random.PRNGKey`` keeps only the low 32 of a large int)."""
    if not 0 <= seed < MAX_SEED:
        raise ValueError(f"--seed must be in [0, 2**63); got {seed}")
    return jnp.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                     dtype=jnp.uint32)


def sub_key(seed: int, tag: int) -> jax.Array:
    """Independent key for one use of the seed (data, CWS params, ...)."""
    return jax.random.fold_in(root_key(seed), tag)


KEY_DATA, KEY_CWS, KEY_TABLE, KEY_SHUFFLE = range(4)


def template_density(nnz_share: float, spike_prob: float,
                     keep: float = 0.9) -> float:
    """Template density that gives ``nnz_share`` nonzero entries:
    P(nonzero) = 1 - (1 - keep * density) * (1 - spike_prob)."""
    d = (1.0 - (1.0 - nnz_share) / (1.0 - spike_prob)) / keep
    if not 0.0 < d <= 1.0:
        raise ValueError(f"nnz_share {nnz_share} is out of reach with "
                         f"spike_prob {spike_prob}")
    return d


@functools.partial(jax.jit, static_argnames=("n", "dim", "n_classes",
                                             "density", "spike_prob"))
def make_rows(key, *, n: int, dim: int, n_classes: int, density: float,
              spike_prob: float):
    """(x (n, dim) float32 in [0, 1), y (n,) int32), on the device."""
    k_t, k_m, k_s = jax.random.split(key, 3)
    # each class template holds exactly round(density * dim) coordinates,
    # so that every seed's rows carry the same share of nonzeros
    rank = jnp.argsort(jnp.argsort(
        jax.random.uniform(k_t, (n_classes, dim)), axis=1), axis=1)
    mask = rank < round(density * dim)
    mag = jnp.exp(jax.random.exponential(jax.random.fold_in(k_t, 1),
                                         (n_classes, dim)) / 1.2) - 1.0
    templates = mask * (0.5 + mag)
    y = jax.random.randint(jax.random.fold_in(k_m, 0), (n,), 0, n_classes)
    noise = jnp.exp(1.3 * jax.random.normal(jax.random.fold_in(k_m, 1),
                                            (n, dim)))
    keep = jax.random.bernoulli(jax.random.fold_in(k_m, 2), 0.9, (n, dim))
    spikes = (jax.random.bernoulli(k_s, spike_prob, (n, dim)) * 12.0 *
              (jnp.exp(jax.random.exponential(jax.random.fold_in(k_s, 1),
                                              (n, dim)) / 1.2) - 1.0))
    v = templates[y] * noise * keep + spikes
    return (v / (1.0 + v)).astype(jnp.float32), y.astype(jnp.int32)


def rows_for(cfg: dict, seed: int, n: int):
    """``n`` rows of configuration ``cfg`` from ``seed``."""
    return make_rows(sub_key(seed, KEY_DATA), n=n, dim=cfg["dim"],
                     n_classes=cfg["n_classes"],
                     density=template_density(cfg["nnz_share"],
                                              cfg["spike_prob"]),
                     spike_prob=cfg["spike_prob"])


def request_schedule(mix: dict, seed: int, seconds: float):
    """Open-loop schedule for ``seconds``: (due times in s from the window
    start, request sizes in rows), both numpy.

    Arrivals are Poisson at ``rate_per_s`` and every request holds
    ``rows_per_request`` rows.  The sequence of gaps comes from
    ``base_seed``; the seed rotates it."""
    rate = float(mix["rate_per_s"])
    n = int(np.ceil(rate * seconds))
    gaps = np.random.default_rng(int(mix["base_seed"])).exponential(
        1.0 / rate, n)
    gaps = np.roll(gaps, int(np.random.default_rng(seed).integers(n)))
    # the fixed multiset of gaps spans about ``seconds``; rescale it to
    # exactly that so every seed's window offers the same load
    due = np.cumsum(gaps) * (seconds / gaps.sum())
    due = np.concatenate([[0.0], due[:-1]])
    return due, np.full(n, int(mix["rows_per_request"]), np.int64)
