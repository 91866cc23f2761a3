"""The streamed trainer's bag head against the row-gather head.

The trainer (``training.linear_trainer._bag_logits_fn``) scores and
differentiates through ``core.linear_model.bag_logits_onehot`` up to
``ONEHOT_MAX_WIDTH`` buckets a hash: a one-hot contraction over hash
blocks whose backward is the same contraction transposed.  ``bag_logits`` / ``bag_logits_packed`` gather rows and
scatter-add their cotangents.  Both select the same table rows; only
the order in which f32 sums accumulate differs, so every comparison here
holds them to the textbook bound for two summation orders of the same
terms: ``(2 m + 4) * eps * sum|terms|`` (m terms, eps the f32 unit
round-off; the 4 covers recombining the three bf16 parts and the bias).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpointer import Checkpointer
from repro.core.hashing import pack_codes
from repro.core.linear_model import (LinearParams, TrainCfg, _loss_fn,
                                     bag_logits, bag_logits_onehot,
                                     bag_logits_packed, init_bag,
                                     squared_hinge_loss)
from repro.data.synthetic import make_template_classification
from repro.pipeline import FeaturePipeline, FeatureSpec
from repro.training import fit_linear_streamed
from repro.training.linear_trainer import ONEHOT_MAX_WIDTH, _bag_logits_fn

EPS = float(jnp.finfo(jnp.float32).eps) / 2
K = 48          # hashes: 48 * 2^8 = 12,288 table rows at b = 8


def sum_bound(m: int, abs_sum) -> np.ndarray:
    return (2 * m + 4) * EPS * np.asarray(abs_sum)


def head_and_gather(spec: FeatureSpec):
    """(trainer head, gather head) for the spec's feature format."""
    pipe = FeaturePipeline.create(jax.random.PRNGKey(3), 8, spec)
    gather = (functools.partial(bag_logits_packed, num_hashes=K, b=spec.bits)
              if spec.packed else bag_logits)
    return _bag_logits_fn(pipe), gather


@pytest.mark.parametrize("edge", ["zero", "top"])
@pytest.mark.parametrize("batch", [1, 512])
@pytest.mark.parametrize("n_classes", [2, 10])
@pytest.mark.parametrize("b", [4, 8])
@pytest.mark.parametrize("packed", [False, True])
def test_head_matches_gather(packed, b, n_classes, batch, edge):
    """Logits and table/bias gradients of the trainer's head equal the
    gather head's to f32 round-off, packed and unpacked, with half the
    codes at the block's first (0) or last (width - 1) bucket."""
    spec = FeatureSpec(num_hashes=K, b_i=b, packed=packed)
    width = spec.width
    key = jax.random.PRNGKey(1000 * b + 10 * n_classes + batch)
    kc, km, kw, kb, kg = jax.random.split(key, 5)
    codes = jax.random.randint(kc, (batch, K), 0, width)
    at_edge = jax.random.bernoulli(km, 0.5, (batch, K))
    codes = jnp.where(at_edge, 0 if edge == "zero" else width - 1, codes)
    idx = (codes + jnp.arange(K) * width).astype(jnp.int32)
    feats = pack_codes(codes, b=b) if packed else idx
    params = LinearParams(
        0.3 * jax.random.normal(kw, (K * width, n_classes)),
        jax.random.normal(kb, (n_classes,)))
    g = jax.random.normal(kg, (batch, n_classes))

    head, gather = head_and_gather(spec)
    out_h, vjp_h = jax.vjp(lambda p: head(p, feats), params)
    out_g, vjp_g = jax.vjp(lambda p: gather(p, feats), params)
    (d_h,), (d_g,) = vjp_h(g), vjp_g(g)

    # Σ|terms| of every sum: the gather head on |table| and |cotangent|
    abs_params = LinearParams(jnp.abs(params.w), jnp.abs(params.b))
    row_abs = bag_logits(abs_params, idx)
    _, vjp_abs = jax.vjp(lambda p: bag_logits(p, idx), abs_params)
    (d_abs,) = vjp_abs(jnp.abs(g))

    np.testing.assert_array_less(np.abs(out_h - out_g),
                                 sum_bound(K, row_abs) + 1e-30)
    np.testing.assert_array_less(np.abs(d_h.w - d_g.w),
                                 sum_bound(batch, d_abs.w) + 1e-30)
    np.testing.assert_array_less(np.abs(d_h.b - d_g.b),
                                 sum_bound(batch, d_abs.b) + 1e-30)
    # the hit rows and only they receive gradient
    np.testing.assert_array_equal(np.asarray(d_h.w) != 0,
                                  np.asarray(d_g.w) != 0)


def test_selection_is_exact():
    """One hash, one row: the logits are the selected table row and the
    table's gradient is the cotangent, bit for bit — the three bf16
    parts carry every f32 bit, over magnitudes from 1e-30 to 1e30."""
    width, c = 256, 10
    mag = 10.0 ** jax.random.uniform(jax.random.PRNGKey(8), (width, c),
                                     minval=-30, maxval=30)
    sign = jnp.where(jax.random.bernoulli(jax.random.PRNGKey(9), 0.5,
                                          (width, c)), 1.0, -1.0)
    params = LinearParams(sign * mag, jnp.zeros((c,)))
    codes = jnp.arange(width, dtype=jnp.int32)[:, None]
    np.testing.assert_array_equal(
        np.asarray(bag_logits_onehot(params, codes)), np.asarray(params.w))
    for r in (0, width - 1):
        g = params.w[r:r + 1]
        _, vjp = jax.vjp(lambda p: bag_logits_onehot(p, codes[r:r + 1]),
                         params)
        (d,) = vjp(g)
        np.testing.assert_array_equal(np.asarray(d.w[r]), np.asarray(g[0]))
        assert int(jnp.count_nonzero(d.w)) == int(jnp.count_nonzero(g))


def test_packed_and_unpacked_heads_are_bit_identical():
    """Same local codes, so the same float ops: packed and unpacked
    training stay bit-identical."""
    width = 1 << 8
    codes = jax.random.randint(jax.random.PRNGKey(5), (512, K), 0, width)
    idx = (codes + jnp.arange(K) * width).astype(jnp.int32)
    params = LinearParams(
        jax.random.normal(jax.random.PRNGKey(6), (K * width, 10)),
        jnp.zeros((10,)))
    outs = []
    for packed, feats in ((False, idx), (True, pack_codes(codes, b=8))):
        head, _ = head_and_gather(FeatureSpec(num_hashes=K, b_i=8,
                                              packed=packed))
        outs.append(jax.value_and_grad(
            lambda p: jnp.sum(jnp.sin(head(p, feats))))(params))
    for a, b in zip(jax.tree_util.tree_leaves(outs[0]),
                    jax.tree_util.tree_leaves(outs[1])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("b_i", [14, 15])
def test_trainer_head_follows_the_spec_width(b_i):
    """Up to ONEHOT_MAX_WIDTH buckets the trainer contracts (no gather,
    no scatter-add in its gradient); wider specs keep the gather head."""
    spec = FeatureSpec(num_hashes=2, b_i=b_i)
    head, gather = head_and_gather(spec)
    params = init_bag(jax.random.PRNGKey(0), spec.num_features, 3)
    idx = jnp.array([[0, 2 * spec.width - 1]], jnp.int32)
    grad = jax.value_and_grad(lambda p: jnp.sum(head(p, idx)))
    hlo = jax.jit(grad).lower(params).as_text()
    wide = spec.width > ONEHOT_MAX_WIDTH
    assert wide == (b_i == 15)
    assert ("stablehlo.scatter" in hlo) == wide
    assert ("stablehlo.gather" in hlo) == wide
    np.testing.assert_array_equal(np.asarray(grad(params)[1].w),
                                  np.asarray(jax.grad(lambda p: jnp.sum(
                                      gather(p, idx)))(params).w))


@pytest.mark.parametrize("n", [1100, 1536])
def test_row_blocks_match_one_block(n):
    """Batches over 512 rows go block by block (a remainder block when
    512 does not divide n): same logits and gradients as the gather."""
    width = 16
    codes = jax.random.randint(jax.random.PRNGKey(n), (n, 8), 0, width)
    idx = (codes + jnp.arange(8) * width).astype(jnp.int32)
    params = LinearParams(
        jax.random.normal(jax.random.PRNGKey(1), (8 * width, 3)),
        jnp.zeros((3,)))
    g = jax.random.normal(jax.random.PRNGKey(2), (n, 3))
    out_h, vjp_h = jax.vjp(lambda p: bag_logits_onehot(p, codes), params)
    out_g, vjp_g = jax.vjp(lambda p: bag_logits(p, idx), params)
    (d_h,), (d_g,) = vjp_h(g), vjp_g(g)
    abs_w = LinearParams(jnp.abs(params.w), params.b)
    _, vjp_abs = jax.vjp(lambda p: bag_logits(p, idx), abs_w)
    (d_abs,) = vjp_abs(jnp.abs(g))
    np.testing.assert_array_less(np.abs(out_h - out_g),
                                 sum_bound(8, bag_logits(abs_w, idx)))
    np.testing.assert_array_less(np.abs(d_h.w - d_g.w),
                                 sum_bound(n, d_abs.w) + 1e-30)


def test_head_scratch_is_bounded_at_full_batch():
    """The batch_size == n path hands the head the whole (n, k) matrix:
    at n = 60,000, k = 1,024, b = 8 a materialized (n, k, 256) one-hot
    would be 31 GB.  Row blocks keep the compiled update's scratch to
    one (512, F) block plus the batch's own local codes."""
    n, k, width, c = 60_000, 1024, 256, 10
    w = jax.ShapeDtypeStruct((k * width, c), jnp.float32)
    bias = jax.ShapeDtypeStruct((c,), jnp.float32)
    codes = jax.ShapeDtypeStruct((n, k), jnp.int32)

    def loss(w, bias, codes):
        return jnp.sum(jnp.square(bag_logits_onehot(LinearParams(w, bias),
                                                    codes)))

    mem = jax.jit(jax.grad(loss)).lower(w, bias, codes).compile() \
        .memory_analysis()
    block = 512 * k * width * 4          # one block's one-hot, even in f32
    codes_bytes = n * k * 4
    assert mem.temp_size_in_bytes < codes_bytes + 2 * block, mem
    assert mem.temp_size_in_bytes < n * k * width * 2 // 20


# -- the trainer's surfaces ----------------------------------------------


@pytest.fixture(scope="module")
def problem():
    ds = make_template_classification(3, n_train=160, n_test=80, dim=32,
                                      n_classes=3, mult_noise=1.1,
                                      spike_prob=0.02, density=0.3)
    spec = FeatureSpec(num_hashes=24, b_i=4)
    pipe = FeaturePipeline.create(jax.random.PRNGKey(7), 32, spec)
    return pipe, jnp.asarray(ds.x_train), jnp.asarray(ds.y_train)


class _KeepStates(Checkpointer):
    """The fit's checkpoint hook, writing nothing: keeps (params, opt
    state) after every step."""

    def __init__(self, directory):
        super().__init__(directory)
        self.kept = {}

    def save_async(self, step, tree, extra=None):
        self.kept[step] = jax.tree_util.tree_map(np.asarray, tree)


def _first_moment(opt_state):
    return [s for s in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda s: hasattr(s, "mu"))
        if hasattr(s, "mu")][0].mu


@pytest.mark.parametrize("batch", ["minibatch", "batch_size_n"])
def test_trainer_steps_apply_the_gather_heads_gradient(problem, tmp_path,
                                                       batch):
    """Three update steps of ``fit_linear_streamed`` (32-row minibatches,
    or the batch_size == n path): the gradient each step applied, read
    back from AdamW's first moment, equals the gather head's gradient
    at that step's parameters on that step's batch, to f32 round-off."""
    pipe, x, y = problem
    n = x.shape[0]
    bs = 32 if batch == "minibatch" else n
    cfg = TrainCfg(n_classes=3, steps=3, lr=0.05, l2=1e-5, batch_size=bs)
    key = jax.random.PRNGKey(11)
    p0 = init_bag(jax.random.PRNGKey(0), pipe.num_features, 3)
    keep = _KeepStates(tmp_path / "ckpt")
    fit_linear_streamed(p0, pipe, x, y, cfg=cfg, shuffle_key=key,
                        ckpt=keep, ckpt_every=1)
    perm = (jax.random.permutation(jax.random.fold_in(key, 0), n)
            if bs < n else jnp.arange(n))
    b1 = 0.9
    mu_prev = jax.tree_util.tree_map(np.zeros_like, p0)
    params = p0
    for step in range(1, 4):
        pos = (step - 1) % (n // bs)
        sel = perm[pos * bs:(pos + 1) * bs]
        feats, yb = pipe.features(x[sel]), y[sel]
        kept = keep.kept[step]
        mu = _first_moment(kept["opt_state"])
        # the gradient the step applied: mu = b1 mu_prev + (1 - b1) g
        applied = ((np.float64(mu.w) - b1 * np.float64(mu_prev.w))
                   / (1 - b1))
        want = jax.grad(_loss_fn)(params, feats, yb, cfg, bag_logits)
        gnorm = np.sqrt(sum(float(jnp.sum(jnp.square(v)))
                            for v in jax.tree_util.tree_leaves(want)))
        assert gnorm < 10.0, "the clip would scale the gradient"

        # bound: the backward's sums over rows, the logits' own round-off
        # carried through the hinge (second derivative 2 / bs), mu's
        # f32 rounding, amplified by the inversion
        logits = bag_logits(params, feats)
        dlog = jax.grad(squared_hinge_loss)(logits, yb, 3)
        abs_p = LinearParams(jnp.abs(params.w), jnp.zeros(3))
        dlogit = sum_bound(pipe.spec.num_hashes,
                           bag_logits(abs_p, feats)) * 2 / bs
        _, vjp_abs = jax.vjp(lambda p: bag_logits(p, feats), abs_p)
        (sums,) = vjp_abs(jnp.abs(dlog))
        (carried,) = vjp_abs(jnp.asarray(dlogit, jnp.float32))
        tol = (sum_bound(bs, sums.w + jnp.abs(2 * cfg.l2 * params.w))
               + np.asarray(carried.w)
               + 4 * EPS * (np.abs(mu.w) + b1 * np.abs(mu_prev.w)) / (1 - b1))
        np.testing.assert_array_less(np.abs(applied - np.asarray(want.w)),
                                     tol + 1e-30)
        mu_prev, params = mu, kept["params"]
