"""Streaming minibatch training (repro.training.linear_trainer) and the
index-bounds/ragged-chunk correctness fixes that ride with it.

Covers: streamed-vs-fullbatch parity (bit-identity at batch_size = n
and on minibatches against a materialized fit with the trainer's one-hot
head, accuracy parity for true minibatches), OOB/sentinel gather guards in
bag_logits/hashed_logits, the single-compile ragged-streaming contract
(counted via the donating chunk fn's jit cache), never-materializing the
(n, k) index matrix (launch-shape assertions), and empty/one-row batches.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.core.linear_model import (LinearParams, TrainCfg, _loss_fn,
                                     bag_logits, bag_logits_onehot,
                                     fit_linear, hashed_logits, init_bag,
                                     init_hashed, linear_accuracy,
                                     make_linear_tx, validate_bag_features)
from repro.analysis import compile_guard
from repro.data.synthetic import make_template_classification
from repro.pipeline import FeaturePipeline, FeatureSpec
from repro.training import fit_linear_streamed, streamed_accuracy


def rand_nonneg(key, shape, sparsity=0.4):
    k1, k2 = jax.random.split(key)
    mag = jnp.exp(jax.random.normal(k1, shape))
    mask = jax.random.bernoulli(k2, 1 - sparsity, shape)
    return mag * mask


@pytest.fixture(scope="module")
def problem():
    """A small learnable classification problem + bound pipeline."""
    ds = make_template_classification(3, n_train=160, n_test=80, dim=32,
                                      n_classes=3, mult_noise=1.1,
                                      spike_prob=0.02, density=0.3)
    xtr = jnp.asarray(ds.x_train)
    xte = jnp.asarray(ds.x_test)
    ytr = jnp.asarray(ds.y_train)
    yte = jnp.asarray(ds.y_test)
    spec = FeatureSpec(num_hashes=24, b_i=4)
    pipe = FeaturePipeline.create(jax.random.PRNGKey(7), 32, spec)
    return pipe, xtr, ytr, xte, yte


def local_codes(pipe, feats):
    """The pipeline's global indices as the trainer's head takes them:
    hash j's code into its own block, idx - j * width."""
    return feats - jnp.arange(pipe.spec.num_hashes) * pipe.spec.width


@functools.partial(jax.jit, static_argnames=("cfg",))
def fit_onehot(params, codes, labels, *, cfg, shuffle_key=None):
    """``fit_linear``'s loop on materialized local codes with the
    trainer's head, ``bag_logits_onehot``: the full-batch gradient at
    ``batch_size`` 0 or n, else the same per-epoch permutations."""
    tx = make_linear_tx(cfg)
    n, bs = codes.shape[0], cfg.batch_size

    def update(i, params, state, xb, yb):
        grads = jax.grad(_loss_fn)(params, xb, yb, cfg, bag_logits_onehot)
        updates, state = tx.update(grads, state, params, i)
        return optim.apply_updates(params, updates), state

    if bs in (0, n):
        return jax.lax.fori_loop(
            0, cfg.steps, lambda i, c: update(i, *c, codes, labels),
            (params, tx.init(params)))[0]

    key = shuffle_key if shuffle_key is not None else jax.random.PRNGKey(0)

    def step(i, carry):
        params, state, perm = carry
        pos = i % (n // bs)
        perm = jax.lax.cond(
            pos == 0,
            lambda: jax.random.permutation(
                jax.random.fold_in(key, i // (n // bs)), n),
            lambda: perm)
        idx = jax.lax.dynamic_slice_in_dim(perm, pos * bs, bs)
        return (*update(i, params, state, jnp.take(codes, idx, axis=0),
                        jnp.take(labels, idx, axis=0)), perm)

    return jax.lax.fori_loop(0, cfg.steps, step,
                             (params, tx.init(params),
                              jnp.arange(n, dtype=jnp.int32)))[0]


class TestStreamedParity:
    def test_batch_size_n_bit_identical_to_fullbatch(self, problem):
        # full batch on precomputed features with the trainer's head (the
        # one-hot contraction on local codes)
        pipe, xtr, ytr, _, _ = problem
        n = xtr.shape[0]
        p0 = init_bag(jax.random.PRNGKey(0), pipe.num_features, 3)
        feats = pipe.features(xtr)
        codes = local_codes(pipe, feats)
        cfg0 = TrainCfg(n_classes=3, steps=40, lr=0.05, l2=1e-5)
        cfgn = TrainCfg(n_classes=3, steps=40, lr=0.05, l2=1e-5,
                        batch_size=n)
        p_fb = fit_onehot(p0, codes, ytr, cfg=cfg0)
        p_st = fit_linear_streamed(p0, pipe, xtr, ytr, cfg=cfgn)
        np.testing.assert_array_equal(np.asarray(p_fb.w), np.asarray(p_st.w))
        np.testing.assert_array_equal(np.asarray(p_fb.b), np.asarray(p_st.b))
        # and the batch_size=n minibatch route is the same, with either
        # head
        p_mn = fit_onehot(p0, codes, ytr, cfg=cfgn)
        np.testing.assert_array_equal(np.asarray(p_fb.w), np.asarray(p_mn.w))
        p_fg = fit_linear(p0, feats, ytr, cfg=cfg0, kind="bag")
        p_mg = fit_linear(p0, feats, ytr, cfg=cfgn, kind="bag")
        np.testing.assert_array_equal(np.asarray(p_fg.w), np.asarray(p_mg.w))

    def test_minibatch_accuracy_parity(self, problem):
        pipe, xtr, ytr, xte, yte = problem
        p0 = init_bag(jax.random.PRNGKey(0), pipe.num_features, 3)
        feats_tr = pipe.features(xtr)
        feats_te = pipe.features(xte)
        cfg_fb = TrainCfg(n_classes=3, steps=200, lr=0.05, l2=1e-5)
        cfg_st = TrainCfg(n_classes=3, steps=200, lr=0.05, l2=1e-5,
                          batch_size=32)
        p_fb = fit_linear(p0, feats_tr, ytr, cfg=cfg_fb, kind="bag")
        p_st = fit_linear_streamed(p0, pipe, xtr, ytr, cfg=cfg_st)
        acc_fb = linear_accuracy(p_fb, feats_te, yte, kind="bag")
        acc_st = streamed_accuracy(p_st, pipe, xte, yte)
        assert abs(acc_fb - acc_st) <= 0.05
        assert acc_st > 0.8   # and it actually learned

    def test_fit_linear_batch_size_actually_routes(self, problem):
        # a true minibatch run must take the shuffled-gather path, i.e.
        # produce different (still-working) params than full batch
        pipe, xtr, ytr, _, _ = problem
        p0 = init_bag(jax.random.PRNGKey(0), pipe.num_features, 3)
        feats = pipe.features(xtr)
        cfg_fb = TrainCfg(n_classes=3, steps=50, lr=0.05, l2=1e-5)
        cfg_mb = TrainCfg(n_classes=3, steps=50, lr=0.05, l2=1e-5,
                          batch_size=32)
        p_fb = fit_linear(p0, feats, ytr, cfg=cfg_fb, kind="bag")
        p_mb = fit_linear(p0, feats, ytr, cfg=cfg_mb, kind="bag")
        assert not np.array_equal(np.asarray(p_fb.w), np.asarray(p_mb.w))

    def test_streamed_matches_fit_linear_minibatch_updates(self, problem):
        # same cfg + same shuffle key -> the streamed trainer and the
        # materialized minibatch path walk the same batch sequence
        pipe, xtr, ytr, _, _ = problem
        p0 = init_bag(jax.random.PRNGKey(0), pipe.num_features, 3)
        codes = local_codes(pipe, pipe.features(xtr))
        cfg = TrainCfg(n_classes=3, steps=30, lr=0.05, l2=1e-5,
                       batch_size=32)
        key = jax.random.PRNGKey(5)
        p_mat = fit_onehot(p0, codes, ytr, cfg=cfg, shuffle_key=key)
        p_str = fit_linear_streamed(p0, pipe, xtr, ytr, cfg=cfg,
                                    shuffle_key=key)
        np.testing.assert_allclose(np.asarray(p_mat.w), np.asarray(p_str.w),
                                   rtol=0, atol=0)


    def test_host_numpy_dataset_matches_device(self, problem):
        # numpy datasets gather per batch on the HOST (only the batch
        # crosses to the device) yet walk the same batch sequence
        pipe, xtr, ytr, _, _ = problem
        p0 = init_bag(jax.random.PRNGKey(0), pipe.num_features, 3)
        cfg = TrainCfg(n_classes=3, steps=20, lr=0.05, l2=1e-5,
                       batch_size=32)
        key = jax.random.PRNGKey(2)
        p_dev = fit_linear_streamed(p0, pipe, xtr, ytr, cfg=cfg,
                                    shuffle_key=key)
        p_host = fit_linear_streamed(p0, pipe, np.asarray(xtr),
                                     np.asarray(ytr), cfg=cfg,
                                     shuffle_key=key)
        np.testing.assert_array_equal(np.asarray(p_dev.w),
                                      np.asarray(p_host.w))
        acc_h = streamed_accuracy(p_host, pipe, np.asarray(xtr),
                                  np.asarray(ytr))
        assert acc_h == streamed_accuracy(p_dev, pipe, xtr, ytr)


class TestValidation:
    def test_negative_batch_size_rejected(self, problem):
        pipe, xtr, ytr, _, _ = problem
        feats = pipe.features(xtr)
        p0 = init_bag(jax.random.PRNGKey(0), pipe.num_features, 3)
        with pytest.raises(ValueError, match="batch_size"):
            fit_linear(p0, feats, ytr,
                       cfg=TrainCfg(n_classes=3, batch_size=-1), kind="bag")

    def test_oversized_batch_rejected(self, problem):
        pipe, xtr, ytr, _, _ = problem
        n = xtr.shape[0]
        feats = pipe.features(xtr)
        p0 = init_bag(jax.random.PRNGKey(0), pipe.num_features, 3)
        with pytest.raises(ValueError, match="exceeds"):
            fit_linear(p0, feats, ytr,
                       cfg=TrainCfg(n_classes=3, batch_size=n + 1),
                       kind="bag")
        with pytest.raises(ValueError, match="exceeds"):
            fit_linear_streamed(p0, pipe, xtr, ytr,
                                cfg=TrainCfg(n_classes=3, batch_size=n + 1))

    def test_streamed_requires_positive_batch(self, problem):
        pipe, xtr, ytr, _, _ = problem
        p0 = init_bag(jax.random.PRNGKey(0), pipe.num_features, 3)
        with pytest.raises(ValueError, match="batch_size"):
            fit_linear_streamed(p0, pipe, xtr, ytr,
                                cfg=TrainCfg(n_classes=3, batch_size=0))

    def test_feature_table_mismatch_rejected(self, problem):
        pipe, xtr, ytr, _, _ = problem
        bad = init_bag(jax.random.PRNGKey(0), pipe.num_features + 16, 3)
        with pytest.raises(ValueError, match="mismatch"):
            validate_bag_features(bad, pipe.num_features)
        with pytest.raises(ValueError, match="mismatch"):
            fit_linear_streamed(bad, pipe, xtr, ytr,
                                cfg=TrainCfg(n_classes=3, batch_size=32))
        with pytest.raises(ValueError, match="mismatch"):
            streamed_accuracy(bad, pipe, xtr, ytr)

    def test_non_bag_param_shapes_rejected(self):
        hashed = init_hashed(jax.random.PRNGKey(0), k=4, width=8,
                             n_classes=2)
        idx = jnp.zeros((3, 4), jnp.int32)
        with pytest.raises(ValueError, match="flat"):
            bag_logits(hashed, idx)
        bag = init_bag(jax.random.PRNGKey(0), 32, 2)
        with pytest.raises(ValueError, match="\\(n, k\\)"):
            bag_logits(bag, idx[0])

    def test_microbatch_divisibility(self, problem):
        pipe, xtr, ytr, _, _ = problem
        p0 = init_bag(jax.random.PRNGKey(0), pipe.num_features, 3)
        with pytest.raises(ValueError, match="microbatch"):
            fit_linear_streamed(p0, pipe, xtr, ytr,
                                cfg=TrainCfg(n_classes=3, batch_size=30),
                                n_microbatches=4)


class TestIndexGuards:
    """The explicit OOB/sentinel policy of the embedding-bag gathers."""

    def _bag(self, F=24, C=3):
        w = jax.random.normal(jax.random.PRNGKey(0), (F, C))
        return LinearParams(w, jnp.zeros((C,)))

    def test_bag_oob_clamps_not_wraps(self):
        p = self._bag(F=24)
        hi = jnp.full((2, 5), 23, jnp.int32)
        oob = jnp.full((2, 5), 24 + 100, jnp.int32)   # way past F
        np.testing.assert_array_equal(np.asarray(bag_logits(p, oob)),
                                      np.asarray(bag_logits(p, hi)))

    def test_bag_negative_clamps_to_zero(self):
        p = self._bag()
        lo = jnp.zeros((2, 5), jnp.int32)
        neg = jnp.full((2, 5), -3, jnp.int32)
        np.testing.assert_array_equal(np.asarray(bag_logits(p, neg)),
                                      np.asarray(bag_logits(p, lo)))

    def test_hashed_sentinel_aliases_bucket0(self):
        # DOCUMENTED policy: -1 sentinel codes (all-zero rows) hit bucket
        # 0 of their hash — the same convention the fused pipeline bakes
        # into its indices, so both learner surfaces agree
        k, width, C = 4, 8, 3
        w = jax.random.normal(jax.random.PRNGKey(1), (k, width, C))
        p = LinearParams(w, jnp.zeros((C,)))
        sent = jnp.full((2, k), -1, jnp.int32)
        zero = jnp.zeros((2, k), jnp.int32)
        np.testing.assert_array_equal(np.asarray(hashed_logits(p, sent)),
                                      np.asarray(hashed_logits(p, zero)))

    def test_hashed_oob_clamps_to_top_bucket(self):
        k, width, C = 4, 8, 3
        w = jax.random.normal(jax.random.PRNGKey(2), (k, width, C))
        p = LinearParams(w, jnp.zeros((C,)))
        top = jnp.full((2, k), width - 1, jnp.int32)
        oob = jnp.full((2, k), width + 7, jnp.int32)
        np.testing.assert_array_equal(np.asarray(hashed_logits(p, oob)),
                                      np.asarray(hashed_logits(p, top)))

    def test_pipeline_indices_inside_table(self, problem):
        pipe, xtr, _, _, _ = problem
        x = xtr.at[3].set(0.0)                     # sentinel row too
        idx = np.asarray(pipe.features(x))
        assert idx.min() >= 0 and idx.max() < pipe.num_features


class TestRaggedStreaming:
    def _pipe(self, row_chunk, d=18, k=10):
        spec = FeatureSpec(num_hashes=k, b_i=3)
        return FeaturePipeline.create(jax.random.PRNGKey(3), d, spec,
                                      row_chunk=row_chunk)

    def test_single_compile_for_ragged_tail(self):
        pipe = self._pipe(row_chunk=8)
        x = rand_nonneg(jax.random.PRNGKey(4), (27, 18))   # 8+8+8+3 rows
        # the donating chunk fn compiles EXACTLY once: the ragged tail is
        # padded to row_chunk, not traced as a second shape
        with compile_guard() as g:
            g.watch(pipe._chunk_fn(), label="chunk_fn")
            feats = pipe.features(x)
        assert feats.shape == (27, 10)

    def test_padded_tail_matches_unchunked(self):
        pipe = self._pipe(row_chunk=8)
        whole = self._pipe(row_chunk=1 << 20)
        whole.params = pipe.params
        x = rand_nonneg(jax.random.PRNGKey(5), (27, 18))
        x = x.at[25].set(0.0)                      # zero row in the tail
        np.testing.assert_array_equal(np.asarray(pipe.features(x)),
                                      np.asarray(whole.features(x)))

    def test_prefix_spec_launches_cached_slice(self):
        # a k-prefix pipeline (spec narrower than params) caches its
        # sliced launch state instead of re-slicing per launch_chunk —
        # and stays bit-exact against the staged oracle
        from repro.core.cws import make_cws_params
        params = make_cws_params(jax.random.PRNGKey(11), 18, 16)
        pipe = FeaturePipeline(params, FeatureSpec(num_hashes=10, b_i=3))
        x = rand_nonneg(jax.random.PRNGKey(12), (9, 18))
        got = pipe.launch_chunk(x)
        assert pipe._state() is pipe._state()
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(pipe.staged_reference(x)))

    def test_feature_chunks_slices(self):
        pipe = self._pipe(row_chunk=8)
        x = rand_nonneg(jax.random.PRNGKey(6), (19, 18))
        full = pipe.features(x)
        spans = []
        for lo, hi, fb in pipe.feature_chunks(x):
            spans.append((lo, hi))
            np.testing.assert_array_equal(np.asarray(fb),
                                          np.asarray(full[lo:hi]))
        assert spans == [(0, 8), (8, 16), (16, 19)]


class TestNeverMaterialize:
    def test_training_launches_only_batch_sized_chunks(self, problem,
                                                       monkeypatch):
        pipe, xtr, ytr, _, _ = problem
        n, bs = xtr.shape[0], 16
        launches = []
        orig = FeaturePipeline.launch_chunk

        def spy(self, xc):
            launches.append(int(xc.shape[0]))
            return orig(self, xc)

        monkeypatch.setattr(FeaturePipeline, "launch_chunk", spy)
        p0 = init_bag(jax.random.PRNGKey(0), pipe.num_features, 3)
        cfg = TrainCfg(n_classes=3, steps=12, lr=0.05, l2=1e-5,
                       batch_size=bs)
        fit_linear_streamed(p0, pipe, xtr, ytr, cfg=cfg)
        assert launches, "streamed fit must drive launch_chunk"
        assert max(launches) == bs < n   # the (n, k) matrix never exists

    def test_streamed_eval_chunks_by_row_chunk(self, problem):
        pipe, xtr, ytr, _, _ = problem
        small = FeaturePipeline(pipe.params, pipe.spec, row_chunk=16)
        p0 = init_bag(jax.random.PRNGKey(0), pipe.num_features, 3)
        seen = []
        for lo, hi, fb in small.feature_chunks(xtr):
            seen.append(int(fb.shape[0]))
        assert max(seen) == 16 < xtr.shape[0]
        # and the convenience evaluator agrees with the materialized one
        acc_s = streamed_accuracy(p0, small, xtr, ytr)
        acc_m = linear_accuracy(p0, pipe.features(xtr), ytr, kind="bag")
        assert acc_s == pytest.approx(acc_m)


class TestEdgeBatches:
    def test_empty_eval(self, problem):
        pipe, xtr, ytr, _, _ = problem
        p0 = init_bag(jax.random.PRNGKey(0), pipe.num_features, 3)
        assert streamed_accuracy(p0, pipe, xtr[:0], ytr[:0]) == 0.0
        assert list(pipe.feature_chunks(xtr[:0])) == []

    def test_one_row_batches(self, problem):
        pipe, _, _, _, _ = problem
        x = rand_nonneg(jax.random.PRNGKey(8), (5, 32))
        y = jnp.array([0, 1, 2, 1, 0], jnp.int32)
        p0 = init_bag(jax.random.PRNGKey(0), pipe.num_features, 3)
        cfg = TrainCfg(n_classes=3, steps=11, lr=0.05, l2=1e-5,
                       batch_size=1)
        p = fit_linear_streamed(p0, pipe, x, y, cfg=cfg)
        assert np.isfinite(np.asarray(p.w)).all()

    def test_one_row_dataset(self, problem):
        pipe, _, _, _, _ = problem
        x = rand_nonneg(jax.random.PRNGKey(9), (1, 32))
        y = jnp.array([1], jnp.int32)
        p0 = init_bag(jax.random.PRNGKey(0), pipe.num_features, 3)
        cfg = TrainCfg(n_classes=3, steps=5, lr=0.05, l2=1e-5,
                       batch_size=1)
        p = fit_linear_streamed(p0, pipe, x, y, cfg=cfg)
        assert np.isfinite(np.asarray(p.w)).all()
