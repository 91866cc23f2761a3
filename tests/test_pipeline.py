"""Fused featurization (ops.cws, emit="index") vs the staged reference
composition, plus registry dispatch and FeaturePipeline
streaming/sharding semantics.

The staged composition ``feature_indices(encode(cws_hash_reference(...)))``
survives in tests as the oracle; the fused kernel must be BIT-exact
against it across the full (b_i, b_t) grid, non-divisible shapes, and
all-zero rows (sentinel -> bucket 0 of its hash).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cws import make_cws_params, cws_hash_reference
from repro.core.hashing import encode, feature_indices
from repro.kernels import ops, registry
from repro.launch.mesh import make_local_mesh
from repro.pipeline import FeaturePipeline, FeatureSpec


def rand_nonneg(key, shape, sparsity=0.4):
    k1, k2 = jax.random.split(key)
    mag = jnp.exp(jax.random.normal(k1, shape))
    mask = jax.random.bernoulli(k2, 1 - sparsity, shape)
    return mag * mask


def staged_oracle(x, params, b_i, b_t):
    i_star, t_star = cws_hash_reference(x, params)
    codes = encode(i_star, t_star, b_i=b_i, b_t=b_t)
    return feature_indices(codes, b_i=b_i, b_t=b_t)


BI_GRID = (0, 1, 2, 4, 8)
BT_GRID = (0, 1, 2)


class TestFusedEncodeBitExact:
    @pytest.mark.parametrize("b_i", BI_GRID)
    @pytest.mark.parametrize("b_t", BT_GRID)
    def test_matches_staged_oracle(self, b_i, b_t):
        # non-divisible (n, D, k) vs (bn, bk, bd) everywhere
        n, d, k = 13, 22, 11
        x = rand_nonneg(jax.random.PRNGKey(b_i * 10 + b_t), (n, d))
        x = x.at[4].set(0.0)                        # an all-zero row too
        p = make_cws_params(jax.random.PRNGKey(1), d, k)
        want = staged_oracle(x, p, b_i, b_t)
        got = ops.cws(x, p, emit="index", b_i=b_i, b_t=b_t, bn=4, bk=4,
                      bd=8, interpret=True)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    @pytest.mark.parametrize("n,d,k,bn,bk,bd", [
        (4, 8, 4, 4, 4, 8),
        (33, 50, 21, 8, 8, 16),     # non-divisible everywhere
        (7, 96, 33, 8, 16, 32),
        # the last D step's loop: a tail of 1, of bd - 1, bd dividing D
        # (no tail branch), and D under bd (bd clamps to D)
        (9, 33, 20, 8, 8, 16),
        (9, 47, 20, 8, 8, 16),
        (9, 48, 20, 8, 8, 16),
        (9, 12, 20, 8, 8, 32),
    ])
    def test_shapes_sweep(self, n, d, k, bn, bk, bd):
        x = rand_nonneg(jax.random.PRNGKey(n * 100 + d), (n, d))
        # row 0 all zero (the sentinel), row 1 nonzero only in the last
        # D block
        last = -(-d // min(bd, d)) * min(bd, d) - min(bd, d)
        x = x.at[0].set(0.0).at[1, :last].set(0.0).at[1, last:].add(0.5)
        p = make_cws_params(jax.random.PRNGKey(d + k), d, k)
        want = staged_oracle(x, p, b_i=4, b_t=1)
        got = ops.cws(x, p, emit="index", b_i=4, b_t=1, bn=bn, bk=bk,
                      bd=bd, interpret=True)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    def test_all_zero_rows_bucket0(self):
        # sentinel i* = -1 must land in bucket 0 OF ITS HASH: index j*width
        n, d, k, b_i = 6, 16, 9, 3
        x = jnp.zeros((n, d))
        p = make_cws_params(jax.random.PRNGKey(2), d, k)
        got = np.asarray(ops.cws(x, p, emit="index", b_i=b_i, bn=4, bk=4,
                                 bd=8, interpret=True))
        want = np.arange(k, dtype=np.int32)[None, :] * (1 << b_i)
        np.testing.assert_array_equal(got, np.broadcast_to(want, (n, k)))

    def test_reference_impl_matches_oracle(self):
        x = rand_nonneg(jax.random.PRNGKey(5), (19, 31))
        p = make_cws_params(jax.random.PRNGKey(6), 31, 14)
        want = staged_oracle(x, p, b_i=8, b_t=2)
        got = ops.cws(x, p, emit="index", b_i=8, b_t=2, impl="reference")
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


class TestFeaturePipeline:
    def _pipe_and_x(self, b_i=4, b_t=0, **kw):
        d, k = 26, 12
        x = rand_nonneg(jax.random.PRNGKey(0), (23, d))
        x = x.at[7].set(0.0)
        spec = FeatureSpec(num_hashes=k, b_i=b_i, b_t=b_t)
        pipe = FeaturePipeline.create(jax.random.PRNGKey(1), d, spec, **kw)
        return pipe, x

    def test_features_match_staged_reference(self):
        pipe, x = self._pipe_and_x(b_i=4, b_t=1)
        np.testing.assert_array_equal(np.asarray(pipe.features(x)),
                                      np.asarray(pipe.staged_reference(x)))

    def test_streaming_chunks_match_single_launch(self):
        pipe, x = self._pipe_and_x(row_chunk=7)   # 23 rows -> 4 chunks
        whole, _ = self._pipe_and_x()
        whole.params = pipe.params                # same buffers
        np.testing.assert_array_equal(np.asarray(pipe.features(x)),
                                      np.asarray(whole.features(x)))

    def test_sharded_matches_unsharded(self):
        pipe, x = self._pipe_and_x()
        mesh = make_local_mesh()
        np.testing.assert_array_equal(np.asarray(pipe.features(x, mesh=mesh)),
                                      np.asarray(pipe.features(x)))

    def test_pallas_interpret_impl_matches_reference(self):
        spec = FeatureSpec(num_hashes=8, b_i=2, b_t=1)
        params = make_cws_params(jax.random.PRNGKey(3), 12, 8)
        x = rand_nonneg(jax.random.PRNGKey(4), (9, 12))
        a = FeaturePipeline(params, spec, impl="pallas-interpret",
                            blocks=(4, 4, 4)).features(x)
        b = FeaturePipeline(params, spec, impl="reference").features(x)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_codes_and_range(self):
        pipe, x = self._pipe_and_x(b_i=3)
        codes = np.asarray(pipe.codes(x))
        assert codes.min() >= -1 and codes.max() < pipe.spec.width
        idx = np.asarray(pipe.features(x))
        assert idx.min() >= 0 and idx.max() < pipe.num_features

    def test_empty_batch(self):
        pipe, x = self._pipe_and_x()
        assert pipe.features(x[:0]).shape == (0, pipe.spec.num_hashes)
        assert pipe.codes(x[:0]).shape == (0, pipe.spec.num_hashes)

    def test_spec_wider_than_params_rejected(self):
        params = make_cws_params(jax.random.PRNGKey(0), 8, 4)
        with pytest.raises(ValueError):
            FeaturePipeline(params, FeatureSpec(num_hashes=8, b_i=1))

    def test_bi0_features_rejected_codes_allowed(self):
        # b_i = 0 keeps i* in full -> flat indices would exceed
        # num_features and silently clip in the bag gather; the
        # embedding-bag surface must reject it, the estimator surface not
        params = make_cws_params(jax.random.PRNGKey(0), 8, 4)
        pipe = FeaturePipeline(params, FeatureSpec(num_hashes=4, b_i=0))
        x = rand_nonneg(jax.random.PRNGKey(1), (5, 8))
        with pytest.raises(ValueError, match="b_i"):
            pipe.features(x)
        assert pipe.codes(x).shape == (5, 4)


class TestRegistry:
    def test_ops_registered(self):
        for op in ("cws", "minmax_gram", "min_sum"):
            names = registry.impl_names(op)
            assert "pallas-interpret" in names and "reference" in names
            assert "pallas" in names

    def test_auto_dispatch_by_capability(self):
        impl = registry.resolve("cws")
        if registry.on_tpu():
            assert impl.name == "pallas"
        else:
            assert impl.name == "reference"

    def test_pallas_requires_tpu_offline(self):
        if registry.on_tpu():
            pytest.skip("pallas is available on TPU")
        with pytest.raises(RuntimeError):
            registry.resolve("cws", "pallas")

    def test_unknown_impl_rejected(self):
        with pytest.raises(KeyError):
            registry.resolve("cws", "no-such-impl")

    def test_cws_rejects_what_it_cannot_launch(self):
        x = jnp.ones((4, 8))
        p = make_cws_params(jax.random.PRNGKey(0), 8, 4)
        key = jax.random.PRNGKey(1)
        with pytest.raises(ValueError, match="num_hashes"):
            ops.cws(x, key, emit="index", b_i=2)      # regen needs k
        with pytest.raises(ValueError, match="carry 4"):
            ops.cws(x, p, emit="index", num_hashes=3, b_i=2)
        with pytest.raises(ValueError, match="no CWS variant"):
            ops.cws(x, p, emit="codes", b_i=2)
        with pytest.raises(ValueError, match="no CWS variant"):
            registry.cws_family("sparse", "index")

    def test_choose_blocks_bounds(self):
        for (n, d, k) in [(4, 8, 4), (33, 50, 21), (1024, 512, 512),
                          (8192, 65536, 1024), (100000, 4096, 2048)]:
            bn, bk, bd = registry.choose_blocks(n, d, k)
            assert 1 <= bn <= n and 1 <= bk <= k and 1 <= bd <= d
            assert registry.vmem_bytes(bn, bk, bd, op="cws") <= 16 * 2 ** 20

    def test_table_override_is_per_op(self):
        shape = (2 ** 14, 2 ** 14, 2 ** 14)
        key = ("cws",) + shape
        registry.update_block_table({key: (64, 32, 256)})
        try:
            assert registry.choose_blocks(*shape) == (64, 32, 256)
            # a CWS-tuned entry must NOT leak into the gram family
            assert registry.choose_blocks(*shape, op="gram") != (64, 32, 256)
        finally:
            registry.BLOCK_TABLE.pop(key, None)
