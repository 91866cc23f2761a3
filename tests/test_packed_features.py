"""Bit-packed b-bit feature encoding (ISSUE 6).

What is pinned down:
  * pack/unpack are exact inverses for every legal b x b_t split,
    including ragged k*b % 32 != 0 and sentinel (all-zero) rows;
  * the packed kernel impls (reference + interpreter) agree bit-for-bit
    with pack_codes over the unpacked oracle, stored-param and regen;
  * FeaturePipeline(packed=True) preserves the streaming invariants:
    streamed == full-batch bit-identical, exactly one compiled chunk
    shape, empty-batch shape/dtype, and the construction-time b_i >= 1
    and b in {1,2,4,8} guards;
  * bag_logits_packed == bag_logits on equivalent features, and the
    whole streamed training loop is bit-identical packed vs unpacked at
    the same (b_i, b_t);
  * 8-device parity under the forced-host-device mesh (CI sharded-smoke).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import compile_guard
from repro.core import hashing
from repro.core.cws import make_cws_params
from repro.core.linear_model import (TrainCfg, bag_logits, bag_logits_packed,
                                     init_bag, init_bag_packed,
                                     validate_bag_features)
from repro.kernels import ops
from repro.launch.mesh import data_axis_size, make_local_mesh
from repro.pipeline import FeaturePipeline, FeatureSpec
from repro.training import fit_linear_streamed, streamed_accuracy

NDEV = len(jax.devices())
multi_device = pytest.mark.skipif(
    NDEV < 8, reason="needs XLA_FLAGS=--xla_force_host_platform_"
                     "device_count=8 (CI sharded-smoke job)")

# every legal b with every b_t split that keeps b_i >= 1
B_SPLITS = [(b - b_t, b_t) for b in hashing.PACKED_BITS
            for b_t in (0, 1, 2) if b - b_t >= 1]


def rand_nonneg(key, shape, sparsity=0.4):
    k1, k2 = jax.random.split(key)
    mag = jnp.exp(jax.random.normal(k1, shape))
    mask = jax.random.bernoulli(k2, 1 - sparsity, shape)
    return mag * mask


class TestPackUnpack:
    @pytest.mark.parametrize("b_i,b_t", B_SPLITS)
    def test_roundtrip_exact(self, b_i, b_t):
        b = b_i + b_t
        # k chosen so k*b % 32 != 0 for b in {1,2,4} (ragged last word)
        k = 37
        codes = jax.random.randint(jax.random.PRNGKey(b), (11, k), 0, 1 << b)
        packed = hashing.pack_codes(codes, b=b)
        assert packed.dtype == jnp.uint32
        assert packed.shape == (11, hashing.packed_width(k, b))
        assert (hashing.unpack_codes(packed, k, b=b) == codes).all()

    def test_sentinels_pack_as_zero(self):
        codes = jnp.array([[-1, 3, -1, 2]], jnp.int32)
        packed = hashing.pack_codes(codes, b=2)
        dec = hashing.unpack_codes(packed, 4, b=2)
        assert (dec == jnp.array([[0, 3, 0, 2]])).all()

    def test_trailing_pad_bits_zero(self):
        # 3 codes of 8 bits -> one word, top byte must be zero
        packed = hashing.pack_codes(jnp.full((1, 3), 255, jnp.int32), b=8)
        assert int(packed[0, 0]) == 0x00FFFFFF

    @pytest.mark.parametrize("b", (0, 3, 5, 16, 32))
    def test_illegal_b_raises(self, b):
        with pytest.raises(ValueError, match="packed encoding needs"):
            hashing.pack_codes(jnp.zeros((2, 4), jnp.int32), b=b)

    def test_width_mismatch_raises(self):
        packed = hashing.pack_codes(jnp.zeros((2, 8), jnp.int32), b=4)
        with pytest.raises(ValueError, match="packed width mismatch"):
            hashing.unpack_codes(packed, 16, b=4)


class TestPackedKernels:
    @pytest.mark.parametrize("b_i,b_t", [(1, 0), (2, 0), (1, 1), (2, 2),
                                         (4, 0), (8, 0), (6, 2)])
    def test_matches_unpacked_oracle(self, b_i, b_t):
        b = b_i + b_t
        n, d, k = 17, 33, 50      # ragged vs every block size
        x = rand_nonneg(jax.random.PRNGKey(0), (n, d))
        params = make_cws_params(jax.random.PRNGKey(1), d, k)
        idx = ops.cws(x, params, emit="index", b_i=b_i, b_t=b_t,
                      impl="reference")
        codes = idx - jnp.arange(k, dtype=jnp.int32) * (1 << b)
        want = hashing.pack_codes(codes, b=b)
        for impl in ("reference", "pallas-interpret"):
            got = ops.cws(x, params, emit="packed", b_i=b_i, b_t=b_t,
                          impl=impl)
            assert got.dtype == jnp.uint32
            assert (got == want).all(), impl
            assert (hashing.unpack_codes(got, k, b=b) == codes).all(), impl

    @pytest.mark.parametrize("b_i,b_t", [(1, 0), (2, 2), (8, 0)])
    def test_rng_matches_unpacked_oracle(self, b_i, b_t):
        b = b_i + b_t
        n, d, k = 13, 21, 40
        x = rand_nonneg(jax.random.PRNGKey(2), (n, d))
        key = jax.random.PRNGKey(5)
        idx = ops.cws(x, key, emit="index", num_hashes=k, b_i=b_i, b_t=b_t,
                      impl="reference")
        want = hashing.pack_codes(
            idx - jnp.arange(k, dtype=jnp.int32) * (1 << b), b=b)
        for impl in ("reference", "pallas-interpret"):
            got = ops.cws(x, key, emit="packed", num_hashes=k, b_i=b_i,
                          b_t=b_t, impl=impl)
            assert (got == want).all(), impl

    # the last D step's loop: a tail of 1, of bd - 1, bd dividing D (no
    # tail branch), and D under bd (bd clamps to D)
    @pytest.mark.parametrize("d,bd", [(33, 16), (47, 16), (48, 16),
                                      (12, 32)])
    @pytest.mark.parametrize("regen", [False, True])
    def test_tail_shapes_match_unpacked_oracle(self, d, bd, regen):
        n, k, b_i = 9, 20, 8
        x = rand_nonneg(jax.random.PRNGKey(d), (n, d))
        # row 0 all zero (the sentinel), row 1 nonzero only in the last
        # D block
        last = -(-d // min(bd, d)) * min(bd, d) - min(bd, d)
        x = x.at[0].set(0.0).at[1, :last].set(0.0).at[1, last:].add(0.5)
        blocks = dict(b_i=b_i, bn=8, bk=8, bd=bd)
        if regen:
            state, blocks["num_hashes"] = jax.random.PRNGKey(5), k
        else:
            state = make_cws_params(jax.random.PRNGKey(1), d, k)
        idx = ops.cws(x, state, emit="index", impl="reference", **blocks)
        got = ops.cws(x, state, emit="packed", impl="pallas-interpret",
                      **blocks)
        codes = idx - jnp.arange(k, dtype=jnp.int32) * (1 << b_i)
        want = hashing.pack_codes(codes, b=b_i)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
        assert (np.asarray(codes[0]) == 0).all()

    def test_all_zero_rows_pack_to_bucket_zero(self):
        n, d, k = 9, 16, 24
        x = rand_nonneg(jax.random.PRNGKey(3), (n, d)).at[4].set(0.0)
        params = make_cws_params(jax.random.PRNGKey(1), d, k)
        got = ops.cws(x, params, emit="packed", b_i=2, b_t=2,
                      impl="pallas-interpret")
        assert (hashing.unpack_codes(got, k, b=4)[4] == 0).all()


@pytest.fixture(scope="module")
def packed_pipes():
    d, k = 40, 50
    spec_p = FeatureSpec(num_hashes=k, b_i=3, b_t=1, packed=True)
    spec_u = FeatureSpec(num_hashes=k, b_i=3, b_t=1)
    key = jax.random.PRNGKey(11)
    return (FeaturePipeline.create(key, d, spec_p, row_chunk=64),
            FeaturePipeline.create(key, d, spec_u, row_chunk=64), d, k)


class TestPackedPipeline:
    def test_decodes_to_unpacked_indices(self, packed_pipes):
        pp, pu, d, k = packed_pipes
        x = rand_nonneg(jax.random.PRNGKey(0), (30, d))
        pf = pp.features(x)
        assert pf.dtype == jnp.uint32
        assert pf.shape == (30, pp.spec.packed_words)
        assert (pp.unpack_features(pf) == pu.features(x)).all()
        assert (pp.staged_reference(x) == pf).all()

    def test_streamed_matches_fullbatch_bit_identical(self, packed_pipes):
        pp, _, d, _ = packed_pipes
        x = rand_nonneg(jax.random.PRNGKey(1), (200, d))   # > row_chunk
        streamed = pp.features(x)
        whole = FeaturePipeline(pp.params, pp.spec,
                                row_chunk=4096).features(x)
        assert (streamed == whole).all()

    def test_single_compiled_chunk_shape(self):
        # fresh pipe: the guard counts NEW cache entries, so watch a
        # cold chunk fn rather than the module-scoped, pre-warmed one
        d, k = 40, 50
        pp = FeaturePipeline.create(
            jax.random.PRNGKey(11), d,
            FeatureSpec(num_hashes=k, b_i=3, b_t=1, packed=True),
            row_chunk=64)
        x = rand_nonneg(jax.random.PRNGKey(2), (150, d))   # ragged tail
        with compile_guard() as g:
            g.watch(pp._chunk_fn(), label="packed chunk_fn")
            list(pp.feature_chunks(x))

    def test_empty_batch(self, packed_pipes):
        pp, _, d, _ = packed_pipes
        out = pp.features(jnp.zeros((0, d)))
        assert out.shape == (0, pp.spec.packed_words)
        assert out.dtype == jnp.uint32

    def test_regen_packed_matches_regen_unpacked(self):
        d, k = 24, 20
        key = jax.random.PRNGKey(4)
        pp = FeaturePipeline.create_regen(
            key, d, FeatureSpec(k, b_i=2, packed=True))
        pu = FeaturePipeline.create_regen(key, d, FeatureSpec(k, b_i=2))
        x = rand_nonneg(jax.random.PRNGKey(5), (15, d))
        assert (pp.unpack_features(pp.features(x)) == pu.features(x)).all()

    def test_packed_b_i0_raises_at_construction(self):
        with pytest.raises(ValueError, match="requires b_i >= 1"):
            FeaturePipeline.create(jax.random.PRNGKey(0), 16,
                                   FeatureSpec(8, b_i=0, packed=True))

    def test_packed_bad_b_raises_at_construction(self):
        with pytest.raises(ValueError, match="packed encoding needs"):
            FeaturePipeline.create(jax.random.PRNGKey(0), 16,
                                   FeatureSpec(8, b_i=2, b_t=1, packed=True))

    def test_unpack_features_needs_packed_spec(self, packed_pipes):
        _, pu, d, _ = packed_pipes
        with pytest.raises(ValueError, match="packed=True"):
            pu.unpack_features(jnp.zeros((2, 7), jnp.uint32))


class TestPackedLogitsAndTraining:
    @pytest.fixture(scope="class")
    def problem(self):
        d, k, n = 40, 32, 192
        spec_p = FeatureSpec(num_hashes=k, b_i=3, b_t=1, packed=True)
        spec_u = FeatureSpec(num_hashes=k, b_i=3, b_t=1)
        key = jax.random.PRNGKey(21)
        pp = FeaturePipeline.create(key, d, spec_p, row_chunk=64)
        pu = FeaturePipeline.create(key, d, spec_u, row_chunk=64)
        x = rand_nonneg(jax.random.PRNGKey(6), (n, d))
        y = jax.random.randint(jax.random.PRNGKey(7), (n,), 0, 3)
        return pp, pu, x, y, k

    def test_bag_logits_packed_matches_bag_logits(self, problem):
        pp, pu, x, _, k = problem
        w = jax.random.normal(jax.random.PRNGKey(8), (k * 16, 3))
        params = init_bag(jax.random.PRNGKey(0), k * 16, 3)._replace(w=w)
        lp = bag_logits_packed(params, pp.features(x), num_hashes=k, b=4)
        lu = bag_logits(params, pu.features(x))
        assert (lp == lu).all()

    def test_table_size_mismatch_raises(self, problem):
        pp, _, x, _, k = problem
        bad = init_bag(jax.random.PRNGKey(0), 100, 3)
        with pytest.raises(ValueError, match="feature-table mismatch"):
            bag_logits_packed(bad, pp.features(x), num_hashes=k, b=4)
        with pytest.raises(ValueError, match="feature-table mismatch"):
            validate_bag_features(bad, pp.num_features, spec=pp.spec)

    def test_packed_width_mismatch_raises(self, problem):
        pp, _, x, _, k = problem
        params = init_bag_packed(jax.random.PRNGKey(0), k, 4, 3)
        with pytest.raises(ValueError, match="packed width mismatch"):
            bag_logits_packed(params, pp.features(x)[:, :-1],
                              num_hashes=k, b=4)

    def test_streamed_training_bit_identical(self, problem):
        pp, pu, x, y, k = problem
        cfg = TrainCfg(n_classes=3, steps=25, batch_size=64)
        tp = fit_linear_streamed(init_bag_packed(jax.random.PRNGKey(0),
                                                 k, 4, 3),
                                 pp, x, y, cfg=cfg)
        tu = fit_linear_streamed(init_bag(jax.random.PRNGKey(0),
                                          pu.num_features, 3),
                                 pu, x, y, cfg=cfg)
        assert (tp.w == tu.w).all() and (tp.b == tu.b).all()
        assert streamed_accuracy(tp, pp, x, y) == \
            streamed_accuracy(tu, pu, x, y)

    def test_fullbatch_path_bit_identical(self, problem):
        pp, pu, x, y, k = problem
        n = x.shape[0]
        cfg = TrainCfg(n_classes=3, steps=8, batch_size=n)
        tp = fit_linear_streamed(init_bag_packed(jax.random.PRNGKey(0),
                                                 k, 4, 3),
                                 pp, x, y, cfg=cfg)
        tu = fit_linear_streamed(init_bag(jax.random.PRNGKey(0),
                                          pu.num_features, 3),
                                 pu, x, y, cfg=cfg)
        assert (tp.w == tu.w).all()


class TestPackedSharded:
    @multi_device
    def test_sharded_features_parity(self):
        mesh = make_local_mesh()
        d, k = 24, 40
        pipe = FeaturePipeline.create(
            jax.random.PRNGKey(1), d,
            FeatureSpec(k, b_i=4, packed=True), row_chunk=32)
        x = rand_nonneg(jax.random.PRNGKey(2), (100, d))
        with compile_guard() as g:
            g.watch(pipe._sharded_chunk_fn(mesh), label="sharded chunk_fn")
            sharded = pipe.features(x, mesh=mesh)
        assert (sharded == pipe.features(x)).all()

    @multi_device
    def test_sharded_streamed_training_parity(self):
        mesh = make_local_mesh()
        ndev = data_axis_size(mesh)
        d, k, n = 24, 32, 160
        spec = FeatureSpec(k, b_i=4, packed=True)
        pipe = FeaturePipeline.create(jax.random.PRNGKey(3), d, spec,
                                      row_chunk=32)
        x = rand_nonneg(jax.random.PRNGKey(4), (n, d))
        y = jax.random.randint(jax.random.PRNGKey(5), (n,), 0, 3)
        cfg = TrainCfg(n_classes=3, steps=15, batch_size=8 * ndev)
        p0 = init_bag_packed(jax.random.PRNGKey(0), k, 4, 3)
        ps = fit_linear_streamed(p0, pipe, x, y, cfg=cfg, mesh=mesh,
                                 shuffle_key=jax.random.PRNGKey(9))
        pl = fit_linear_streamed(p0, pipe, x, y, cfg=cfg,
                                 shuffle_key=jax.random.PRNGKey(9))
        # same batch walk; only grad-summation order differs
        np.testing.assert_allclose(np.asarray(ps.w), np.asarray(pl.w),
                                   atol=2e-5)
        acc_s = streamed_accuracy(ps, pipe, x, y, mesh=mesh)
        acc_l = streamed_accuracy(ps, pipe, x, y)
        assert acc_s == acc_l
