"""Zero-parameter-traffic (regenerated-RNG) CWS: the CWS kernel on key
words vs the counter-based oracle, tile/key-order independence, the
param-free pipeline mode, and the measured-autotune registry plumbing.

Contract (DESIGN.md §3 + §7): the kernel's regenerated-parameter variants
(`ops.cws` on key words) and `cws_hash_regen` all evaluate the SAME
elementwise (key, d, k) -> (r, log_c, beta) map (threefry2x32 counter
spec in repro.core.regen), so
(i*, t*) — and therefore the fused indices — are BIT-identical across
implementations and across any tile decomposition.  Tests enforce
equality, not allclose.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.cws import cws_hash_regen
from repro.core.hashing import encode, feature_indices
from repro.core.regen import key_words, regen_params, regen_tile
from repro.kernels import ops, registry
from repro.pipeline import FeaturePipeline, FeatureSpec

from benchmarks.bench_cws_kernel import rand_nonneg


def regen_staged_oracle(x, key, k, b_i, b_t):
    i_star, t_star = cws_hash_regen(x, key, k)
    codes = encode(i_star, t_star, b_i=b_i, b_t=b_t)
    return feature_indices(codes, b_i=b_i, b_t=b_t)


BI_GRID = (0, 1, 2, 4, 8)
BT_GRID = (0, 1, 2)

# D against bd at the last D step: a tail of 1, a tail of bd - 1, bd
# dividing D (no tail branch), and D under bd (bd clamps to D)
TAIL_SHAPES = [
    # (n, D, k, bn, bk, bd)
    (9, 33, 20, 8, 8, 16),
    (9, 47, 20, 8, 8, 16),
    (9, 48, 20, 8, 8, 16),
    (9, 12, 20, 8, 8, 32),
]


def last_block(d, bd):
    """First column of the last D block (``bd`` clamped to D)."""
    bd = min(bd, d)
    return -(-d // bd) * bd - bd


def with_edge_rows(x, bd):
    """x with row 0 all zero (the sentinel) and row 1 nonzero only in
    its last D block, the one whose loop stops at the real D."""
    last = last_block(x.shape[1], bd)
    return x.at[0].set(0.0).at[1, :last].set(0.0).at[1, last:].add(0.5)


class TestCounterSpec:
    def test_tile_decomposition_invariance(self):
        """Any tiling of the (D, k) grid regenerates identical params."""
        key = jax.random.PRNGKey(3)
        k0, k1 = key_words(key)
        d, k = 24, 20
        full = regen_tile(k0, k1, 0, 0, d, k)
        for (d0, kh0, bd, bk) in [(0, 0, 8, 4), (8, 4, 16, 16), (17, 13, 7, 7)]:
            tile = regen_tile(k0, k1, d0, kh0, bd, bk)
            for f, t in zip(full, tile):
                want = f[d0:d0 + bd, kh0:kh0 + bk]
                got = t[:want.shape[0], :want.shape[1]]
                np.testing.assert_array_equal(np.asarray(want),
                                              np.asarray(got))

    def test_distributions(self):
        """r, c ~ Gamma(2,1) (mean 2, var 2), beta ~ U[0,1) — sanity at
        Monte-Carlo scale, loose tolerances."""
        p = regen_params(jax.random.PRNGKey(0), 128, 512)   # 65536 draws
        assert abs(float(p.r.mean()) - 2.0) < 0.05
        assert abs(float(p.r.var()) - 2.0) < 0.15
        assert abs(float(jnp.exp(p.log_c).mean()) - 2.0) < 0.05
        assert abs(float(p.beta.mean()) - 0.5) < 0.02
        assert float(p.beta.min()) >= 0.0 and float(p.beta.max()) < 1.0
        assert float(p.r.min()) > 0.0

    def test_key_sensitivity(self):
        a = regen_params(jax.random.PRNGKey(0), 16, 16)
        b = regen_params(jax.random.PRNGKey(1), 16, 16)
        assert (np.asarray(a.r) != np.asarray(b.r)).mean() > 0.99

    def test_accepts_raw_and_typed_keys(self):
        raw = jax.random.PRNGKey(7)                    # uint32[2]
        typed = jax.random.key(7)                      # typed key dtype
        a = regen_params(raw, 8, 8)
        b = regen_params(typed, 8, 8)
        np.testing.assert_array_equal(np.asarray(a.r), np.asarray(b.r))


class TestHashRngBitExact:
    def test_oracle_block_invariance(self):
        """cws_hash_regen is independent of its chunking — the §7 counter
        stream has no block structure."""
        x = rand_nonneg(jax.random.PRNGKey(0), (13, 22))
        key = jax.random.PRNGKey(5)
        a = cws_hash_regen(x, key, 11, hash_block=4, row_block=8)
        b = cws_hash_regen(x, key, 11, hash_block=128, row_block=256)
        np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
        np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))

    @pytest.mark.parametrize("n,d,k,bn,bk,bd", [
        (4, 8, 4, 4, 4, 8),
        (13, 22, 11, 4, 4, 8),      # non-divisible everywhere
        (33, 50, 21, 8, 8, 16),
        (7, 96, 33, 8, 16, 32),
    ] + TAIL_SHAPES)
    def test_kernel_matches_oracle(self, n, d, k, bn, bk, bd):
        x = rand_nonneg(jax.random.PRNGKey(n * 100 + d), (n, d))
        x = x.at[min(3, n - 1)].set(0.0)               # an all-zero row too
        x = with_edge_rows(x, bd)
        key = jax.random.PRNGKey(d + k)
        want_i, want_t = cws_hash_regen(x, key, k)
        got_i, got_t = ops.cws(x, key, emit="hash", num_hashes=k, bn=bn,
                               bk=bk, bd=bd, interpret=True)
        np.testing.assert_array_equal(np.asarray(want_i), np.asarray(got_i))
        np.testing.assert_array_equal(np.asarray(want_t), np.asarray(got_t))
        assert (np.asarray(got_i[1]) >= last_block(d, bd)).all()

    def test_kernel_tile_invariance(self):
        """Different (bn, bk, bd) — different grid iteration order — must
        regenerate the same parameters: counter keying is on GLOBAL
        coordinates, not tile-local state."""
        x = rand_nonneg(jax.random.PRNGKey(2), (19, 30))
        key = jax.random.PRNGKey(9)
        a = ops.cws(x, key, emit="hash", num_hashes=14, bn=4, bk=4, bd=8,
                    interpret=True)
        b = ops.cws(x, key, emit="hash", num_hashes=14, bn=16, bk=8, bd=32,
                    interpret=True)
        np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
        np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))


class TestEncodeRngBitExact:
    @pytest.mark.parametrize("b_i", BI_GRID)
    @pytest.mark.parametrize("b_t", BT_GRID)
    def test_matches_counter_oracle(self, b_i, b_t):
        n, d, k = 13, 22, 11
        x = rand_nonneg(jax.random.PRNGKey(b_i * 10 + b_t), (n, d))
        x = x.at[4].set(0.0)
        key = jax.random.PRNGKey(1)
        want = regen_staged_oracle(x, key, k, b_i, b_t)
        got = ops.cws(x, key, emit="index", num_hashes=k, b_i=b_i, b_t=b_t,
                      bn=4, bk=4, bd=8, interpret=True)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    @pytest.mark.parametrize("n,d,k,bn,bk,bd", TAIL_SHAPES)
    def test_tail_shapes_match_counter_oracle(self, n, d, k, bn, bk, bd):
        x = with_edge_rows(rand_nonneg(jax.random.PRNGKey(d), (n, d)), bd)
        key = jax.random.PRNGKey(3)
        want = regen_staged_oracle(x, key, k, 4, 1)
        got = ops.cws(x, key, emit="index", num_hashes=k, b_i=4, b_t=1,
                      bn=bn, bk=bk, bd=bd, interpret=True)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    def test_all_zero_rows_bucket0(self):
        n, d, k, b_i = 6, 16, 9, 3
        x = jnp.zeros((n, d))
        key = jax.random.PRNGKey(2)
        got = np.asarray(ops.cws(x, key, emit="index", num_hashes=k,
                                 b_i=b_i, bn=4, bk=4, bd=8, interpret=True))
        want = np.arange(k, dtype=np.int32)[None, :] * (1 << b_i)
        np.testing.assert_array_equal(got, np.broadcast_to(want, (n, k)))

    def test_reference_impl_matches_oracle(self):
        x = rand_nonneg(jax.random.PRNGKey(5), (19, 31))
        key = jax.random.PRNGKey(6)
        want = regen_staged_oracle(x, key, 14, 8, 2)
        got = ops.cws(x, key, emit="index", num_hashes=14, b_i=8, b_t=2,
                      impl="reference")
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    def test_collision_rate_estimates_kernel(self):
        """Statistics, not bits: regenerated codes are still CWS samples,
        so the collision rate estimates K_MM."""
        from repro.core.kernels import minmax_pair
        from repro.core.hashing import full_collision_estimate
        ku, kv = jax.random.split(jax.random.PRNGKey(4))
        u = rand_nonneg(ku, (1, 32), sparsity=0.2)
        v = 0.5 * u + 0.5 * rand_nonneg(kv, (1, 32), sparsity=0.2)
        i_u, t_u = cws_hash_regen(u, jax.random.PRNGKey(8), 2048)
        i_v, t_v = cws_hash_regen(v, jax.random.PRNGKey(8), 2048)
        k_hat = float(full_collision_estimate(i_u, t_u, i_v, t_v)[0])
        k_mm = float(minmax_pair(u[0], v[0]))
        assert abs(k_hat - k_mm) < 0.05


class TestParamFreePipeline:
    def test_features_match_staged_reference(self):
        x = rand_nonneg(jax.random.PRNGKey(0), (23, 17))
        pipe = FeaturePipeline.create_regen(jax.random.PRNGKey(1), 17,
                                            FeatureSpec(9, b_i=5, b_t=1))
        np.testing.assert_array_equal(
            np.asarray(pipe.features(x)),
            np.asarray(pipe.staged_reference(x)))

    def test_interpret_kernel_parity(self):
        x = rand_nonneg(jax.random.PRNGKey(2), (9, 26))
        mk = lambda impl: FeaturePipeline.create_regen(
            jax.random.PRNGKey(3), 26, FeatureSpec(7, b_i=4),
            impl=impl, blocks=(8, 4, 8))
        np.testing.assert_array_equal(
            np.asarray(mk("pallas-interpret").features(x)),
            np.asarray(mk("reference").features(x)))

    def test_streaming_parity(self):
        x = rand_nonneg(jax.random.PRNGKey(4), (41, 12))
        mk = lambda rc: FeaturePipeline.create_regen(
            jax.random.PRNGKey(5), 12, FeatureSpec(6, b_i=3), row_chunk=rc)
        np.testing.assert_array_equal(np.asarray(mk(7).features(x)),
                                      np.asarray(mk(1000).features(x)))

    def test_with_key_fresh_parameters(self):
        """The Monte-Carlo rep path: a new key is a new parameter draw;
        the same key is the same draw (consistency)."""
        x = rand_nonneg(jax.random.PRNGKey(6), (11, 14))
        pipe = FeaturePipeline.create_regen(jax.random.PRNGKey(7), 14,
                                            FeatureSpec(8, b_i=4))
        same = pipe.with_key(jax.random.PRNGKey(7))
        other = pipe.with_key(jax.random.PRNGKey(8))
        np.testing.assert_array_equal(np.asarray(pipe.features(x)),
                                      np.asarray(same.features(x)))
        assert (np.asarray(other.features(x)) !=
                np.asarray(pipe.features(x))).any()

    def test_codes_and_hashes(self):
        x = rand_nonneg(jax.random.PRNGKey(8), (5, 10))
        pipe = FeaturePipeline.create_regen(jax.random.PRNGKey(9), 10,
                                            FeatureSpec(4, b_i=0))
        i_star, t_star = pipe.hashes(x)
        assert i_star.shape == (5, 4)
        codes = pipe.codes(x)
        np.testing.assert_array_equal(np.asarray(codes),
                                      np.asarray(encode(i_star, t_star)))
        with pytest.raises(ValueError):     # b_i = 0 has no bag expansion
            pipe.features(x)

    def test_constructor_validation(self):
        spec = FeatureSpec(4, b_i=2)
        with pytest.raises(ValueError):
            FeaturePipeline(None, spec)                 # no key/dim
        with pytest.raises(ValueError):
            FeaturePipeline.create(jax.random.PRNGKey(0), 8, spec,
                                   regen_key=jax.random.PRNGKey(1))
        stored = FeaturePipeline.create(jax.random.PRNGKey(0), 8, spec)
        with pytest.raises(ValueError):
            stored.with_key(jax.random.PRNGKey(1))


class TestRegistryAutotune:
    def test_new_op_families_registered(self):
        for op in ("cws", "min_sum"):
            names = registry.impl_names(op)
            assert {"pallas", "pallas-interpret", "reference"} <= set(names)

    def test_block_table_roundtrip(self, tmp_path):
        path = tmp_path / "bt.json"
        entries = {registry.table_key("cws_rng", 64, 128, 64): (32, 64, 128),
                   registry.table_key("min_sum", 256, 256, 256):
                       (64, 128, 256)}
        registry.save_block_table(path, entries)
        try:
            loaded = registry.load_block_table(path)
            assert loaded == entries
            assert registry.choose_blocks(60, 100, 60, op="cws_rng") == \
                (32, 60, 100)       # table hit, clamped to the problem
        finally:                    # don't leak into other tests
            for k in entries:
                registry.BLOCK_TABLE.pop(k, None)

    def test_block_candidates_fit_budget(self):
        for op in ("cws", "cws_rng", "min_sum"):
            cands = registry.block_candidates(1024, 1024, 1024, op=op)
            assert cands
            for (b1, b2, bd) in cands:
                assert registry.vmem_bytes(b1, b2, bd, op=op) <= 8 * 2 ** 20

    def test_min_sum_default_blocks(self):
        """min_sum_pallas resolves unset blocks via choose_blocks and
        stays correct on non-divisible shapes."""
        from repro.kernels.minmax_gram import min_sum_pallas
        from repro.kernels.ref import min_sum_ref
        x = rand_nonneg(jax.random.PRNGKey(0), (13, 37))
        y = rand_nonneg(jax.random.PRNGKey(1), (9, 37))
        np.testing.assert_allclose(
            np.asarray(min_sum_pallas(x, y, interpret=True)),
            np.asarray(min_sum_ref(x, y)), rtol=1e-6)

    def test_autotune_harness_dry_run(self):
        """The harness's sweep cells run importable end-to-end (CI keeps
        this green via the bench-smoke job's --dry-run)."""
        import tools.autotune_blocks as ab
        blocks, us, rows = ab.tune("cws_rng", 64, 64, 64, repeats=1,
                                   dry_run=True)
        assert blocks == registry.choose_blocks(64, 64, 64, op="cws_rng")
        assert rows == []
