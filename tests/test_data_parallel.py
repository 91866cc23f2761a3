"""Data-parallel streamed training + sharded/streamed featurization
composition (DESIGN.md §11).

Single-device assertions (bit-identity of the mesh= paths against the
unsharded ones) run everywhere; the multi-device parity tests activate
under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CI
``sharded-smoke`` job) and skip otherwise.  The four-device fit against
one device (``TestFourDevices``) runs everywhere, in a subprocess that
forces four host devices.

What is pinned down:
  * sharded+streamed composition pads ONCE to lcm(row_chunk, ndev) and
    compiles exactly one chunk shape (the PR 3 invariant, now under
    mesh=);
  * the n < ndev edge: all-pad shards featurize to bucket 0 and slice
    off; whole-array launches never run through the donating fn (the
    zero-pad pass-through may alias the caller's live x);
  * fit_linear_streamed(mesh=)/streamed_accuracy(mesh=) are bit-identical
    to the unsharded streamed path on a 1-device mesh and walk the same
    batch sequence on N devices (accuracy within 0.5 pp, shared shuffle
    key);
  * the param-free (create_regen) pipeline rides every sharded path.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import compile_guard
from repro.core.linear_model import TrainCfg, init_bag
from repro.data.synthetic import make_template_classification
from repro.launch.mesh import data_axis_size, make_data_mesh, make_local_mesh
from repro.pipeline import FeaturePipeline, FeatureSpec
from repro.training import fit_linear_streamed, streamed_accuracy

NDEV = len(jax.devices())
multi_device = pytest.mark.skipif(
    NDEV < 8, reason="needs XLA_FLAGS=--xla_force_host_platform_"
                     "device_count=8 (CI sharded-smoke job)")


def rand_nonneg(key, shape, sparsity=0.4):
    k1, k2 = jax.random.split(key)
    mag = jnp.exp(jax.random.normal(k1, shape))
    mask = jax.random.bernoulli(k2, 1 - sparsity, shape)
    return mag * mask


@pytest.fixture(scope="module")
def problem():
    ds = make_template_classification(3, n_train=160, n_test=80, dim=32,
                                      n_classes=3, mult_noise=1.1,
                                      spike_prob=0.02, density=0.3)
    spec = FeatureSpec(num_hashes=24, b_i=4)
    pipe = FeaturePipeline.create(jax.random.PRNGKey(7), 32, spec)
    return (pipe, jnp.asarray(ds.x_train), jnp.asarray(ds.y_train),
            jnp.asarray(ds.x_test), jnp.asarray(ds.y_test))


@pytest.fixture(scope="module")
def mesh():
    return make_local_mesh()


class TestShardedStreamedFeatures:
    """Satellite 1+2: mesh= and streaming compose on ONE padded chunk
    shape; tiny batches survive all-pad shards."""

    def _pipe(self, row_chunk, d=18, k=10):
        spec = FeatureSpec(num_hashes=k, b_i=3)
        return FeaturePipeline.create(jax.random.PRNGKey(3), d, spec,
                                      row_chunk=row_chunk)

    def test_chunk_rows_is_lcm(self, mesh):
        ndev = data_axis_size(mesh)
        pipe = self._pipe(row_chunk=28)
        assert pipe.chunk_rows() == 28
        assert pipe.chunk_rows(mesh) == np.lcm(28, ndev)

    def test_streamed_sharded_matches_unsharded(self, mesh):
        pipe = self._pipe(row_chunk=8)
        whole = self._pipe(row_chunk=1 << 20)
        whole.params = pipe.params
        x = rand_nonneg(jax.random.PRNGKey(4), (27, 18))   # ragged tail
        x = x.at[25].set(0.0)                              # zero row too
        np.testing.assert_array_equal(np.asarray(pipe.features(x, mesh=mesh)),
                                      np.asarray(whole.features(x)))

    def test_single_compile_under_mesh(self, mesh):
        """The PR 3 single-compile invariant extends to mesh=: every
        chunk (ragged tail included) pads to lcm(row_chunk, ndev), so
        the donating sharded fn traces exactly one shape."""
        pipe = self._pipe(row_chunk=8)
        x = rand_nonneg(jax.random.PRNGKey(5), (3 * pipe.chunk_rows(mesh)
                                                + 5, 18))
        with compile_guard() as g:
            g.watch(pipe._sharded_chunk_fn(mesh), label="sharded chunk_fn")
            pipe.features(x, mesh=mesh)

    def test_tiny_n_below_ndev(self, mesh):
        """n < ndev: some shards are ALL pad rows — they must featurize
        (all-zero -> sentinel -> bucket 0) and slice off."""
        pipe = self._pipe(row_chunk=8)
        x = rand_nonneg(jax.random.PRNGKey(6), (3, 18))
        np.testing.assert_array_equal(np.asarray(pipe.features(x, mesh=mesh)),
                                      np.asarray(pipe.features(x)))

    def test_whole_array_launch_never_donates(self, mesh):
        """Satellite 2: with zero pad, jnp.pad may pass the caller's x
        straight through — the whole-array sharded launch must route via
        the NON-donating fn so x (and the [:n] slice source) stay
        valid."""
        ndev = data_axis_size(mesh)
        pipe = self._pipe(row_chunk=8)
        x = rand_nonneg(jax.random.PRNGKey(7), (ndev, 18))  # pad == 0
        got = pipe.features(x, mesh=mesh)
        # the lone-whole-chunk iterator path (streamed_accuracy's entry
        # point) must follow the same no-donate policy: its full-range
        # slice can alias the caller's x just the same
        [(_, _, via_chunks)] = list(pipe.feature_chunks(x, mesh=mesh))
        np.testing.assert_array_equal(np.asarray(via_chunks),
                                      np.asarray(got))
        assert (mesh, False) in pipe._sharded_fns
        assert (mesh, True) not in pipe._sharded_fns
        # x is still alive and consistent after the launch
        np.testing.assert_array_equal(np.asarray(pipe.features(x)),
                                      np.asarray(got))

    def test_param_free_sharded_streamed(self, mesh):
        spec = FeatureSpec(num_hashes=10, b_i=3)
        pipe = FeaturePipeline.create_regen(jax.random.PRNGKey(8), 18,
                                            spec, row_chunk=8)
        x = rand_nonneg(jax.random.PRNGKey(9), (27, 18))
        np.testing.assert_array_equal(np.asarray(pipe.features(x, mesh=mesh)),
                                      np.asarray(pipe.features(x)))
        np.testing.assert_array_equal(np.asarray(pipe.features(x, mesh=mesh)),
                                      np.asarray(pipe.staged_reference(x)))

    def test_launch_chunk_rejects_indivisible_rows(self, mesh):
        if data_axis_size(mesh) == 1:
            pytest.skip("every row count divides a 1-device mesh")
        pipe = self._pipe(row_chunk=8)
        x = rand_nonneg(jax.random.PRNGKey(10),
                        (data_axis_size(mesh) + 1, 18))
        with pytest.raises(ValueError, match="divisible"):
            pipe.launch_chunk(x, mesh=mesh)

    def test_feature_chunks_mesh_spans(self, mesh):
        pipe = self._pipe(row_chunk=8)
        rows = pipe.chunk_rows(mesh)
        n = 2 * rows + 3
        x = rand_nonneg(jax.random.PRNGKey(11), (n, 18))
        full = pipe.features(x)
        spans = []
        for lo, hi, fb in pipe.feature_chunks(x, mesh=mesh):
            spans.append((lo, hi))
            np.testing.assert_array_equal(np.asarray(fb),
                                          np.asarray(full[lo:hi]))
        assert spans == [(0, rows), (rows, 2 * rows), (2 * rows, n)]


class TestShardedTraining:
    """Tentpole: fit_linear_streamed(mesh=) — bit-identical at ndev=1,
    same batch walk at any ndev."""

    def test_one_device_mesh_bit_identity(self, problem):
        pipe, xtr, ytr, _, _ = problem
        m1 = make_data_mesh(1)
        p0 = init_bag(jax.random.PRNGKey(0), pipe.num_features, 3)
        cfg = TrainCfg(n_classes=3, steps=30, lr=0.05, l2=1e-5,
                       batch_size=32)
        key = jax.random.PRNGKey(5)
        pa = fit_linear_streamed(p0, pipe, xtr, ytr, cfg=cfg,
                                 shuffle_key=key)
        pb = fit_linear_streamed(p0, pipe, xtr, ytr, cfg=cfg,
                                 shuffle_key=key, mesh=m1)
        np.testing.assert_array_equal(np.asarray(pa.w), np.asarray(pb.w))
        np.testing.assert_array_equal(np.asarray(pa.b), np.asarray(pb.b))

    def test_bs_equals_n_mesh_bit_identity(self, problem):
        pipe, xtr, ytr, _, _ = problem
        m1 = make_data_mesh(1)
        n = xtr.shape[0]
        p0 = init_bag(jax.random.PRNGKey(0), pipe.num_features, 3)
        cfg = TrainCfg(n_classes=3, steps=20, lr=0.05, l2=1e-5,
                       batch_size=n)
        pa = fit_linear_streamed(p0, pipe, xtr, ytr, cfg=cfg)
        pb = fit_linear_streamed(p0, pipe, xtr, ytr, cfg=cfg, mesh=m1)
        np.testing.assert_array_equal(np.asarray(pa.w), np.asarray(pb.w))

    def test_streamed_accuracy_mesh_identical(self, problem, mesh):
        pipe, xtr, ytr, _, _ = problem
        p0 = init_bag(jax.random.PRNGKey(1), pipe.num_features, 3)
        a = streamed_accuracy(p0, pipe, xtr, ytr)
        b = streamed_accuracy(p0, pipe, xtr, ytr, mesh=mesh)
        assert a == b   # an integer correct-count: exact on any ndev

    def test_host_numpy_dataset_mesh_matches_device(self, problem, mesh):
        pipe, xtr, ytr, _, _ = problem
        if xtr.shape[0] % data_axis_size(mesh):
            pytest.skip("fixture rows don't divide this device count")
        p0 = init_bag(jax.random.PRNGKey(0), pipe.num_features, 3)
        cfg = TrainCfg(n_classes=3, steps=20, lr=0.05, l2=1e-5,
                       batch_size=32)
        key = jax.random.PRNGKey(2)
        pa = fit_linear_streamed(p0, pipe, xtr, ytr, cfg=cfg,
                                 shuffle_key=key, mesh=mesh)
        pb = fit_linear_streamed(p0, pipe, np.asarray(xtr),
                                 np.asarray(ytr), cfg=cfg,
                                 shuffle_key=key, mesh=mesh)
        np.testing.assert_array_equal(np.asarray(pa.w), np.asarray(pb.w))

    def test_batch_size_must_divide_data_axis(self, problem, mesh):
        pipe, xtr, ytr, _, _ = problem
        if data_axis_size(mesh) == 1:
            pytest.skip("every batch size divides a 1-device mesh")
        p0 = init_bag(jax.random.PRNGKey(0), pipe.num_features, 3)
        cfg = TrainCfg(n_classes=3, steps=5,
                       batch_size=data_axis_size(mesh) + 1)
        with pytest.raises(ValueError, match="data axis"):
            fit_linear_streamed(p0, pipe, xtr, ytr, cfg=cfg, mesh=mesh)

    def test_microbatch_divides_local_batch(self, problem):
        pipe, xtr, ytr, _, _ = problem
        m1 = make_data_mesh(1)
        p0 = init_bag(jax.random.PRNGKey(0), pipe.num_features, 3)
        cfg = TrainCfg(n_classes=3, steps=8, lr=0.05, l2=1e-5,
                       batch_size=32)
        key = jax.random.PRNGKey(3)
        pa = fit_linear_streamed(p0, pipe, xtr, ytr, cfg=cfg,
                                 shuffle_key=key, n_microbatches=2)
        pb = fit_linear_streamed(p0, pipe, xtr, ytr, cfg=cfg,
                                 shuffle_key=key, n_microbatches=2,
                                 mesh=m1)
        np.testing.assert_array_equal(np.asarray(pa.w), np.asarray(pb.w))
        with pytest.raises(ValueError, match="microbatch"):
            fit_linear_streamed(p0, pipe, xtr, ytr,
                                cfg=TrainCfg(n_classes=3, steps=2,
                                             batch_size=30),
                                n_microbatches=4, mesh=m1)

    def test_never_materializes_full_index_matrix(self, problem, mesh,
                                                  monkeypatch):
        """The sharded update featurizes per shard INSIDE shard_map —
        trace-time launch shapes stay at the local batch, never (n, k)."""
        pipe, xtr, ytr, _, _ = problem
        n, bs = xtr.shape[0], 16
        if bs % data_axis_size(mesh):
            pytest.skip("batch doesn't divide this device count")
        shapes = []
        orig = FeaturePipeline._launch_with

        def spy(self, xc, state):
            shapes.append(int(xc.shape[0]))
            return orig(self, xc, state)

        monkeypatch.setattr(FeaturePipeline, "_launch_with", spy)
        p0 = init_bag(jax.random.PRNGKey(0), pipe.num_features, 3)
        cfg = TrainCfg(n_classes=3, steps=6, lr=0.05, l2=1e-5,
                       batch_size=bs)
        fit_linear_streamed(p0, pipe, xtr, ytr, cfg=cfg, mesh=mesh)
        assert shapes, "sharded fit must launch the pipeline kernel"
        assert max(shapes) == bs // data_axis_size(mesh) < n


@multi_device
class TestMultiDeviceParity:
    """The forced-8-host-device job: the real sharded walk."""

    def test_mesh_has_eight_data_shards(self, mesh):
        assert data_axis_size(mesh) == 8

    def test_features_bit_parity(self, problem, mesh):
        # featurization is per-row deterministic: splitting rows across
        # devices must be BIT-exact, not approximately equal
        pipe, xtr, _, _, _ = problem
        np.testing.assert_array_equal(
            np.asarray(pipe.features(xtr, mesh=mesh)),
            np.asarray(pipe.features(xtr)))

    def test_training_accuracy_parity(self, problem, mesh):
        pipe, xtr, ytr, xte, yte = problem
        p0 = init_bag(jax.random.PRNGKey(0), pipe.num_features, 3)
        cfg = TrainCfg(n_classes=3, steps=200, lr=0.05, l2=1e-5,
                       batch_size=32)
        key = jax.random.PRNGKey(5)
        pa = fit_linear_streamed(p0, pipe, xtr, ytr, cfg=cfg,
                                 shuffle_key=key)
        pb = fit_linear_streamed(p0, pipe, xtr, ytr, cfg=cfg,
                                 shuffle_key=key, mesh=mesh)
        acc_a = streamed_accuracy(pa, pipe, xte, yte)
        acc_b = streamed_accuracy(pb, pipe, xte, yte, mesh=mesh)
        # same shuffle key -> same batch walk; only the gradient
        # summation order differs (psum reassociation)
        assert abs(acc_a - acc_b) <= 0.005
        np.testing.assert_allclose(np.asarray(pa.w), np.asarray(pb.w),
                                   rtol=1e-3, atol=1e-4)

    def test_param_free_training_parity(self, problem, mesh):
        _, xtr, ytr, xte, yte = problem
        spec = FeatureSpec(num_hashes=24, b_i=4)
        pipe = FeaturePipeline.create_regen(jax.random.PRNGKey(11), 32,
                                            spec)
        p0 = init_bag(jax.random.PRNGKey(0), pipe.num_features, 3)
        cfg = TrainCfg(n_classes=3, steps=80, lr=0.05, l2=1e-5,
                       batch_size=32)
        key = jax.random.PRNGKey(6)
        pa = fit_linear_streamed(p0, pipe, xtr, ytr, cfg=cfg,
                                 shuffle_key=key)
        pb = fit_linear_streamed(p0, pipe, xtr, ytr, cfg=cfg,
                                 shuffle_key=key, mesh=mesh)
        acc_a = streamed_accuracy(pa, pipe, xte, yte)
        acc_b = streamed_accuracy(pb, pipe, xte, yte, mesh=mesh)
        assert abs(acc_a - acc_b) <= 0.005

    def test_ragged_n_streamed_parity(self, mesh):
        spec = FeatureSpec(num_hashes=10, b_i=3)
        pipe = FeaturePipeline.create(jax.random.PRNGKey(12), 18, spec,
                                      row_chunk=12)   # lcm(12, 8) = 24
        assert pipe.chunk_rows(mesh) == 24
        x = rand_nonneg(jax.random.PRNGKey(13), (61, 18))  # 24+24+13
        with compile_guard() as g:
            g.watch(pipe._sharded_chunk_fn(mesh), label="sharded chunk_fn")
            sharded = pipe.features(x, mesh=mesh)
        np.testing.assert_array_equal(np.asarray(sharded),
                                      np.asarray(pipe.features(x)))


FOUR_DEVICES = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.linear_model import TrainCfg, init_bag
from repro.data.synthetic import make_template_classification
from repro.launch.mesh import make_data_mesh
from repro.pipeline import FeaturePipeline, FeatureSpec
from repro.training import fit_linear_streamed, trainer

ds = make_template_classification(3, n_train=1024, n_test=16, dim=32,
                                  n_classes=3, mult_noise=1.1,
                                  spike_prob=0.02, density=0.3)
pipe = FeaturePipeline.create(jax.random.PRNGKey(7), 32,
                              FeatureSpec(num_hashes=64, b_i=4))
x, y = jnp.asarray(ds.x_train), jnp.asarray(ds.y_train)
p0 = init_bag(jax.random.PRNGKey(1), pipe.num_features, 3)
# 64 rows a device, 256 a step: 36 steps walk 9 epochs of 4 batches
cfg = TrainCfg(n_classes=3, steps=36, lr=0.05, l2=1e-5, batch_size=256)
key = jax.random.PRNGKey(5)


def fit(mesh=None):
    return fit_linear_streamed(p0, pipe, x, y, cfg=cfg, shuffle_key=key,
                               mesh=mesh)


def gaps(a, b):
    return {k: float(jnp.linalg.norm(getattr(a, k) - getattr(b, k)) /
                     jnp.linalg.norm(getattr(a, k))) for k in ("w", "b")}


one = fit()
out = {"devices": len(jax.devices()), "sound": gaps(one, fit(make_data_mesh(4)))}
# with the data and the table on the mesh, no step copies an array from
# one device to another (the launch state, the epoch's row order and the
# step's scalars are placed on every device once)
rep = NamedSharding(make_data_mesh(4), P())
try:
    with jax.transfer_guard_device_to_device("disallow"):
        jax.block_until_ready(fit_linear_streamed(
            jax.device_put(p0, rep), pipe, *jax.device_put((x, y), rep),
            cfg=cfg, shuffle_key=key, mesh=make_data_mesh(4)))
    out["device_to_device"] = None
except Exception as e:
    out["device_to_device"] = str(e)[:500]
# the exchange left out: each device steps on its own shard's gradient
trainer._pmean_loss_grads = lambda loss, grads, axis_name: (loss, grads)
out["no_exchange"] = gaps(one, fit(make_data_mesh(4)))
print(json.dumps(out))
"""

# only the order of the gradient's sum differs between one device and
# four (about 1e-7 of each leaf's norm after 36 steps on the CPU)
FOUR_DEVICE_RTOL = 1e-5


@pytest.fixture(scope="module")
def four_devices():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", FOUR_DEVICES], cwd=root,
                         env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    report = json.loads(out.stdout.strip().splitlines()[-1])
    assert report["devices"] == 4
    return report


class TestFourDevices:
    """fit_linear_streamed(mesh=make_data_mesh(4)) against the
    single-device fit on the same 256-row global batches and shuffle
    key: the leaves' norm-relative gaps after 36 steps."""

    def test_four_devices_match_one(self, four_devices):
        for leaf, gap in four_devices["sound"].items():
            assert gap <= FOUR_DEVICE_RTOL, (leaf, gap)

    def test_exchange_left_out_fails_the_tolerance(self, four_devices):
        assert max(four_devices["no_exchange"].values()) > FOUR_DEVICE_RTOL

    def test_steps_copy_nothing_between_devices(self, four_devices):
        assert four_devices["device_to_device"] is None, \
            four_devices["device_to_device"]
