#!/usr/bin/env python3
"""Chip smoke: the featurize -> train -> serve path once on a TPU, checked.

Runs the paper's main path through its normal entry points at the full
width of the ``minmax_paper`` config (D=256, k=1024, b_i=8, b_t=0, 10
classes) on an MNIST-sized dataset generated from ``--seed``:

  featurize  ``FeaturePipeline.create`` (stored params) and
             ``create_regen`` on 70,000 rows, and the stored pipeline's
             bit-packed words; the Pallas features must equal the
             ``reference`` impl's (at most 0.1% of (row, hash) entries
             may differ, and the share is printed).
  train      ``fit_linear_streamed`` for the config's 400 steps, then
             ``streamed_accuracy``; test accuracy within 0.5 pp of the
             same run on the float32 ``reference`` impl.
  serve      ``export_served_model`` -> ``ServingService.from_bundle``
             (default bucket ladder) answering a few hundred ragged
             requests (1-512 rows) through ``submit``; the served logits
             must be bit-equal to the offline ``features`` ->
             ``bag_logits`` composition, and no future may fail.
  gram       the CWS collision rate against the exact min-max Gram from
             ``minmax_gram`` (Pallas), mean absolute error <= 3/sqrt(k).

``--chips 4`` runs only the data-parallel path instead:
``fit_linear_streamed(..., mesh=make_data_mesh(4))`` against the same
seed's one-chip run (features bit-identical across meshes, shards on 4
distinct devices, accuracy within 0.5 pp).

It refuses to run without a TPU, and asserts that every featurize op
resolved to the ``pallas`` impl and that the serving executables hold a
Mosaic kernel (``tpu_custom_call``).  Wall times and compile counts are
printed for orientation: a smoke, not a benchmark.  The last line of
stdout is one JSON object, ``{"ok": true, "device": {...}}``; any failed
phase exits nonzero before printing it.

    python chip_smoke.py [--seed 0] [--chips 4]
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import json
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax                                               # noqa: E402
import jax.numpy as jnp                                  # noqa: E402
import numpy as np                                       # noqa: E402

from repro.configs.minmax_paper import CONFIG            # noqa: E402
from repro.core.linear_model import (TrainCfg, bag_logits,  # noqa: E402
                                     init_bag)
from repro.data.synthetic import make_template_classification  # noqa: E402
from repro.kernels import ops, registry                  # noqa: E402
from repro.launch.compile_cache import setup_compile_cache  # noqa: E402
from repro.launch.mesh import make_data_mesh             # noqa: E402
from repro.pipeline import FeaturePipeline, FeatureSpec  # noqa: E402
from repro.serving import ServingService                 # noqa: E402
from repro.training import (export_served_model,         # noqa: E402
                            fit_linear_streamed, streamed_accuracy)

N_TRAIN, N_TEST = 60_000, 10_000      # MNIST-sized, as the paper's Table 1
BATCH = 512                           # divides over 1 and 4 chips
N_REQUESTS = 300
MAX_REQUEST_ROWS = 512
GRAM_ROWS = 512
FEATURE_MISMATCH_LIMIT = 1e-3         # share of (row, hash) entries
ACCURACY_GAP_PP = 0.5
KERNEL_MARKER = "tpu_custom_call"
PALLAS_OPS = ("cws_encode", "cws_encode_rng", "cws_encode_packed",
              "minmax_gram")


class SmokeFailure(RuntimeError):
    pass


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class Phases:
    """Per-phase wall time and XLA compile count (one backend compile
    event per executable built, cache hits included)."""

    def __init__(self):
        self.compiles = 0
        self.rows = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration_s: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    @contextlib.contextmanager
    def phase(self, name: str):
        c0, t0 = self.compiles, time.perf_counter()
        print(f"[smoke] phase {name} ...", flush=True)
        yield
        dt, n = time.perf_counter() - t0, self.compiles - c0
        self.rows.append((name, dt, n))
        print(f"[smoke, not a benchmark] phase {name}: {dt:.1f} s wall, "
              f"{n} compiles", flush=True)


def feature_spec(packed: bool = False) -> FeatureSpec:
    return FeatureSpec(num_hashes=CONFIG.num_hashes, b_i=CONFIG.b_i,
                       b_t=CONFIG.b_t, packed=packed)


def train_cfg() -> TrainCfg:
    return TrainCfg(n_classes=CONFIG.n_classes, steps=CONFIG.steps,
                    lr=CONFIG.lr, l2=CONFIG.l2, batch_size=BATCH)


def make_data(seed: int):
    ds = make_template_classification(
        seed, n_train=N_TRAIN, n_test=N_TEST, dim=CONFIG.dim,
        n_classes=CONFIG.n_classes)
    return (jnp.asarray(ds.x_train), jnp.asarray(ds.y_train),
            jnp.asarray(ds.x_test), jnp.asarray(ds.y_test))


def pipelines(seed: int, impl=None):
    """(stored, regen) pipelines on the config's spec from ``seed``."""
    key = jax.random.PRNGKey(seed)
    spec = feature_spec()
    return (FeaturePipeline.create(key, CONFIG.dim, spec, impl=impl),
            FeaturePipeline.create_regen(key, CONFIG.dim, spec, impl=impl))


def require_pallas_dispatch() -> None:
    for op in PALLAS_OPS:
        picked = registry.resolve(op).name
        require(picked == "pallas",
                f"registry resolved {op!r} to {picked!r}, not 'pallas'")


def require_kernel(compiled_text: str, what: str) -> None:
    require(KERNEL_MARKER in compiled_text,
            f"{what}: no {KERNEL_MARKER} in the compiled executable")


def mismatch_share(got, want) -> float:
    got, want = np.asarray(got), np.asarray(want)
    require(got.shape == want.shape and got.dtype == want.dtype,
            f"shape/dtype {got.shape} {got.dtype} vs reference "
            f"{want.shape} {want.dtype}")
    return float(np.mean(got != want))


def train_eval(pipe, params0, data, seed: int, mesh=None):
    x_tr, y_tr, x_te, y_te = data
    params = fit_linear_streamed(params0, pipe, x_tr, y_tr, cfg=train_cfg(),
                                 shuffle_key=jax.random.PRNGKey(seed + 2),
                                 mesh=mesh)
    acc = streamed_accuracy(params, pipe, x_te, y_te, mesh=mesh)
    return params, acc


def init_params(pipe, seed: int):
    return init_bag(jax.random.PRNGKey(seed + 1), pipe.num_features,
                    CONFIG.n_classes)


# ---------------------------------------------------------------------------
# one chip: featurize -> train -> serve -> gram
# ---------------------------------------------------------------------------

def featurize_phase(data, seed: int):
    """Pallas features of every row, stored, regen and stored-packed,
    against the reference impl.  Returns (stored pipe, its features of
    all rows)."""
    require_pallas_dispatch()
    x_all = jnp.concatenate([data[0], data[2]], axis=0)
    stored, regen = pipelines(seed)
    ref_stored, ref_regen = pipelines(seed, impl="reference")
    key = jax.random.PRNGKey(seed)
    packed, ref_packed = (
        FeaturePipeline.create(key, CONFIG.dim, feature_spec(packed=True),
                               impl=impl) for impl in (None, "reference"))
    feats = {}
    for name, pipe, ref in (("stored", stored, ref_stored),
                            ("regen", regen, ref_regen),
                            ("stored packed", packed, ref_packed)):
        got = jax.block_until_ready(pipe.features(x_all))
        share = mismatch_share(got, ref.features(x_all))
        print(f"featurize {name}: {got.shape[0]} rows x {got.shape[1]} "
              f"{'words' if pipe.spec.packed else 'hashes'}; share of "
              f"entries differing from the reference impl {share:.6%}",
              flush=True)
        require(share <= FEATURE_MISMATCH_LIMIT,
                f"{name} features differ from the reference impl in "
                f"{share:.4%} of entries (limit "
                f"{FEATURE_MISMATCH_LIMIT:.1%})")
        if not pipe.spec.packed:
            lo, hi = int(jnp.min(got)), int(jnp.max(got))
            require(0 <= lo and hi < pipe.num_features,
                    f"{name} feature index range [{lo}, {hi}] outside "
                    f"[0, {pipe.num_features})")
        feats[name] = got
    return stored, feats["stored"]


def train_phase(stored, data, seed: int):
    params0 = init_params(stored, seed)
    params, acc = train_eval(stored, params0, data, seed)
    ref_pipe, _ = pipelines(seed, impl="reference")
    _, acc_ref = train_eval(ref_pipe, params0, data, seed)
    gap_pp = abs(acc - acc_ref) * 100
    print(f"train: {CONFIG.steps} steps x {BATCH} rows; test accuracy "
          f"pallas {acc:.4f}, reference {acc_ref:.4f}, gap {gap_pp:.2f} pp "
          f"(chance {1 / CONFIG.n_classes:.2f})", flush=True)
    require(gap_pp <= ACCURACY_GAP_PP,
            f"accuracy gap {gap_pp:.2f} pp > {ACCURACY_GAP_PP} pp")
    require(acc > 2.0 / CONFIG.n_classes,
            f"test accuracy {acc:.4f} is near chance")
    return params


def serve_phase(params, stored, x_test, seed: int):
    """Ragged requests through ``ServingService.submit``; every answer
    bit-equal to the offline composition."""
    rng = np.random.default_rng(seed)
    sizes = rng.integers(1, MAX_REQUEST_ROWS + 1, N_REQUESTS)
    sizes[:2] = (1, MAX_REQUEST_ROWS)
    x_np = np.asarray(x_test)
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]]) % x_np.shape[0]
    requests = [np.take(x_np, np.arange(s, s + m), axis=0, mode="wrap")
                for s, m in zip(starts, sizes)]

    with tempfile.TemporaryDirectory(prefix=".smoke_bundle_",
                                     dir=ROOT) as tmp:
        bundle = pathlib.Path(tmp) / "bundle"
        export_served_model(params, stored, bundle)
        with ServingService.from_bundle(bundle) as svc:
            runner = svc.runner
            for b in runner.buckets:
                text = runner.pipe.scoring_chunk_fn().lower(
                    jnp.zeros((b, runner.pipe.dim), jnp.float32),
                    runner._state, runner.params).compile().as_text()
                require_kernel(text, f"serving executable (bucket {b})")
            served = _submit_all(svc, requests)
            stats = svc.stats()

    require(stats.get("compile_count") == len(runner.buckets),
            f"serving compiled {stats.get('compile_count')} executables "
            f"for {len(runner.buckets)} buckets")
    got = np.concatenate(served, axis=0)
    want = _offline_logits(params, stored, np.concatenate(requests, axis=0))
    require(got.shape == want.shape and np.all(np.isfinite(got)),
            f"served logits {got.shape} (finite: "
            f"{bool(np.all(np.isfinite(got)))}) vs offline {want.shape}")
    n_diff = int(np.sum(got != want))
    print(f"serve: {len(requests)} requests, {got.shape[0]} rows over "
          f"buckets {runner.buckets}; served logits differing from "
          f"offline: {n_diff} of {got.size}; latency p50 "
          f"{stats['latency_ms']['p50']:.1f} ms p99 "
          f"{stats['latency_ms']['p99']:.1f} ms; pad rows "
          f"{stats.get('pad_rows', 0)}", flush=True)
    require(n_diff == 0, f"{n_diff} served logits differ from the offline "
            f"features -> bag_logits composition (max abs diff "
            f"{float(np.max(np.abs(got - want))):.3g})")


def _submit_all(svc, requests):
    """Keep at most the gateway's queue bound in flight; read every
    future (a failed one raises here)."""
    limit = svc.gateway.max_queue_rows
    inflight = collections.deque()
    rows_inflight = 0
    out = []
    for x in requests:
        while inflight and rows_inflight + x.shape[0] > limit:
            m, fut = inflight.popleft()
            out.append(fut.result(timeout=300))
            rows_inflight -= m
        inflight.append((x.shape[0], svc.submit(x)))
        rows_inflight += x.shape[0]
    out.extend(fut.result(timeout=300) for _, fut in inflight)
    for x, y in zip(requests, out):
        require(y.shape == (x.shape[0], CONFIG.n_classes),
                f"request of {x.shape[0]} rows answered with {y.shape}")
    return out


def _offline_logits(params, pipe, x) -> np.ndarray:
    logits = jax.jit(bag_logits)
    return np.concatenate(
        [np.asarray(logits(params, fb))
         for _, _, fb in pipe.feature_chunks(jnp.asarray(x))], axis=0)


@jax.jit
def _collision_rate(f):
    """(m, k) features -> (m, m) share of hashes on which rows agree."""
    return jnp.mean((f[:, None, :] == f[None, :, :]).astype(jnp.float32),
                    axis=-1)


def gram_phase(feats_test, x_test):
    """Collision rate of the 0-bit CWS features vs the exact min-max
    kernel (Pallas ``minmax_gram``, itself checked against the
    reference impl)."""
    x = x_test[:GRAM_ROWS]
    f = feats_test[:GRAM_ROWS]
    exact = ops.minmax_gram(x, x)
    ref = ops.minmax_gram(x, x, impl="reference")
    gram_err = float(jnp.max(jnp.abs(exact - ref)))
    collide = _collision_rate(f)
    off = ~np.eye(x.shape[0], dtype=bool)
    mae = float(np.mean(np.abs(np.asarray(collide - exact))[off]))
    bound = 3 / np.sqrt(CONFIG.num_hashes)
    print(f"gram: {x.shape[0]} rows; minmax_gram pallas vs reference max "
          f"abs diff {gram_err:.3g}; collision-rate MAE vs exact "
          f"{mae:.4f} (bound {bound:.4f})", flush=True)
    require(gram_err <= 1e-5, f"minmax_gram pallas vs reference differ "
            f"by {gram_err:.3g}")
    require(mae <= bound, f"collision MAE {mae:.4f} > 3/sqrt(k) {bound:.4f}")


def run_one_chip(seed: int, phases: Phases) -> None:
    with phases.phase("data"):
        data = make_data(seed)
    with phases.phase("featurize"):
        stored, feats = featurize_phase(data, seed)
    with phases.phase("train"):
        params = train_phase(stored, data, seed)
    with phases.phase("serve"):
        serve_phase(params, stored, data[2], seed)
    with phases.phase("gram"):
        gram_phase(feats[N_TRAIN:], data[2])


# ---------------------------------------------------------------------------
# four chips: data-parallel training vs the same seed on one chip
# ---------------------------------------------------------------------------

def run_four_chips(seed: int, phases: Phases) -> None:
    require(len(jax.devices()) >= 4,
            f"--chips 4 needs 4 devices; JAX sees {len(jax.devices())}")
    require_pallas_dispatch()
    with phases.phase("data"):
        data = make_data(seed)
        stored, _ = pipelines(seed)
        mesh = make_data_mesh(4)
    with phases.phase("features across meshes"):
        x_all = jnp.concatenate([data[0], data[2]], axis=0)
        one = stored.features(x_all)
        four = stored.features(x_all, mesh=mesh)
        share = mismatch_share(four, one)
        chunk = stored.launch_chunk(x_all[:stored.chunk_rows(mesh)],
                                    mesh=mesh)
        devices = {s.device.id for s in chunk.addressable_shards}
        rows = {s.data.shape[0] for s in chunk.addressable_shards}
        print(f"mesh features: share differing from one chip {share:.6%}; "
              f"one launch's shards on devices {sorted(devices)} with "
              f"{sorted(rows)} rows each", flush=True)
        require(share == 0.0, "features differ between 1 and 4 chips")
        require(len(devices) == 4,
                f"sharded launch landed on devices {sorted(devices)}")
    params0 = init_params(stored, seed)
    with phases.phase("train 1 chip"):
        _, acc1 = train_eval(stored, params0, data, seed)
    with phases.phase("train 4 chips"):
        _, acc4 = train_eval(stored, params0, data, seed, mesh=mesh)
    gap_pp = abs(acc1 - acc4) * 100
    print(f"data-parallel train: accuracy 1 chip {acc1:.4f}, 4 chips "
          f"{acc4:.4f}, gap {gap_pp:.2f} pp", flush=True)
    require(gap_pp <= ACCURACY_GAP_PP,
            f"1-vs-4-chip accuracy gap {gap_pp:.2f} pp")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's default device is "
              f"{dev.platform!r}); this smoke runs only on a TPU",
              file=sys.stderr)
        return 2
    cache = setup_compile_cache()
    print(f"[smoke, not a benchmark] device {dev.device_kind} x "
          f"{len(jax.devices())}; jax {jax.__version__}; compile cache "
          f"{cache}; minmax_paper D={CONFIG.dim} k={CONFIG.num_hashes} "
          f"b_i={CONFIG.b_i} b_t={CONFIG.b_t}", flush=True)

    phases = Phases()
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(args.seed, phases)
    else:
        run_one_chip(args.seed, phases)
    print(f"[smoke, not a benchmark] total {time.perf_counter() - t0:.1f} s "
          f"wall, {phases.compiles} compiles", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
