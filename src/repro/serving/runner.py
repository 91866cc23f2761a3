"""The serving runner: persistent pre-compiled featurize+score executables.

One ``BucketRunner`` owns one served model — a ``FeaturePipeline`` (the
CWS state: two uint32 key words in regen mode, the (D, k) matrices in
stored mode) plus the linear (F, C) bag table — and the ladder of padded
batch shapes it is willing to launch.  Each bucket compiles ONE fused
featurize+score executable (``FeaturePipeline.scoring_chunk_fn``: the
encode kernel feeding ``bag_logits``/``bag_logits_packed`` inside a
single jit), keyed implicitly by the registry block table (block choice
is a function of the launch shape) and pinned to the pipeline's
``fingerprint()``: a runner serves exactly one feature space, verified at
construction against the table like the trainer does.

``warmup()`` compiles every bucket up front so steady-state traffic never
eats a compile; after it, ``compile_count()`` must equal
``len(buckets)`` forever — the serving twin of the streaming
single-compile invariant, asserted by the compile-discipline tests and
``analysis.compile_guard``.

The chaos plan hooks the dispatch step (site ``"serve_step"``, indexed by
dispatch count) exactly like the trainer's ``"step"`` site, so the chaos
suite can hang or kill the runner under a live gateway and prove the
watchdog + recovery story.
"""
from __future__ import annotations

import time
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.linear_model import LinearParams, validate_bag_features
from repro.kernels import registry
from repro.pipeline import FeaturePipeline

Array = jax.Array

__all__ = ["BucketRunner"]


class BucketRunner:
    def __init__(self, params: LinearParams, pipe: FeaturePipeline, *,
                 buckets: Optional[Sequence[int]] = None,
                 chaos=None, monitor=None):
        validate_bag_features(params, pipe.num_features, spec=pipe.spec)
        self.pipe = pipe
        self.params = params
        fam = registry.cws_family(pipe.source, pipe.emit)
        self.family = fam
        self.buckets: Tuple[int, ...] = tuple(
            sorted(set(int(b) for b in buckets))
            if buckets is not None else registry.serve_buckets(fam))
        if not self.buckets or self.buckets[0] <= 0:
            raise ValueError(f"need positive buckets; got {self.buckets}")
        self.fingerprint = pipe.fingerprint()
        self.n_classes = int(params.b.shape[0])
        self.chaos = chaos
        self.monitor = monitor
        self._fn = pipe.scoring_chunk_fn()
        self._state = pipe._state()
        self._dispatches = 0
        if monitor is not None:
            monitor.gauge("compile_count", self.compile_count)

    @property
    def max_bucket(self) -> int:
        return self.buckets[-1]

    def bucket_for(self, rows: int) -> int:
        """Smallest bucket holding ``rows``; callers split anything
        larger than the top bucket into max-bucket segments first."""
        if rows <= 0 or rows > self.max_bucket:
            raise ValueError(
                f"{rows} rows do not fit the bucket ladder {self.buckets}")
        for b in self.buckets:
            if rows <= b:
                return b
        raise AssertionError("unreachable")

    def compile_count(self) -> int:
        """Executables compiled so far (== len(buckets) after warmup;
        growing past it in steady state means a retrace escaped the
        padding discipline)."""
        return self._fn._cache_size()

    def warmup(self) -> float:
        """Compile every bucket's executable up front (all-zero rows —
        the same pad content live traffic uses) so no request ever pays
        a compile.  Returns the wall seconds spent; after this,
        ``compile_count() == len(buckets)``."""
        t0 = time.perf_counter()
        for b in self.buckets:
            out = self._fn(jnp.zeros((b, self.pipe.dim), jnp.float32),
                           self._state, self.params)
            jax.block_until_ready(out)
        return time.perf_counter() - t0

    def run(self, xb: Array) -> Array:
        """One dispatch: ``xb`` (bucket, D) padded rows -> (bucket, C)
        logits, blocked until ready (serving latency means COMPLETED).
        The chaos hook fires before the launch, indexed by dispatch
        count, mirroring the trainer's per-step site."""
        if xb.shape[0] not in self.buckets:
            raise ValueError(
                f"dispatch shape {xb.shape[0]} is not a bucket of "
                f"{self.buckets}; pad via bucket_for first")
        i = self._dispatches
        self._dispatches += 1
        if self.chaos is not None:
            self.chaos.fire("serve_step", i)
        out = self._fn(xb, self._state, self.params)
        jax.block_until_ready(out)
        return out

    def score(self, x) -> np.ndarray:
        """The runner-local scoring path (no gateway): bucket, pad,
        dispatch, slice — splitting requests larger than the top bucket
        into max-bucket segments.  Bit-identical to the offline
        ``bag_logits(params, pipe.features(x))`` composition: pad rows
        are all-zero, featurize to sentinel -> bucket 0, and are sliced
        off; real rows never see the pad (row-parallel kernels)."""
        x = np.asarray(x, np.float32)
        n = x.shape[0]
        if n == 0:
            return np.zeros((0, self.n_classes), np.float32)
        outs = []
        for lo in range(0, n, self.max_bucket):
            seg = x[lo:lo + self.max_bucket]
            m = seg.shape[0]
            bucket = self.bucket_for(m)
            if bucket > m:
                seg = np.pad(seg, ((0, bucket - m), (0, 0)))
            t0 = time.perf_counter()
            out = self.run(jnp.asarray(seg))
            if self.monitor is not None:
                self.monitor.record_batch(bucket, m,
                                          time.perf_counter() - t0)
            outs.append(np.asarray(out)[:m])
        return np.concatenate(outs, axis=0)
