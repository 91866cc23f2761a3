"""Streaming minibatch training over the featurization pipeline.

The paper's point is that 0-bit CWS lets a LINEAR learner stand in for
the exact min-max kernel machine on data far too large for a Gram matrix
— "b-Bit Minwise Hashing for Large-Scale Linear SVM" is exactly this
regime.  The full-batch ``fit_linear`` contradicts it: it consumes a
materialized (n, k) index matrix, so dataset size re-enters the memory
equation that the embedding-bag layout was designed to keep it out of.

This module is the missing third leg (sample -> encode -> LEARN AT
SCALE): each minibatch is featurized INSIDE the training loop by one
donated pipeline kernel launch (``FeaturePipeline.launch_chunk``), so the
full (n, k) matrix never exists.  Peak working set (DESIGN.md §9):

    O(batch_size * max(D, k))     batch gather + one launch in flight
  + O(F * C)                      the (num_features, n_classes) table
                                  + its Adam moments

— independent of n.  The raw (n, D) rows stay wherever the caller keeps
them (host numpy is fine: the per-batch gather is the only device copy;
device-resident jax.Arrays gather ON DEVICE through one jitted call and
never bounce through host numpy).

Epoch shuffling draws one permutation per epoch from ``shuffle_key``
(ragged remainder dropped — a fresh permutation drops different rows each
epoch); ``batch_size == n`` skips the permutation, since a full-batch
gradient is order-invariant, and is then bit-identical to a full-batch
fit of the same head on the precomputed features (``fit_linear``'s loop;
with the one-hot head, on the local codes).  The update step shares the trainer's microbatch/donation machinery: grads via
``trainer.microbatch_grads`` and (params, opt state) donated on TPU so
Adam updates the table in place.

The head (``_bag_logits_fn``) is the one-hot contraction
``bag_logits_onehot`` on every surface here — the single-chip and the
sharded update, the ``batch_size == n`` path and ``streamed_accuracy``:
the pipeline's indices are block-laid (hash j in rows ``j * width ..``),
so the contraction and its transpose run on the MXU, where a row gather
and its scatter-add move one table row at a time.  Its work per row
grows with the hash width, so specs wider than ``ONEHOT_MAX_WIDTH``
buckets keep the gather, as do the serving head and ``fit_linear``.
The choice follows the spec alone, not the backend, so the CPU tests
run the chip's head (on the CPU the contraction is the slower at every
width: it materializes each row block's one-hot).

Data parallelism (DESIGN.md §11): pass ``mesh=`` to run every per-batch
launch shard_mapped over the mesh's ``data`` axis — each device
featurizes its shard of the minibatch with the pipeline kernel, computes
local grads through the shared ``microbatch_grads`` path, grads/loss are
psum'd inside it, and the optimizer update stays replicated.  What every
step passes in is placed on the mesh ahead of the loop (the launch
state and the table at set-up, the row order once an epoch, the step's
scalars from the host), so no step copies arrays between devices.  On a
1-device mesh this is bit-identical to the unsharded path under the same
``shuffle_key``; on N devices the batch walk is identical and only
gradient summation order differs (float reassociation).

Preemption tolerance (DESIGN.md §13): ``ckpt=``/``ckpt_every=`` stream
``(params, opt_state, stream position, shuffle key, pipeline state,
FeatureSpec fingerprint)`` through the async elastic ``Checkpointer``,
and ``resume_linear_streamed`` continues from the latest committed step
BIT-IDENTICALLY to an uninterrupted run: the per-epoch
``fold_in(shuffle_key, epoch)`` permutation plus the step index fully
determine the batch stream, so no batch is replayed and none skipped.
Restore reshards into the CURRENT mesh (replicated state + a
mesh-independent batch walk), so a run checkpointed at 8 devices resumes
at 4 or 1 — and vice versa — with matching accuracy.  A
``StepWatchdog`` can ride the loop (hung-step detection mid-step), and a
``repro.runtime.chaos.ChaosPlan`` injects deterministic faults for the
chaos tests.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import zlib
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import optim
from repro.checkpoint import Checkpointer, latest_step, restore_checkpoint
from repro.core.hashing import unpack_codes
from repro.core.linear_model import (LinearParams, TrainCfg, _loss_fn,
                                     bag_logits, bag_logits_onehot, init_bag,
                                     make_linear_tx, validate_bag_features)
from repro.kernels import registry
from repro.launch.mesh import data_axis_size
from repro.pipeline import FeaturePipeline
from repro.runtime.fault_tolerance import RetryingTrainer, StepWatchdog
from repro.training.trainer import microbatch_grads

Array = jax.Array

# Host spans in the profiler's trace (``jax.profiler``): inert unless a
# profile is being taken, and then on the same clock as the device ops.
FIT_SPAN = "repro.fit"              # a whole fit or resume call
SETUP_SPAN = "repro.fit.setup"      # everything before the first step
STEP_SPAN = "repro.fit.step"        # one step on the host, its save excluded


def _setup_args(pipe: FeaturePipeline, cfg: TrainCfg, mesh) -> dict:
    """The setup span's arguments: ``shards``, the devices the fit splits
    each batch over (the mesh's ``data`` axis; 1 without a mesh), and
    ``cws_loop_share``, the share of the padded dimension loop that the
    kernel runs in each device's launch of a step."""
    shards = 1 if mesh is None else data_axis_size(mesh)
    return {"shards": shards, "cws_loop_share":
            pipe.loop_share(max(cfg.batch_size // shards, 1))}


__all__ = ["fit_linear_streamed", "resume_linear_streamed",
           "fit_linear_streamed_resilient", "streamed_accuracy",
           "resume_streamed_accuracy", "export_served_model"]


# widest hash block the trainer contracts.  The contraction's work per
# row grows with k * width and the gather's with k alone; on a TPU v5e,
# at k = 256 and 512 rows, the update step takes 12.7 against 19.8 ms at
# 2^14 buckets and 26.3 against 23.7 ms at 2^15, so wider specs gather
ONEHOT_MAX_WIDTH = 1 << 14


def _bag_logits_fn(pipe: FeaturePipeline):
    """The trainer's logits head for the pipeline's output format (see
    the module docstring for which surfaces contract and which gather).

    Up to ``ONEHOT_MAX_WIDTH`` buckets a hash, the head takes local
    codes — ``idx - j * width`` of the block-laid indices, or the
    unpacked words of a ``spec.packed`` pipeline — and runs
    ``bag_logits_onehot``.  Packed and unpacked training at the same
    (b_i, b_t) are bit-identical: the local codes match, so every float
    op matches.  Wider (unpacked) specs gather with ``bag_logits``;
    packed words hold at most 8 bits, so they always contract."""
    spec = pipe.spec
    if spec.width > ONEHOT_MAX_WIDTH:
        return bag_logits
    k = spec.num_hashes
    if getattr(spec, "packed", False):
        def local_codes(fb):
            return unpack_codes(fb, k, b=spec.bits)
    else:
        def local_codes(fb):
            # the clamp keeps the subtraction inside int32 for any input
            offs = jnp.arange(k, dtype=jnp.int32) * spec.width
            return fb.astype(jnp.int32).clip(0, k * spec.width - 1) - offs

    def logits(params, fb):
        return bag_logits_onehot(params, local_codes(fb))

    return logits


def _make_update_step(cfg: TrainCfg, tx, n_micro: int, logits_fn):
    """One donated jitted update on a featurized minibatch — the bag
    head riding the trainer's microbatch/donation machinery."""
    donate = registry.donate_argnums(0, 1)

    def loss_fn(p, inputs, labels):
        return _loss_fn(p, inputs, labels, cfg, logits_fn), {}

    @functools.partial(jax.jit, donate_argnums=donate)
    def update(params, state, fb, yb, i):
        loss, _, grads = microbatch_grads(
            loss_fn, params, {"inputs": fb, "labels": yb}, n_micro=n_micro)
        updates, state = tx.update(grads, state, params, i)
        return optim.apply_updates(params, updates), state, loss

    return update


def _make_sharded_update_step(cfg: TrainCfg, tx, n_micro: int,
                              pipe: FeaturePipeline, mesh, *,
                              featurize: bool):
    """The data-parallel update: ONE jitted launch per step that
    shard_maps featurize+grads over the ``data`` axis and applies the
    optimizer on the psum'd grads, replicated.

    ``featurize=True`` takes the raw (bs, D) batch and runs the pipeline
    kernel per shard (the per-step path); ``featurize=False`` takes
    precomputed (bs, k) indices (the order-invariant batch_size == n
    path, featurized once up front and REUSED across steps — so the
    batch must NOT be donated there).  (params, opt state) are donated
    on TPU, plus the per-step gather buffer when featurizing; the
    pipeline's launch state rides along replicated and is never
    donated."""
    donate = (registry.donate_argnums(0, 1, 3) if featurize
              else registry.donate_argnums(0, 1))
    logits_fn = _bag_logits_fn(pipe)

    def loss_fn(p, inputs, labels):
        return _loss_fn(p, inputs, labels, cfg, logits_fn), {}

    def local_grads(params, pstate, xb, yb):
        fb = pipe._launch_with(xb, pstate) if featurize else xb
        # psum of loss/grads happens INSIDE the shared helper so the
        # data-parallel all-reduce sits at one blessed point
        loss, _, grads = microbatch_grads(
            loss_fn, params, {"inputs": fb, "labels": yb},
            n_micro=n_micro, axis_name="data")
        return loss, grads

    grads_fn = jax.shard_map(
        local_grads, mesh=mesh,
        in_specs=(P(), pipe.state_pspec(), P("data", None), P("data")),
        out_specs=(P(), P()),
        check_vma=False,
    )

    @functools.partial(jax.jit, donate_argnums=donate)
    def update(params, state, pstate, xb, yb, i):
        _, grads = grads_fn(params, pstate, xb, yb)
        updates, state = tx.update(grads, state, params, i)
        return optim.apply_updates(params, updates), state

    return update


def _make_device_gather(bs: int, mesh):
    """One jitted per-batch gather for device-resident datasets: slice
    the epoch permutation window and take rows/labels in a single
    dispatch.  With a mesh the outputs land ALREADY SHARDED over
    ``data`` (no host bounce, no post-hoc reshard)."""
    kw = {}
    if mesh is not None:
        kw["out_shardings"] = (NamedSharding(mesh, P("data", None)),
                               NamedSharding(mesh, P("data")))

    @functools.partial(jax.jit, **kw)
    def gather(x, labels, perm, pos):
        idx = jax.lax.dynamic_slice_in_dim(perm, pos * bs, bs)
        return jnp.take(x, idx, axis=0), jnp.take(labels, idx, axis=0)

    return gather


# -- checkpoint helpers ------------------------------------------------


def _as_checkpointer(ckpt, chaos=None) -> Checkpointer:
    if isinstance(ckpt, Checkpointer):
        return ckpt
    return Checkpointer(ckpt, chaos=chaos)


def _key_data_list(key) -> list:
    """PRNG key -> JSON-able uint32 words (old-style uint32 key arrays;
    typed keys unwrap through jax.random.key_data)."""
    try:
        if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
            key = jax.random.key_data(key)
    except (AttributeError, TypeError):
        pass
    return np.asarray(key, np.uint32).tolist()


def _params_digest(tree) -> str:
    data = b"".join(np.asarray(a).tobytes()
                    for a in jax.tree_util.tree_leaves(tree))
    return f"{zlib.crc32(data) & 0xFFFFFFFF:08x}"


def _check_match(what: str, stored, current) -> None:
    if stored != current:
        raise ValueError(
            f"checkpoint {what} mismatch: resume must replay the exact "
            f"run that was checkpointed.\n  checkpointed: {stored}\n"
            f"  current:      {current}")


def _guard_fresh_dir(ck: Checkpointer, resume_fn: str) -> None:
    existing = latest_step(ck.ckpt_dir)
    if existing is not None:
        raise ValueError(
            f"checkpoint dir {ck.ckpt_dir} already holds committed step "
            f"{existing}; a fresh fit would interleave its step numbers "
            f"with the old run's. Use {resume_fn} to continue it, or "
            f"point ckpt= at a fresh directory")


class _StreamSetup:
    """Everything the streamed loop needs, derived ONCE from the call
    arguments (all validation lives here) — shared by fresh fits
    (``fit_linear_streamed``) and resumes (``resume_linear_streamed``),
    which is what makes the two paths provably walk the same stream."""

    def __init__(self, pipe: FeaturePipeline, x: Array, labels: Array,
                 cfg: TrainCfg, shuffle_key, n_microbatches: int, mesh):
        n = x.shape[0]
        bs = cfg.batch_size
        if bs <= 0:
            raise ValueError(
                "fit_linear_streamed needs batch_size in [1, n]; "
                "batch_size=0 is the explicit full-batch fit_linear path "
                "(which materializes the full (n, k) index matrix)")
        if bs > n:
            raise ValueError(
                f"batch_size {bs} exceeds the {n} available rows")
        ndev = 1 if mesh is None else data_axis_size(mesh)
        if bs % ndev:
            raise ValueError(
                f"batch_size {bs} must divide by the mesh data axis "
                f"({ndev}) so every device sees the same local batch shape")
        local_bs = bs // ndev
        if n_microbatches < 1 or local_bs % n_microbatches:
            raise ValueError(f"per-device batch {local_bs} must divide "
                             f"into {n_microbatches} microbatches")
        if labels.shape[0] != n:
            raise ValueError(
                f"labels {labels.shape} do not match x {x.shape}")

        self.pipe, self.x, self.labels = pipe, x, labels
        self.cfg, self.mesh, self.n, self.bs = cfg, mesh, n, bs
        self.n_micro = n_microbatches
        self.tx = make_linear_tx(cfg)
        self.steps_per_epoch = max(n // bs, 1)
        self.key = (shuffle_key if shuffle_key is not None
                    else jax.random.PRNGKey(0))
        self.shuffle = bs < n

        # host-resident datasets (numpy/memmap) are gathered on the HOST
        # so only the (bs, D) batch ever crosses to the device; jax-array
        # datasets gather on device (one jitted call per batch, sharded
        # outputs under a mesh).
        self.host_data = not isinstance(x, jax.Array)
        self.labels_host = None
        self.batch_shardings = None
        self.gather = None
        if self.host_data and self.shuffle:
            self.labels_host = np.asarray(labels)
            self.batch_shardings = None if mesh is None else (
                NamedSharding(mesh, P("data", None)),
                NamedSharding(mesh, P("data")))
        elif self.shuffle:
            self.labels = jnp.asarray(labels)
            self.gather = _make_device_gather(bs, mesh)

        # what every step passes in is placed on the mesh once: an array
        # left on one device is copied to every other at each dispatch
        self.replicated = None if mesh is None else NamedSharding(mesh, P())
        if mesh is None:
            self.update = _make_update_step(cfg, self.tx, n_microbatches,
                                            _bag_logits_fn(pipe))
            self.pstate = None
        else:
            self.update = _make_sharded_update_step(
                cfg, self.tx, n_microbatches, pipe, mesh,
                featurize=self.shuffle)
            self.pstate = jax.device_put(
                pipe._state(), NamedSharding(mesh, pipe.state_pspec()))

        self.fb_full = self.yb_full = None
        if not self.shuffle:
            # batch_size == n: the gradient is order-invariant, so skip
            # the permutation AND per-step re-featurization — one launch
            # sweep up front (peak (bs, k) = (n, k) is what bs = n asks
            # for).  Deterministic, so a resume recomputes it exactly.
            self.fb_full = pipe.features(
                jnp.asarray(x) if self.host_data else x, mesh=mesh)
            self.yb_full = jnp.asarray(labels)
            if mesh is not None:
                self.yb_full = jax.device_put(
                    self.yb_full, NamedSharding(mesh, P("data")))

    def permutation(self, epoch: int):
        """The epoch's row order (on the mesh, replicated once an epoch)."""
        perm = jax.random.permutation(jax.random.fold_in(self.key, epoch),
                                      self.n)
        if self.replicated is None:
            return perm
        return jax.device_put(perm, self.replicated)

    def scalar(self, v: int):
        """A step's int32 argument (on the mesh, put on every device from
        the host rather than made on one and copied to the others)."""
        if self.replicated is None:
            return jnp.int32(v)
        return jax.device_put(np.int32(v), self.replicated)

    # -- the checkpoint payload ----------------------------------------

    def ckpt_tree(self, params, state) -> dict:
        """(params, opt state, pipeline key-or-params): the full model
        state.  The stream POSITION rides in ``extra`` (host metadata)."""
        return {"params": params, "opt_state": state,
                "pipeline": self.pipe._state()}

    def ckpt_extra(self, next_step: int) -> dict:
        return {"stream": {
            "next_step": int(next_step),
            "shuffle_key": _key_data_list(self.key),
            "fingerprint": self.pipe.fingerprint(),
            "cfg": dataclasses.asdict(self.cfg),
            "n": int(self.n),
            "n_microbatches": int(self.n_micro),
        }}

    def template(self):
        """ShapeDtypeStruct tree for elastic restore: rebuilt from
        (pipe, cfg) alone, so resume needs no pickled objects."""
        p0 = init_bag(jax.random.PRNGKey(0), self.pipe.num_features,
                      self.cfg.n_classes)
        tree = {"params": p0, "opt_state": self.tx.init(p0)}
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

    def shardings(self):
        """(params, opt state) are REPLICATED in this trainer on every
        mesh — the elastic part of a reshard is that the restore targets
        whatever devices exist now."""
        if self.mesh is None:
            return None
        rep = NamedSharding(self.mesh, P())
        return jax.tree_util.tree_map(lambda _: rep, self.template())


def _stream_loop(S: _StreamSetup, params: LinearParams, state, start: int,
                 *, ckpt: Optional[Checkpointer], ckpt_every: int,
                 watchdog: Optional[StepWatchdog], chaos,
                 return_state: bool):
    """Run update steps ``start .. cfg.steps`` — THE loop behind both
    fresh fits and resumes.  The per-epoch permutation is re-derived from
    ``(shuffle_key, epoch)`` at entry, so starting mid-epoch walks the
    exact batches an uninterrupted run would have walked."""
    cfg, pipe, mesh = S.cfg, S.pipe, S.mesh
    perm = perm_host = None
    cur_epoch = -1
    try:
        for i in range(start, cfg.steps):
            epoch, pos = divmod(i, S.steps_per_epoch)
            with jax.profiler.StepTraceAnnotation(STEP_SPAN, step_num=i):
                if watchdog is not None:
                    watchdog.start_step(i)
                try:
                    if chaos is not None:
                        chaos.fire("step", i)
                    if S.shuffle:
                        if epoch != cur_epoch:
                            perm = S.permutation(epoch)
                            if S.host_data:
                                perm_host = np.asarray(perm)
                            cur_epoch = epoch
                        if S.host_data:
                            sel = perm_host[pos * S.bs:(pos + 1) * S.bs]
                            xb, yb = S.x[sel], S.labels_host[sel]
                            if mesh is None:
                                xb, yb = jnp.asarray(xb), jnp.asarray(yb)
                            else:
                                # one host->device hop into the data layout
                                xb = jax.device_put(xb, S.batch_shardings[0])
                                yb = jax.device_put(yb, S.batch_shardings[1])
                        else:
                            xb, yb = S.gather(S.x, S.labels, perm,
                                              S.scalar(pos))
                        if mesh is None:
                            # the gather buffer is ours alone -> safe to
                            # donate to the featurization launch
                            fb = pipe.launch_chunk(xb)
                            params, state, _ = S.update(params, state, fb, yb,
                                                        S.scalar(i))
                        else:
                            # sharded: featurize runs INSIDE the shard_map
                            params, state = S.update(params, state, S.pstate,
                                                     xb, yb, S.scalar(i))
                    elif mesh is None:
                        params, state, _ = S.update(params, state, S.fb_full,
                                                    S.yb_full, S.scalar(i))
                    else:
                        params, state = S.update(params, state, S.pstate,
                                                 S.fb_full, S.yb_full,
                                                 S.scalar(i))
                    if watchdog is not None:
                        jax.block_until_ready(params)
                except KeyboardInterrupt as e:
                    # the watchdog monitor interrupts a hung step with
                    # SIGINT; convert to the abort signal (a real Ctrl-C,
                    # with no fired timeout, re-raises untouched)
                    if watchdog is not None:
                        watchdog.reraise_if_fired(e)
                    raise
                if watchdog is not None:
                    watchdog.end_step()
            done = i + 1
            if (ckpt is not None and ckpt_every > 0
                    and (done % ckpt_every == 0 or done == cfg.steps)):
                ckpt.save_async(done, S.ckpt_tree(params, state),
                                extra=S.ckpt_extra(done))
        if ckpt is not None:
            ckpt.wait()   # surface any trailing async write error loudly
    finally:
        if watchdog is not None:
            watchdog.stop()
    return (params, state) if return_state else params


def fit_linear_streamed(params: LinearParams, pipe: FeaturePipeline,
                        x: Array, labels: Array, *, cfg: TrainCfg,
                        shuffle_key: Optional[Array] = None,
                        n_microbatches: int = 1,
                        mesh=None,
                        ckpt=None, ckpt_every: int = 0,
                        watchdog: Optional[StepWatchdog] = None,
                        chaos=None,
                        return_state: bool = False) -> LinearParams:
    """Minibatch SGD with featurization fused into the loop.

    ``x`` (n, D) raw nonneg rows; ``params`` a flat bag table built with
    ``init_bag(key, pipe.num_features, n_classes)`` (validated here at
    build time — see validate_bag_features).  ``cfg.steps`` counts
    updates; ``cfg.batch_size`` must be in [1, n] — batch_size=0 (the
    explicit full-batch path) belongs to ``fit_linear``, which this
    function matches bit-for-bit at ``batch_size == n``.

    Every batch launches the SAME (batch_size, D) chunk shape, so the
    featurization kernel compiles exactly once per fit.

    ``mesh=`` runs the whole per-batch hot loop data-parallel: the batch
    gather lands sharded over the ``data`` axis, each device featurizes
    and differentiates its shard, grads are psum'd, and the optimizer
    update is replicated.  ``batch_size`` must divide by the data-axis
    size (each device sees a fixed local batch shape).

    ``ckpt=`` (a ``Checkpointer`` or a directory) with ``ckpt_every=N``
    async-saves the full training state every N steps (and at the end);
    ``resume_linear_streamed`` continues such a run bit-identically —
    on ANY device count.  The target directory must be fresh (a dir
    holding committed steps means you want resume).  ``watchdog=`` arms
    a StepWatchdog around every step (its background monitor catches
    hung steps mid-flight); ``chaos=`` threads a deterministic fault
    plan through the step path (tests).  ``return_state=True`` returns
    ``(params, opt_state)`` instead of params alone."""
    with jax.profiler.TraceAnnotation(FIT_SPAN):
        with jax.profiler.TraceAnnotation(SETUP_SPAN,
                                          **_setup_args(pipe, cfg, mesh)):
            validate_bag_features(params, pipe.num_features, spec=pipe.spec)
            S = _StreamSetup(pipe, x, labels, cfg, shuffle_key,
                             n_microbatches, mesh)
            ck = _as_checkpointer(ckpt, chaos) if ckpt is not None else None
            if ck is not None and ckpt_every > 0:
                _guard_fresh_dir(ck, "resume_linear_streamed")
            state = S.tx.init(params)
            if S.replicated is not None:
                params, state = jax.device_put((params, state), S.replicated)
            if registry.on_tpu():
                # the update step donates (params, state); the first call
                # would otherwise donate — and delete — the CALLER's init
                # table
                params = jax.tree_util.tree_map(jnp.copy, params)
        return _stream_loop(S, params, state, 0, ckpt=ck,
                            ckpt_every=ckpt_every, watchdog=watchdog,
                            chaos=chaos, return_state=return_state)


def resume_linear_streamed(ckpt, pipe: FeaturePipeline, x: Array,
                           labels: Array, *, cfg: TrainCfg,
                           shuffle_key: Optional[Array] = None,
                           n_microbatches: int = 1,
                           mesh=None,
                           step: Optional[int] = None,
                           ckpt_every: int = 0,
                           watchdog: Optional[StepWatchdog] = None,
                           chaos=None,
                           return_state: bool = False) -> LinearParams:
    """Continue a checkpointed ``fit_linear_streamed`` run from its
    latest committed step (or an explicit ``step=``), BIT-IDENTICALLY to
    the run never having been interrupted.

    Why bit-identity holds: the checkpoint carries ``(params, opt_state)``
    exactly (fp32 round-trips losslessly through the shard files) plus
    the stream position and shuffle key; the batch walk is a pure
    function of ``(shuffle_key, epoch, step)`` — the per-epoch
    ``fold_in`` permutation is re-derived, never stored half-consumed —
    so step ``s`` of the resumed run consumes the same rows with the
    same state as step ``s`` of an uninterrupted one.  No batch is
    replayed against the wrong params and none is skipped.

    ELASTIC: restore reshards into the CURRENT mesh (the checkpoint
    stores global arrays, not device layouts), so a run checkpointed at
    8 devices resumes at 4 or 1 — or the reverse.  Across a device-count
    change only psum summation order differs (float reassociation);
    at the SAME device count the final params are bit-identical.

    Guards: the checkpoint's FeatureSpec fingerprint (spec + dim + a
    digest of the CWS parameters/key), TrainCfg, dataset row count,
    microbatching, and shuffle key (if one is passed) must all match
    the checkpointed run — each mismatch raises loudly instead of
    resuming into silent garbage."""
    with jax.profiler.TraceAnnotation(FIT_SPAN):
        with jax.profiler.TraceAnnotation(SETUP_SPAN,
                                          **_setup_args(pipe, cfg, mesh)):
            ck = _as_checkpointer(ckpt, chaos)
            target = latest_step(ck.ckpt_dir) if step is None else step
            if target is None:
                raise FileNotFoundError(
                    f"no committed checkpoint under {ck.ckpt_dir}; start "
                    f"with fit_linear_streamed(..., ckpt=, ckpt_every=)")
            manifest = json.loads((ck.ckpt_dir / f"step_{target:08d}"
                                   / "manifest.json").read_text())
            stream = manifest.get("extra", {}).get("stream")
            if stream is None:
                raise ValueError(
                    f"checkpoint step {target} under {ck.ckpt_dir} carries "
                    f"no stream state — not a fit_linear_streamed "
                    f"checkpoint")

            _check_match("pipeline fingerprint", stream["fingerprint"],
                         pipe.fingerprint())
            _check_match("TrainCfg", stream["cfg"], dataclasses.asdict(cfg))
            _check_match("dataset rows", stream["n"], int(x.shape[0]))
            _check_match("n_microbatches", stream["n_microbatches"],
                         int(n_microbatches))
            stored_key = jnp.asarray(
                np.asarray(stream["shuffle_key"], np.uint32))
            if shuffle_key is not None:
                _check_match("shuffle_key", stream["shuffle_key"],
                             _key_data_list(shuffle_key))

            S = _StreamSetup(pipe, x, labels, cfg, stored_key,
                             n_microbatches, mesh)
            restored = restore_checkpoint(ck.ckpt_dir, target, S.template(),
                                          shardings=S.shardings())
        return _stream_loop(S, restored["params"], restored["opt_state"],
                            int(stream["next_step"]), ckpt=ck,
                            ckpt_every=ckpt_every, watchdog=watchdog,
                            chaos=chaos, return_state=return_state)


def fit_linear_streamed_resilient(params: LinearParams,
                                  pipe: FeaturePipeline, x: Array,
                                  labels: Array, *, cfg: TrainCfg,
                                  ckpt, ckpt_every: int,
                                  shuffle_key: Optional[Array] = None,
                                  n_microbatches: int = 1,
                                  mesh=None,
                                  trainer: Optional[RetryingTrainer] = None,
                                  hard_timeout_s: float = 0.0,
                                  chaos=None,
                                  return_state: bool = False):
    """The preemption-grade wrapper: checkpointed streamed training under
    the RetryingTrainer restart loop and (optionally) a hard-timeout
    StepWatchdog.

    Each attempt restores from the latest committed checkpoint if one
    exists (else starts fresh), so it survives in-process software
    faults (step exceptions, hung steps aborted by the watchdog, failed
    async checkpoint writes) with exponential backoff and a structured
    restart log — pass your own ``trainer=RetryingTrainer(...)`` to
    control backoff and read ``trainer.restart_log`` afterwards.  It
    also survives PROCESS death by construction: call it again in the
    new process (same ``ckpt`` dir) and it resumes where the old one
    committed — even on a different device count."""
    ck = _as_checkpointer(ckpt, chaos)
    trainer = trainer or RetryingTrainer()

    def attempt():
        wd = (StepWatchdog(hard_timeout_s=hard_timeout_s)
              if hard_timeout_s > 0 else None)
        try:
            if latest_step(ck.ckpt_dir) is None:
                return fit_linear_streamed(
                    params, pipe, x, labels, cfg=cfg,
                    shuffle_key=shuffle_key, n_microbatches=n_microbatches,
                    mesh=mesh, ckpt=ck, ckpt_every=ckpt_every, watchdog=wd,
                    chaos=chaos, return_state=return_state)
            return resume_linear_streamed(
                ck, pipe, x, labels, cfg=cfg, shuffle_key=shuffle_key,
                n_microbatches=n_microbatches, mesh=mesh,
                ckpt_every=ckpt_every, watchdog=wd, chaos=chaos,
                return_state=return_state)
        finally:
            if wd is not None:
                wd.stop()

    return trainer.call(attempt)


def export_served_model(params: LinearParams, pipe: FeaturePipeline,
                        path) -> None:
    """Hand a trained ``(params, pipe)`` pair to the serving stack: write
    a ``repro.serving`` bundle directory — the linear (F, C) table + the
    spec fingerprint + the CWS key words (regen mode) or matrices — that
    ``ServingService.from_bundle``/``launch/serve.py --bundle`` boots a
    replica from.  The trainer owns this hop so the fingerprint stamped
    into the bundle is the SAME one its checkpoints carry: train, resume,
    and serve all pin one feature space."""
    from repro.serving.bundle import save_bundle
    save_bundle(path, params, pipe)


def streamed_accuracy(params: LinearParams, pipe: FeaturePipeline,
                      x: Array, labels: Array, *, mesh=None,
                      ckpt=None, ckpt_every: int = 0,
                      chaos=None) -> float:
    """Accuracy over pipeline features without materializing (n, k):
    walks ``pipe.feature_chunks`` and accumulates correct counts.  With
    ``mesh=`` each chunk launch is shard_mapped over ``data`` (same
    chunk walk, so the count — an integer — is identical).  Scores
    through the trainer's head (``_bag_logits_fn``); packed chunks stay
    uint32 words end to end.

    ``ckpt=``/``ckpt_every=N`` (chunks) checkpoint the partial count +
    stream position so ``resume_streamed_accuracy`` can finish a killed
    evaluation exactly (featurization is per-row deterministic, so the
    remaining rows score identically under any chunking).  Use a
    directory separate from the training checkpoints — eval steps are
    chunk indices."""
    validate_bag_features(params, pipe.num_features, spec=pipe.spec)
    ck = _as_checkpointer(ckpt, chaos) if ckpt is not None else None
    if ck is not None and ckpt_every > 0:
        _guard_fresh_dir(ck, "resume_streamed_accuracy")
    n = x.shape[0]
    if n == 0:
        return 0.0
    return _eval_loop(params, pipe, x, labels, mesh=mesh, ck=ck,
                      ckpt_every=ckpt_every, chaos=chaos,
                      base_lo=0, base_chunk=0, correct=jnp.int32(0),
                      total=n)


def _eval_loop(params, pipe, x, labels, *, mesh, ck, ckpt_every, chaos,
               base_lo, base_chunk, correct, total) -> float:
    """Walk (and score) ``x`` chunk by chunk, counting from ``correct``;
    positions in checkpoints are GLOBAL (offset by base_lo/base_chunk)."""
    logits_fn = _bag_logits_fn(pipe)
    labels = jnp.asarray(labels)
    fingerprint = pipe.fingerprint()
    table_digest = _params_digest(params)
    # accumulate on device: a host int() per chunk would serialize each
    # chunk's compute against the next chunk's dispatch
    for c, (lo, hi, fb) in enumerate(pipe.feature_chunks(x, mesh=mesh)):
        if chaos is not None:
            chaos.fire("eval_chunk", base_chunk + c)
        pred = jnp.argmax(logits_fn(params, fb), axis=-1)
        correct = correct + jnp.sum((pred == labels[lo:hi])
                                    .astype(jnp.int32))
        done = c + 1
        if (ck is not None and ckpt_every > 0 and hi > lo
                and (done % ckpt_every == 0)):
            ck.save_async(base_chunk + done, {"correct": correct},
                          extra={"eval": {
                              "next_lo": int(base_lo + hi),
                              "next_chunk": int(base_chunk + done),
                              "n": int(total),
                              "fingerprint": fingerprint,
                              "table_digest": table_digest,
                          }})
    if ck is not None:
        ck.wait()
    return int(correct) / total


def resume_streamed_accuracy(ckpt, params: LinearParams,
                             pipe: FeaturePipeline, x: Array,
                             labels: Array, *, mesh=None,
                             chaos=None) -> float:
    """Finish a killed ``streamed_accuracy(ckpt=...)`` run: restores the
    committed partial count and scores only the remaining rows.  Exact —
    featurization and scoring are per-row deterministic, so the answer
    equals the uninterrupted one regardless of where the kill landed.
    Guards fingerprint, table digest, and row count like the trainer."""
    validate_bag_features(params, pipe.num_features, spec=pipe.spec)
    ck = _as_checkpointer(ckpt, chaos)
    target = latest_step(ck.ckpt_dir)
    if target is None:
        raise FileNotFoundError(
            f"no committed eval checkpoint under {ck.ckpt_dir}")
    manifest = json.loads(
        (ck.ckpt_dir / f"step_{target:08d}" / "manifest.json").read_text())
    ev = manifest.get("extra", {}).get("eval")
    if ev is None:
        raise ValueError(
            f"checkpoint step {target} under {ck.ckpt_dir} carries no "
            f"eval state — not a streamed_accuracy checkpoint")
    _check_match("pipeline fingerprint", ev["fingerprint"],
                 pipe.fingerprint())
    _check_match("table digest", ev["table_digest"],
                 _params_digest(params))
    _check_match("dataset rows", ev["n"], int(x.shape[0]))
    restored = restore_checkpoint(
        ck.ckpt_dir, target,
        {"correct": jax.ShapeDtypeStruct((), jnp.int32)})
    lo = int(ev["next_lo"])
    n = int(ev["n"])
    if lo >= n:
        return int(restored["correct"]) / n
    return _eval_loop(params, pipe, x[lo:], labels[lo:], mesh=mesh,
                      ck=None, ckpt_every=0, chaos=chaos, base_lo=lo,
                      base_chunk=int(ev["next_chunk"]),
                      correct=restored["correct"], total=n)


# ---------------------------------------------------------------------------
# analysis sites (repro.analysis / tools/kernel_lint.py)
# ---------------------------------------------------------------------------
# The trainer's donating/shard_mapped update steps, registered for the
# donation and collective lints.  Builders construct a tiny pipeline +
# optimizer; args are ShapeDtypeStructs where possible so auditing never
# materializes a batch or compiles a step.

def _analysis_setup(mesh=None):
    from repro.pipeline import FeatureSpec
    pipe = FeaturePipeline.create_regen(
        jax.random.PRNGKey(0), 16, FeatureSpec(num_hashes=16, b_i=2),
        row_chunk=8)
    ndev = 1 if mesh is None else data_axis_size(mesh)
    cfg = TrainCfg(n_classes=3, steps=4, batch_size=2 * ndev)
    tx = make_linear_tx(cfg)
    params = init_bag(jax.random.PRNGKey(1), pipe.num_features,
                      cfg.n_classes)
    return pipe, cfg, tx, params


@registry.register_donation_site("trainer.update_step")
def _donation_site_update_step():
    with registry.force_donation():
        pipe, cfg, tx, params = _analysis_setup()
        step = _make_update_step(cfg, tx, 1, _bag_logits_fn(pipe))
    state = tx.init(params)
    fb = jax.ShapeDtypeStruct((cfg.batch_size, pipe.spec.num_hashes),
                              jnp.int32)
    yb = jax.ShapeDtypeStruct((cfg.batch_size,), jnp.int32)
    i = jnp.zeros((), jnp.int32)
    return {"fn": lambda *a: step(*a), "args": (params, state, fb, yb, i),
            "donate_argnums": (0, 1)}


@registry.register_numerics_site("trainer.grad_accum")
def _numerics_site_grad_accum():
    # n_micro=2 so the microbatch gradient accumulator appears as a real
    # scan carry — the dtype-flow check pins it to float32.  The bag
    # head's backward is a one-hot contraction, not a scatter: nothing is
    # blessed, so a float scatter-add coming back into the update fails.
    pipe, cfg, tx, params = _analysis_setup()
    step = _make_update_step(cfg, tx, 2, _bag_logits_fn(pipe))
    state = tx.init(params)
    fb = jax.ShapeDtypeStruct((cfg.batch_size, pipe.spec.num_hashes),
                              jnp.int32)
    yb = jax.ShapeDtypeStruct((cfg.batch_size,), jnp.int32)
    i = jnp.zeros((), jnp.int32)
    return {"fn": lambda *a: step(*a), "args": (params, state, fb, yb, i)}


@registry.register_collective_site("trainer.sharded_update")
def _collective_site_sharded_update():
    from repro.launch.mesh import make_data_mesh
    mesh = make_data_mesh()
    with registry.force_donation():
        pipe, cfg, tx, params = _analysis_setup(mesh)
        step = _make_sharded_update_step(cfg, tx, 1, pipe, mesh,
                                         featurize=True)
    state = tx.init(params)
    xb = jax.ShapeDtypeStruct((cfg.batch_size, pipe.dim), jnp.float32)
    yb = jax.ShapeDtypeStruct((cfg.batch_size,), jnp.int32)
    i = jnp.zeros((), jnp.int32)
    # the blessed-point contract: ONE psum per grad leaf plus one for the
    # loss, all inside microbatch_grads, all over the data axis
    n_grad_leaves = len(jax.tree_util.tree_leaves(params))
    return {"fn": lambda *a: step(*a),
            "args": (params, state, pipe._state(), xb, yb, i),
            "expected_psums": n_grad_leaves + 1,
            "expected_axes": ("data",)}
