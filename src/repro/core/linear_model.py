"""Linear classifiers for (a) dense features and (b) CWS-hashed features.

The hashed dataset (k hashes, each a one-hot over 2^{b_i+b_t} buckets) is an
embedding-bag: logits_c = sum_j W_c[j, code_j] + b_c.  This is the exact
structure of a vocab-sharded embedding table, so at scale W shards over the
`model` mesh axis (width dim) and the batch over `data`, reusing the LM
sharding rules.

Two heads compute it.  ``hashed_logits``, ``bag_logits`` and
``bag_logits_packed`` gather table rows (their backward is a scatter-add):
they take arbitrary indices under a clamp policy, and serve the
forward-only scoring paths and ``fit_linear``.  ``bag_logits_onehot``
contracts a one-hot over hash blocks with the table on the MXU, and its
backward is the same contraction transposed: the streamed trainer's head
(repro.training.linear_trainer) up to its ``ONEHOT_MAX_WIDTH`` buckets a
hash, where XLA's TPU gather and scatter-add move one table row at a time
and the backward sorts its indices.  Both
select the same rows exactly; f32 sums differ only in order.

Losses: multiclass squared hinge (one-vs-rest, matching the paper's
LIBLINEAR L2-loss setting) or softmax cross-entropy.  l2 reg corresponds to
1/(2C) * ||W||^2, so C sweeps map to the paper's C grid.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import optim

Array = jax.Array


class LinearParams(NamedTuple):
    w: Array  # dense: (D, C); hashed: (k, width, C)
    b: Array  # (C,)


def init_dense(key: Array, dim: int, n_classes: int) -> LinearParams:
    return LinearParams(jnp.zeros((dim, n_classes), jnp.float32),
                        jnp.zeros((n_classes,), jnp.float32))


def init_hashed(key: Array, k: int, width: int, n_classes: int) -> LinearParams:
    return LinearParams(jnp.zeros((k, width, n_classes), jnp.float32),
                        jnp.zeros((n_classes,), jnp.float32))


def init_bag(key: Array, num_features: int, n_classes: int) -> LinearParams:
    """Flat embedding-bag table (F, C) for pipeline feature indices
    (F = k * 2^{b_i+b_t}); the (k, width, C) 'hashed' layout reshaped."""
    return LinearParams(jnp.zeros((num_features, n_classes), jnp.float32),
                        jnp.zeros((n_classes,), jnp.float32))


def dense_logits(params: LinearParams, x: Array) -> Array:
    return x @ params.w + params.b


def hashed_logits(params: LinearParams, codes: Array) -> Array:
    """codes: (n, k) int32 bucket ids in [0, width). Embedding-bag gather.

    Index policy (deliberate, tested in test_linear_stream.py): sentinel
    codes (-1, emitted by ``encode`` for all-zero rows) clamp to bucket 0
    — the SAME convention the fused pipeline bakes into its indices, so
    an all-zero row is featurized identically on both surfaces (it
    aliases a real bucket-0 hit; the paper's scheme has no reserved
    empty bucket).  Codes >= width (a spec/params mismatch) clamp to
    width-1 instead of hitting XLA's implementation-defined OOB gather
    behavior; catch mismatches loudly with validate_bag_features."""
    width = params.w.shape[1]
    # (n, k, C) <- W[j, codes[:, j], :]
    gathered = jnp.take_along_axis(
        params.w[None],                      # (1, k, width, C)
        codes[:, :, None, None].astype(jnp.int32).clip(0, width - 1),
        axis=2,
    )[:, :, 0, :]
    return gathered.sum(axis=1) + params.b


def _bag_sum(rows: Array) -> Array:
    """(n, k, C) gathered rows -> (n, C), summed over k in one fixed
    pairwise order (zero-padded to a power of two, then halved).

    A plain ``sum(axis=1)`` leaves the order to the compiler, which picks
    it per program: the fused serving executable and the offline
    ``features`` -> ``bag_logits`` composition then differ in the last
    bit.  Elementwise adds in a fixed tree compile to the same result in
    any program, on any backend."""
    k = rows.shape[1]
    p = 1 << max(k - 1, 0).bit_length()
    rows = jnp.pad(rows, ((0, 0), (0, p - k), (0, 0)))
    while rows.shape[1] > 1:
        h = rows.shape[1] // 2
        rows = rows[:, :h] + rows[:, h:]
    return rows[:, 0]


def bag_logits(params: LinearParams, idx: Array) -> Array:
    """idx: (n, k) int32 GLOBAL feature indices in [0, F) — exactly what
    repro.pipeline.FeaturePipeline.features emits.  Embedding-bag gather
    over the flat (F, C) table.

    Pipeline indices are in-range by construction (sentinels already map
    to bucket 0 of their hash upstream), so the [0, F-1] clamp only
    guards a features/table mismatch that XLA gather semantics would
    otherwise corrupt silently; validate_bag_features turns the same
    mismatch into a loud build-time error."""
    if idx.ndim != 2:
        raise ValueError(f"bag indices must be (n, k); got {idx.shape}")
    if params.w.ndim != 2:
        raise ValueError("bag params must be a flat (F, C) table "
                         f"(init_bag); got w {params.w.shape}")
    num_features = params.w.shape[0]
    # mode="clip" (a no-op on the already-clamped indices) skips
    # jnp.take's negative-wraparound add of num_features, which cannot
    # even trace once the table reaches 2^31 rows (int32 overflow)
    return _bag_sum(jnp.take(params.w,
                             idx.astype(jnp.int32).clip(0, num_features - 1),
                             axis=0, mode="clip")) + params.b


# rows of one one-hot block: the head's scratch is (_ONEHOT_ROWS, F) bf16
# whatever the batch, so the batch_size == n path stays bounded.  512 is
# four of the MXU's 128-row passes, and one block's one-hot is 268 MB at
# k = 1,024, b = 8 where a backend materializes it (the CPU does; the TPU
# compiler builds it inside the dot's fusion)
_ONEHOT_ROWS = 512


def _parts(x: Array) -> Array:
    """f32 (..., C) -> bf16 (..., 3C): three parts whose f32 sum is ``x``
    exactly, side by side, so one bf16 MXU pass (while 3C fits the 128
    lanes) carries all three.

    Each part is the top 16 bits of what is left (a bf16 is the top
    half of an f32): the first takes sign, exponent and 7 mantissa
    bits, the residual ``x - part`` is exact and holds the next 16, and
    after the second cut at most 8 remain, which the third holds
    whole.  Bit truncation, not a rounding cast, so nothing is narrowed
    (exact for normal f32; parts below bf16's normal range may flush)."""
    parts = []
    for _ in range(3):
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32) >> 16
        part = jax.lax.bitcast_convert_type(bits.astype(jnp.uint16),
                                            jnp.bfloat16)
        parts.append(part)
        x = x - part.astype(jnp.float32)
    return jnp.concatenate(parts, axis=-1)


def _mxu_select(onehot: Array, parts: Array, dims) -> Array:
    """``dot_general`` of an exact 0/1 bf16 one-hot with ``_parts(x)``,
    accumulated in f32: every selected part is exact, and the three
    parts' sums are added back in one fixed order."""
    c = parts.shape[-1] // 3
    out = jax.lax.dot_general(onehot, parts, dims,
                              preferred_element_type=jnp.float32)
    return (out[..., :c] + out[..., c:2 * c]) + out[..., 2 * c:]


def _onehot_t(codes: Array, width: int) -> Array:
    """(r, k) local codes -> the (k, width, r) exact 0/1 bf16 one-hot:
    [j, v, i] is 1 where row i's hash j lands in bucket v.  Hash-major,
    so (k, width) is the (k, width, C) view of the flat table's rows."""
    hit = codes.T[:, None, :] == jnp.arange(width, dtype=codes.dtype)[:, None]
    return hit.astype(jnp.bfloat16)


# the forward contracts the one-hot's (hash, bucket) axes with the
# table's; the backward contracts its row axis with the cotangent's
_FWD_DIMS = (((0, 1), (0, 1)), ((), ()))
_BWD_DIMS = (((2,), (0,)), ((), ()))


def _rows(x: Array, i) -> Array:
    """Row block ``i`` of ``x``: a dynamic slice, so the loops below
    read the batch in place instead of a padded or re-laid-out copy."""
    return jax.lax.dynamic_slice_in_dim(x, i * _ONEHOT_ROWS, _ONEHOT_ROWS)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _onehot_contract(width: int, w: Array, codes: Array) -> Array:
    """Σ_j onehot(codes[:, j]) @ W_j over the (k, width, C) view of the
    flat (F, C) table: logits without the bias.  Batches over
    ``_ONEHOT_ROWS`` rows go block by block, the remainder last."""
    w3 = _parts(w.reshape(codes.shape[1], width, -1))

    def block(c):
        return _mxu_select(_onehot_t(c, width), w3, _FWD_DIMS)

    if codes.shape[0] <= _ONEHOT_ROWS:
        return block(codes)
    nb, rem = divmod(codes.shape[0], _ONEHOT_ROWS)
    out = jax.lax.map(lambda i: block(_rows(codes, i)), jnp.arange(nb))
    out = out.reshape(nb * _ONEHOT_ROWS, -1)
    if rem:
        out = jnp.concatenate([out, block(codes[nb * _ONEHOT_ROWS:])])
    return out


def _onehot_contract_fwd(width, w, codes):
    return _onehot_contract(width, w, codes), codes


def _onehot_contract_bwd(width, codes, g):
    """dW = onehotᵀ @ g, the same exact contraction transposed (over
    rows), summed over the row blocks in f32; the codes get no
    cotangent.  No scatter: each table row's gradient is MXU
    accumulation over the batch."""
    k = codes.shape[1]

    def block(c, gb):
        return _mxu_select(_onehot_t(c, width), _parts(gb), _BWD_DIMS)

    if codes.shape[0] <= _ONEHOT_ROWS:
        dw = block(codes, g)
    else:
        nb, rem = divmod(codes.shape[0], _ONEHOT_ROWS)
        dw = jax.lax.fori_loop(
            0, nb, lambda i, dw: dw + block(_rows(codes, i), _rows(g, i)),
            jnp.zeros((k, width, g.shape[1]), jnp.float32))
        if rem:
            tail = nb * _ONEHOT_ROWS
            dw = dw + block(codes[tail:], g[tail:])
    return dw.reshape(k * width, -1), None


_onehot_contract.defvjp(_onehot_contract_fwd, _onehot_contract_bwd)


def bag_logits_onehot(params: LinearParams, codes: Array) -> Array:
    """Embedding-bag logits from LOCAL codes, by one-hot contraction.

    codes: (n, k) int32 bucket ids in [0, width) — hash j's code into
    its own block of the flat (F, C) table, F = k * width, which is how
    ``FeaturePipeline`` lays out its indices (``j * width + code_j``).
    So logits = Σ_j onehot(code_j) @ W_j: one contraction of an
    (n, F) one-hot against the table, on the MXU, and its gradient is
    the same contraction transposed.  The streamed trainer's head
    (training.linear_trainer) up to its ``ONEHOT_MAX_WIDTH``: XLA's TPU
    gather and scatter-add move one lane-padded table row at a time,
    and the scatter sorts its indices.  The work per row grows with
    k * width (the gather's with k), and each row block's one-hot is
    materialized where the backend does not fuse it into the dot.

    Exact in f32: the one-hot is 0/1 in bf16 and the table (forward) or
    the logits' cotangent (backward) is split into three bf16 parts, so
    every selected value is exact and sums accumulate in f32 — the same
    rows as ``bag_logits`` to f32 round-off, in another summation order.
    Batches go in blocks of 512 rows, so the one-hot is at most
    (512, F) whatever n (the TPU compiler builds it inside the dot's
    fusion, not in HBM).  Codes clamp into [0, width - 1],
    ``hashed_logits``' policy per hash block."""
    if codes.ndim != 2:
        raise ValueError(f"bag codes must be (n, k); got {codes.shape}")
    if params.w.ndim != 2 or params.w.shape[0] % codes.shape[1]:
        raise ValueError(
            f"bag params must be a flat (k * width, C) table for "
            f"{codes.shape[1]} hashes; got w {params.w.shape}")
    width = params.w.shape[0] // codes.shape[1]
    codes = codes.astype(jnp.int32).clip(0, width - 1)
    return _onehot_contract(width, params.w, codes) + params.b


def check_bag_table_size(num_hashes: int, b: int) -> int:
    """Construction-time int32-overflow guard for packed bag tables.

    Packed gathers rebuild global indices ``j * 2^b + code_j`` in int32
    (the gather index dtype the TPU path uses), so the last legal index
    ``(num_hashes - 1) * 2^b + (2^b - 1) = num_hashes * 2^b - 1`` must
    fit int32.  ``num_hashes * 2^b <= 2^31`` is exact: at b = 8 the
    boundary is num_hashes = 2^23, whose top index is 2147483647 ==
    int32 max.  Beyond it the offset arithmetic wraps negative and the
    clamp silently folds every overflowed hash onto row 0 — found by the
    int_range analyzer (DESIGN.md §15), pinned here loudly.  Returns the
    table row count ``num_hashes * 2^b``."""
    from repro.core.hashing import check_packed_bits
    check_packed_bits(b)
    num_features = num_hashes * (1 << b)
    if num_features > 2 ** 31:
        raise ValueError(
            f"packed bag table overflow: {num_hashes} hashes at b = {b} "
            f"index {num_features} features, but the top index "
            f"{num_features - 1} exceeds int32 max ({2 ** 31 - 1}) and "
            f"the j*2^b offset arithmetic would wrap; keep "
            f"num_hashes * 2^b <= 2^31 (at b = {b}: num_hashes <= "
            f"{2 ** 31 >> b})")
    return num_features


def bag_logits_packed(params: LinearParams, packed: Array, *,
                      num_hashes: int, b: int) -> Array:
    """Embedding-bag logits straight from bit-packed features.

    packed: (n, ceil(num_hashes*b/32)) uint32 words as emitted by
    FeaturePipeline(packed=True) / ops.cws(emit="packed").  Unpacks in
    registers (shift/mask — the packed words never round-trip through an
    int32 feature matrix), rebuilds the global indices
    ``j * 2^b + code_j``, and gathers the flat (num_hashes * 2^b, C)
    table exactly like ``bag_logits`` — same clamp policy, and sentinels
    were already folded to bucket 0 at pack time.  Bit-identical to
    ``bag_logits(params, unpacked_indices)`` by construction."""
    from repro.core.hashing import packed_width, unpack_codes
    if packed.ndim != 2:
        raise ValueError(f"packed features must be (n, words); "
                         f"got {packed.shape}")
    if packed.dtype != jnp.uint32:
        raise ValueError(f"packed features must be uint32 words; "
                         f"got {packed.dtype}")
    if packed.shape[-1] != packed_width(num_hashes, b):
        raise ValueError(
            f"packed width mismatch: got {packed.shape[-1]} words but "
            f"{num_hashes} hashes at b = {b} pack into "
            f"{packed_width(num_hashes, b)}")
    if params.w.ndim != 2:
        raise ValueError("bag params must be a flat (F, C) table "
                         f"(init_bag); got w {params.w.shape}")
    num_features = params.w.shape[0]
    if num_features != check_bag_table_size(num_hashes, b):
        raise ValueError(
            f"feature-table mismatch: table has {num_features} rows but "
            f"{num_hashes} hashes at b = {b} index {num_hashes * (1 << b)} "
            f"features; build with init_bag_packed(key, num_hashes, b, C)")
    codes = unpack_codes(packed, num_hashes, b=b)
    offs = jnp.arange(num_hashes, dtype=jnp.int32) * (1 << b)
    idx = (offs + codes).astype(jnp.int32)
    # mode="clip" as in bag_logits: at the 2^31-row boundary table the
    # default negative-wraparound add would overflow int32 at trace time
    return _bag_sum(jnp.take(params.w, idx.clip(0, num_features - 1),
                             axis=0, mode="clip")) + params.b


def init_bag_packed(key: Array, num_hashes: int, b: int,
                    n_classes: int) -> LinearParams:
    """Flat table sized for packed b-bit features: (num_hashes * 2^b, C).
    The truncated-width twin of ``init_bag`` — at b = 4 the table is
    2^(full-4) x smaller than the untruncated space."""
    return init_bag(key, check_bag_table_size(num_hashes, b), n_classes)


def validate_bag_features(params: LinearParams, num_features: int, *,
                          spec=None) -> None:
    """Trace-time guard wiring a (F, C) table to a feature space: a table
    whose row count differs from the pipeline's ``num_features`` makes
    every bag_logits gather clamp (logits silently corrupted), so fail
    where the sizes are both known instead.

    Pass the pipeline's FeatureSpec via ``spec`` when it may be packed:
    a packed spec additionally pins the expected feature width to
    ``ceil(k*b/32)`` uint32 words so the packed/unpacked surfaces can't
    be cross-wired silently (the trainer does this for you)."""
    if params.w.ndim != 2:
        raise ValueError("bag params must be a flat (F, C) table "
                         f"(init_bag); got w {params.w.shape}")
    if spec is not None and getattr(spec, "packed", False):
        expected = spec.num_hashes * (1 << spec.bits)
        if params.w.shape[0] != expected:
            raise ValueError(
                f"feature-table mismatch: table has {params.w.shape[0]} "
                f"rows but the packed pipeline ({spec.num_hashes} hashes "
                f"at b = {spec.bits}) indexes {expected} features; build "
                f"with init_bag_packed(key, num_hashes, b, n_classes)")
        return
    if params.w.shape[0] != num_features:
        raise ValueError(
            f"feature-table mismatch: table has {params.w.shape[0]} rows "
            f"but the pipeline emits indices into {num_features} features; "
            f"build with init_bag(key, pipe.num_features, n_classes)")


_LOGITS_FNS = {"dense": dense_logits, "hashed": hashed_logits,
               "bag": bag_logits}


def squared_hinge_loss(logits: Array, labels: Array, n_classes: int) -> Array:
    y = jnp.where(jax.nn.one_hot(labels, n_classes, dtype=jnp.float32) > 0,
                  1.0, -1.0)
    margins = jnp.maximum(0.0, 1.0 - y * logits)
    return jnp.mean(jnp.sum(jnp.square(margins), axis=-1))


def softmax_xent_loss(logits: Array, labels: Array, n_classes: int) -> Array:
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


@dataclasses.dataclass(frozen=True)
class TrainCfg:
    n_classes: int
    steps: int = 400          # UPDATE steps (not epochs), any batch_size
    lr: float = 0.05
    l2: float = 1e-4          # = 1/(2C) scaled by n
    batch_size: int = 0       # 0 => explicit full batch; > 0 => minibatch
    loss: str = "squared_hinge"


def _loss_fn(params, xb, yb, cfg: TrainCfg, logits_fn):
    logits = logits_fn(params, xb)
    if cfg.loss == "squared_hinge":
        data = squared_hinge_loss(logits, yb, cfg.n_classes)
    else:
        data = softmax_xent_loss(logits, yb, cfg.n_classes)
    reg = cfg.l2 * jnp.sum(jnp.square(params.w))
    return data + reg


def make_linear_tx(cfg: TrainCfg):
    """The one optimizer recipe for the linear tier — shared by the
    full-batch/minibatch paths here and the streaming trainer
    (repro.training.linear_trainer), so their updates are bit-comparable."""
    return optim.chain(optim.clip_by_global_norm(10.0),
                       optim.adamw(optim.cosine_schedule(cfg.lr, cfg.steps)))


@functools.partial(jax.jit, static_argnames=("cfg", "kind"))
def fit_linear(params: LinearParams, x: Array, labels: Array, *,
               cfg: TrainCfg, kind: str = "dense",
               shuffle_key: Array | None = None) -> LinearParams:
    """Adam on materialized features: full batch (``cfg.batch_size == 0``
    — deterministic, bit-stable, good up to ~100k examples on CPU) or
    permutation-shuffled minibatches (``cfg.batch_size > 0``; a fresh
    epoch permutation is derived per epoch from ``shuffle_key``, and the
    ragged remainder of each permutation is dropped — different rows
    each epoch).  ``cfg.steps`` counts updates on both paths.

    ``batch_size == n`` takes the full-batch gradient without a gather:
    a full-batch gradient is permutation-invariant, so shuffling only
    costs float reassociation — skipping it keeps the path bit-identical
    to ``batch_size == 0``.  For n too large to materialize the (n, k)
    feature matrix at all, use repro.training.linear_trainer, which
    streams featurization inside the loop."""
    logits_fn = _LOGITS_FNS[kind]
    n = x.shape[0]
    bs = cfg.batch_size
    if bs < 0:
        raise ValueError(f"batch_size must be >= 0; got {bs}")
    if bs > n:
        raise ValueError(
            f"batch_size {bs} exceeds the {n} available rows; pass "
            f"batch_size=0 for the explicit full-batch path")
    tx = make_linear_tx(cfg)
    state = tx.init(params)

    if bs in (0, n):
        def step(i, carry):
            params, state = carry
            grads = jax.grad(_loss_fn)(params, x, labels, cfg, logits_fn)
            updates, state = tx.update(grads, state, params, i)
            return optim.apply_updates(params, updates), state

        params, _ = jax.lax.fori_loop(0, cfg.steps, step, (params, state))
        return params

    steps_per_epoch = n // bs
    key = shuffle_key if shuffle_key is not None else jax.random.PRNGKey(0)

    def step(i, carry):
        params, state, perm = carry
        epoch = i // steps_per_epoch
        pos = i % steps_per_epoch
        # the O(n log n) shuffle runs only on epoch boundaries; the
        # permutation is carried through the loop in between
        perm = jax.lax.cond(
            pos == 0,
            lambda: jax.random.permutation(jax.random.fold_in(key, epoch),
                                           n),
            lambda: perm)
        idx = jax.lax.dynamic_slice_in_dim(perm, pos * bs, bs)
        xb = jnp.take(x, idx, axis=0)
        yb = jnp.take(labels, idx, axis=0)
        grads = jax.grad(_loss_fn)(params, xb, yb, cfg, logits_fn)
        updates, state = tx.update(grads, state, params, i)
        return optim.apply_updates(params, updates), state, perm

    perm0 = jnp.arange(n, dtype=jnp.int32)   # replaced at i = 0 (pos == 0)
    params, _, _ = jax.lax.fori_loop(0, cfg.steps, step,
                                     (params, state, perm0))
    return params


def linear_accuracy(params: LinearParams, x: Array, labels: Array,
                    kind: str = "dense") -> float:
    logits_fn = _LOGITS_FNS[kind]
    pred = jnp.argmax(logits_fn(params, x), axis=-1)
    return float(jnp.mean((pred == labels).astype(jnp.float32)))


def best_linear_accuracy_over_C(x_tr, y_tr, x_te, y_te, *, n_classes,
                                kind="dense",
                                l2s=(1e-6, 1e-5, 1e-4, 1e-3),
                                steps=400, lr=0.05):
    """Mirror of the paper's C sweep for the linear learner (dense only;
    hashed/bag features go through best_hashed_accuracy_over_C or
    best_bag_accuracy_over_C)."""
    if kind != "dense":
        raise ValueError("use best_hashed_accuracy_over_C / "
                         "best_bag_accuracy_over_C for hashed features")
    best = 0.0
    for l2 in l2s:
        cfg = TrainCfg(n_classes=n_classes, steps=steps, lr=lr, l2=float(l2))
        p0 = init_dense(jax.random.PRNGKey(0), x_tr.shape[-1], n_classes)
        p = fit_linear(p0, x_tr, y_tr, cfg=cfg, kind=kind)
        best = max(best, linear_accuracy(p, x_te, y_te, kind=kind))
    return best


def best_hashed_accuracy_over_C(codes_tr, y_tr, codes_te, y_te, *, n_classes,
                                k: int, width: int,
                                l2s=(1e-6, 1e-5, 1e-4),
                                steps=400, lr=0.05):
    best = 0.0
    for l2 in l2s:
        cfg = TrainCfg(n_classes=n_classes, steps=steps, lr=lr, l2=float(l2))
        p0 = init_hashed(jax.random.PRNGKey(0), k, width, n_classes)
        p = fit_linear(p0, codes_tr, y_tr, cfg=cfg, kind="hashed")
        best = max(best, linear_accuracy(p, codes_te, y_te, kind="hashed"))
    return best


# ---------------------------------------------------------------------------
# numerics-analysis sites (repro.analysis / tools/kernel_lint.py)
# ---------------------------------------------------------------------------
# Hostile-input interval proofs for the embedding-bag gathers: bag_logits
# under a FULL-int32 index seed (the clamp must dominate the gather), and
# the packed offset arithmetic at the exact int32 boundary
# (num_hashes = 2^23, b = 8: top index 2^31 - 1).  ShapeDtypeStructs
# only — the 2^31-row table never materializes.

from repro.kernels import registry as _registry  # noqa: E402


@_registry.register_numerics_site("linear.bag_logits")
def _numerics_site_bag_logits():
    import jax as _jax
    w = _jax.ShapeDtypeStruct((96, 3), jnp.float32)
    bias = _jax.ShapeDtypeStruct((3,), jnp.float32)
    idx = _jax.ShapeDtypeStruct((4, 6), jnp.int32)   # full int32 range
    return {"fn": lambda w, bias, idx: bag_logits(LinearParams(w, bias),
                                                  idx),
            "args": (w, bias, idx)}


@_registry.register_numerics_site("linear.bag_logits_packed_boundary")
def _numerics_site_bag_logits_packed():
    import jax as _jax
    k, b = 1 << 23, 8                        # top index == int32 max
    w = _jax.ShapeDtypeStruct((check_bag_table_size(k, b), 3), jnp.float32)
    bias = _jax.ShapeDtypeStruct((3,), jnp.float32)
    from repro.core.hashing import packed_width
    packed = _jax.ShapeDtypeStruct((2, packed_width(k, b)), jnp.uint32)
    return {"fn": lambda w, bias, packed: bag_logits_packed(
                LinearParams(w, bias), packed, num_hashes=k, b=b),
            "args": (w, bias, packed)}


def best_bag_accuracy_over_C(idx_tr, y_tr, idx_te, y_te, *, n_classes,
                             num_features: int,
                             l2s=(1e-6, 1e-5, 1e-4),
                             steps=400, lr=0.05):
    """C sweep over pipeline feature indices (the fused-kernel artifact)."""
    best = 0.0
    for l2 in l2s:
        cfg = TrainCfg(n_classes=n_classes, steps=steps, lr=lr, l2=float(l2))
        p0 = init_bag(jax.random.PRNGKey(0), num_features, n_classes)
        p = fit_linear(p0, idx_tr, y_tr, cfg=cfg, kind="bag")
        best = max(best, linear_accuracy(p, idx_te, y_te, kind="bag"))
    return best
