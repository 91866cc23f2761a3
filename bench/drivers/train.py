"""Training: one ``fit_linear_streamed`` call on the on-device training
set, from a zero table to the end of its schedule.

Set-up makes the rows and the pipeline from the seed and runs a fit of
the window's own configuration through its first three steps, keeping
the state after each on the host through the fit's checkpoint hook; the
program's fault plan stops it before its fourth step.  So every program
the window runs is compiled and in the persistent cache.  The window is
one fresh fit, its start included, since users pay the start of every
fit.

The fit's length is fixed by the traffic file (``steps_per_s`` times the
window), not by the measured speed: round-off between two sound float32
fits grows with the number of steps once the training loss is small, so
a length that grew with the program's speed would move the check's
lower reading.  The cosine horizon is part of the compiled step, so the
length is known before set-up compiles it.

The check has the plain reference follow every step on the same batches
and compares, per leaf, gaps of norms: the first gradient as the
optimizer got it (from its first moment after one step) and the
parameters' change after three steps, both from set-up's fit, and the
change over the window's whole fit.  The first two catch a loss of
precision; the third, which round-off lets swing from seed to seed,
catches a window that trains wrong.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen, reference
from bench.program import make_pipeline
from bench.harness import Outcome, span


def leaf_gap(prog: dict, ref: dict, ref_grad: dict) -> float:
    """Worst leaf's |norm(program) - norm(reference)| over the larger of
    that leaf's reference norm and the median leaf's.  Leaves whose
    reference gradient is under a thousandth of the median leaf's move
    by round-off alone and are left out."""
    med_g = float(np.median(list(ref_grad.values())))
    keep = [k for k in ref if ref_grad[k] >= 1e-3 * med_g]
    med = float(np.median([ref[k] for k in keep]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep)


def _norms(tree: dict) -> dict:
    return {k: float(jnp.linalg.norm(jnp.asarray(v, jnp.float32)))
            for k, v in tree.items()}


# set-up's fit is checked over its first CHECKED steps
CHECKED = 3
# the stated AdamW first-moment decay: mu after one step is (1 - B1) g
B1 = 0.9


def _first_steps_keeper(directory):
    """A ``Checkpointer`` for the fit's checkpoint hook that writes
    nothing: it keeps a host copy of the state after each of the first
    ``CHECKED`` steps (the copy is taken before the next step donates the
    buffers)."""
    from repro.checkpoint.checkpointer import Checkpointer

    class FirstSteps(Checkpointer):
        def __init__(self):
            super().__init__(directory)
            self.kept = {}

        def save_async(self, step, tree, extra=None):
            if step <= CHECKED:
                self.kept[step] = jax.tree_util.tree_map(np.asarray, tree)

    return FirstSteps()


def _first_moment(opt_state):
    """The AdamW first moment in the optimizer's state tree."""
    found = [s for s in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")]
    if len(found) != 1:
        raise RuntimeError("no single AdamW state with a first moment 'mu'")
    return found[0].mu


def run(ctx) -> Outcome:
    from repro.core.linear_model import TrainCfg, init_bag
    from repro.launch.mesh import make_data_mesh
    from repro.runtime.chaos import ChaosKill, ChaosPlan, kill_at
    from repro.training import fit_linear_streamed
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg, mix, seed = ctx.cfg, ctx.mix, ctx.seed
    n, bs, c = cfg["n_train"], int(mix["batch_size"]), cfg["n_classes"]
    key_cws = gen.sub_key(seed, gen.KEY_CWS)
    shuffle = gen.sub_key(seed, gen.KEY_SHUFFLE)

    x, y = gen.rows_for(cfg, seed, n)
    nnz = float(jnp.count_nonzero(x)) / n
    mesh = make_data_mesh(ctx.chips) if ctx.chips > 1 else None
    if mesh is not None:
        rep = NamedSharding(mesh, P())
        x, y = jax.device_put(x, rep), jax.device_put(y, rep)
    pipe = make_pipeline(cfg, key_cws)
    p0 = init_bag(jax.random.PRNGKey(0), pipe.num_features, c)

    steps = int(round(mix["steps_per_s"] * ctx.seconds))
    tcfg = TrainCfg(n_classes=c, steps=steps, lr=cfg["lr"], l2=cfg["l2"],
                    batch_size=bs, loss=cfg["loss"])

    def fit(**kw):
        return fit_linear_streamed(p0, pipe, x, y, cfg=tcfg,
                                   shuffle_key=shuffle, mesh=mesh, **kw)

    if ctx.control:
        wall = float("nan")
    else:
        keeper = _first_steps_keeper(ctx.scratch / "first_steps")
        with span("bench.warmup"):
            try:
                fit(chaos=ChaosPlan(kill_at(CHECKED)), ckpt=keeper,
                    ckpt_every=1)
            except ChaosKill:
                pass
        first = keeper.kept
        grad1 = {k: v / (1 - B1) for k, v in
                 _first_moment(first[1]["opt_state"])._asdict().items()}
        p3 = first[CHECKED]["params"]
        change3 = _norms({"w": p3.w - p0.w, "b": p3.b - p0.b})
        grad1 = _norms(grad1)
        del first, keeper, p3

        ctx.begin_window()
        with span("bench.fit"):
            params = fit()
            jax.block_until_ready(params)
        wall = ctx.end_window()
        ctx.read_memory()
        change = _norms({"w": params.w - p0.w, "b": params.b - p0.b})
        del params
    del pipe, p0

    # -- the reference follows every step of the fit -----------------------
    cws = reference.cws_params(cfg, key_cws)
    ref = _follow(cfg, x, y, shuffle, cws, steps, bs, jnp.float32)
    if ctx.control:
        # the reference in bfloat16 stands in the program's place
        got = _follow(cfg, x, y, shuffle, cws, steps, bs, jnp.bfloat16)
        change, grad1, change3 = (_norms(got[k])
                                  for k in ("last", "grad1", "early"))
    ref_g1 = _norms(ref["grad1"])
    checks = {"grad_gap": leaf_gap(grad1, ref_g1, ref_g1),
              "change3_gap": leaf_gap(change3, _norms(ref["early"]), ref_g1),
              "change_gap": leaf_gap(change, _norms(ref["last"]), ref_g1)}
    return Outcome(
        metrics={"train_rows_per_s": steps * bs / wall},
        attempted=steps, failed=0, checks=checks,
        layer={"rows": steps * bs, "steps": steps, "launches": steps,
               "nnz_per_row": nnz})


def _follow(cfg, x, y, shuffle, cws, steps, bs, dt):
    """The reference's fit of ``steps`` updates in arithmetic ``dt``: its
    parameters after the last step (``last``) and after ``CHECKED``
    steps (``early``), and its first clipped gradient (``grad1``), each
    as {"w", "b"}.  Batches follow the fit's stated order: epoch e walks
    ``permutation(fold_in(shuffle, e), n)`` in whole batches."""
    n, c = x.shape[0], cfg["n_classes"]
    dev = jax.devices()[0]
    x, y = jax.device_put(x, dev), jax.device_put(y, dev)
    idx = reference.indices(
        reference.codes(x, *cws, b_i=cfg["b_i"], dtype=dt), cfg["b_i"])
    per_epoch = n // bs

    def batches():
        for i in range(steps):
            epoch, pos = divmod(i, per_epoch)
            if pos == 0:
                perm = jax.random.permutation(
                    jax.random.fold_in(shuffle, epoch), n)
            sel = perm[pos * bs:(pos + 1) * bs]
            yield idx[sel], y[sel]

    zeros = (jnp.zeros((cfg["num_hashes"] << cfg["b_i"], c), dt),
             jnp.zeros((c,), dt))
    got = reference.train(zeros, batches(), lr=cfg["lr"], total_steps=steps,
                          n_classes=c, l2=cfg["l2"], early=CHECKED)
    return {k: dict(zip("wb", v)) for k, v in got.items()}
