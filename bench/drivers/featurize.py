"""Offline featurization: back-to-back ``FeaturePipeline.features``
passes over the whole on-device dataset, each blocked at its end.

Set-up runs one pass, which compiles every program the window runs.  The
window counts whole passes until ``--seconds`` have gone by.  The check
compares a sample of the last pass's rows, drawn from the seed, with the
reference's codes: the share of (row, hash) codes that differ.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen, reference
from bench.program import make_pipeline
from bench.harness import Outcome, span


def run(ctx) -> Outcome:
    cfg, mix, seed = ctx.cfg, ctx.mix, ctx.seed
    n = cfg["n_train"] + cfg["n_test"]
    x, _ = gen.rows_for(cfg, seed, n)
    nnz = float(jnp.count_nonzero(x)) / n
    rng = np.random.default_rng(seed)
    sample = np.sort(rng.choice(n, int(mix["sample_rows"]), replace=False))
    key_cws = gen.sub_key(seed, gen.KEY_CWS)
    k, bits = cfg["num_hashes"], cfg["b_i"] + cfg["b_t"]

    passes, wall, launches = 0, float("nan"), 0
    if not ctx.control:
        pipe = make_pipeline(cfg, key_cws)
        with span("bench.warmup"):
            jax.block_until_ready(pipe.features(x))
        ctx.begin_window()
        while True:
            with span("bench.pass"):
                out = pipe.features(x)
                jax.block_until_ready(out)
            passes += 1
            if ctx.elapsed() >= ctx.seconds:
                break
        wall = ctx.end_window()
        launches = passes * -(-n // pipe.row_chunk)
        ctx.read_memory()
        got = np.asarray(out[jnp.asarray(sample)])
        del out, pipe
        got = reference.unpack(got, k, bits) if cfg.get("packed") else \
            np.asarray(got) - np.arange(k) * (1 << bits)

    xs = x[jnp.asarray(sample)]
    del x
    params = reference.cws_params(cfg, key_cws)
    want = np.asarray(reference.codes(xs, *params, b_i=cfg["b_i"]))
    if ctx.control:
        # the reference in bfloat16 stands in the program's place
        got = np.asarray(reference.codes(xs, *params, b_i=cfg["b_i"],
                                         dtype=jnp.bfloat16))
    rows = passes * n
    return Outcome(
        metrics={"featurize_rows_per_s": rows / wall},
        attempted=passes, failed=0,
        checks={"code_mismatch_share": float(np.mean(got != want))},
        layer={"rows": rows, "passes": passes, "launches": launches,
               "nnz_per_row": nnz})
