"""Online scoring: open-loop requests through ``ServingService.submit``.

Set-up writes a served-model bundle (the configuration's CWS parameters
from the seed and a random linear table made by the benchmark), boots
``ServingService.from_bundle`` (which compiles every bucket), and sends
each bucket a few requests.  The window sends the traffic file's
schedule: each request on its due time whether or not earlier ones have
finished, timed from its due time to the moment its logits are back.
A request that fails, is refused or never comes back is counted in
``failed`` and kept out of the latencies.  The check compares a sample
of the served requests, drawn from the seed with the largest among them,
with the reference's logits: the share of their rows on which some logit
is off by more than ``row_tol``.
"""
from __future__ import annotations

import queue
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen, reference
from bench.harness import Outcome, span
from bench.program import make_pipeline
from repro.serving.gateway import ServeError


def make_table(cfg: dict, seed: int):
    """The served (F, C) table and (C,) bias, from the seed, on device."""
    f = cfg["num_hashes"] << (cfg["b_i"] + cfg["b_t"])
    c, std = cfg["n_classes"], float(cfg["table_std"])

    @jax.jit
    def draw(key):
        kw, kb = jax.random.split(key)
        return (std * jax.random.normal(kw, (f, c), jnp.float32),
                std * jax.random.normal(kb, (c,), jnp.float32))

    return draw(gen.sub_key(seed, gen.KEY_TABLE))


def pick_sample(finished, sizes, rows_wanted: int, seed: int):
    """Requests to check, among those ``finished``: the largest, then
    others drawn from the seed until ``rows_wanted`` rows are covered."""
    finished = np.asarray(sorted(finished))
    if finished.size == 0:
        return []
    first = int(finished[np.argmax(sizes[finished])])
    picked, rows = [first], int(sizes[first])
    for i in np.random.default_rng(seed).permutation(finished):
        if rows >= rows_wanted:
            break
        if i != first:
            picked.append(int(i))
            rows += int(sizes[i])
    return sorted(picked)


def send(svc, views, due, t0, drain_s: float):
    """Open loop: submit request i at ``t0 + due[i]``; a second thread
    collects completions in order.  Returns (latency s, NaN where the
    request failed; send lag s; {request: logits} of those finished)."""
    n = len(views)
    done = np.full(n, np.nan)
    lag = np.empty(n)
    results = {}
    inbox = queue.SimpleQueue()
    close = [None]

    def collect():
        for _ in range(n):
            i, fut = inbox.get()
            if fut is None:
                continue
            while True:
                limit = close[0]
                wait = 1.0 if limit is None else max(
                    limit - time.perf_counter(), 0.0)
                try:
                    out = fut.result(timeout=wait)
                except TimeoutError:
                    if limit is not None:
                        break
                    continue
                except ServeError:
                    break
                done[i] = time.perf_counter()
                results[i] = out
                break

    waiter = threading.Thread(target=collect, name="bench-collect")
    waiter.start()
    try:
        for i in range(n):
            t_due = t0 + due[i]
            now = time.perf_counter()
            if now < t_due:
                time.sleep(t_due - now)
            lag[i] = time.perf_counter() - t_due
            try:
                fut = svc.submit(views[i])
            except ServeError:
                fut = None
            inbox.put((i, fut))
        close[0] = time.perf_counter() + drain_s
    finally:
        if close[0] is None:
            close[0] = time.perf_counter()
        waiter.join()
    return done - (t0 + due), lag, results


def run(ctx) -> Outcome:
    from repro.serving import ServingService
    from repro.training import export_served_model

    cfg, mix, seed = ctx.cfg, ctx.mix, ctx.seed
    n = cfg["n_test"]
    x, _ = gen.rows_for(cfg, seed, n)
    nnz = float(jnp.count_nonzero(x)) / n
    x_host = np.asarray(x)
    del x
    wrapped = np.concatenate([x_host, x_host[:int(mix["rows_per_request"])]])
    due, sizes = gen.request_schedule(mix, seed, ctx.seconds)
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]]) % n
    views = [wrapped[s:s + m] for s, m in zip(starts, sizes)]
    key_cws = gen.sub_key(seed, gen.KEY_CWS)
    w, b = make_table(cfg, seed)

    lat = lag = np.zeros(0)
    results, before, after, wall = {}, {}, {}, float("nan")
    if not ctx.control:
        from repro.core.linear_model import LinearParams
        bundle = ctx.scratch / "bundle"
        export_served_model(LinearParams(w, b), make_pipeline(cfg, key_cws),
                            bundle)
        svc = ServingService.from_bundle(bundle)
        try:
            with span("bench.warmup"):
                for bucket in svc.runner.buckets:
                    for _ in range(int(mix["warmup_per_bucket"])):
                        svc.score(wrapped[:bucket], timeout=60)
            before = svc.stats()
            ctx.begin_window()
            with span("bench.send"):
                lat, lag, results = send(svc, views, due, ctx.t0,
                                         float(mix["drain_s"]))
            wall = ctx.end_window()
            after = svc.stats()
            ctx.read_memory()
        finally:
            svc.stop()
        del svc

    # -- check: a sample of the finished requests against the reference ----
    if ctx.control:
        results = dict.fromkeys(range(len(sizes)))
    picked = pick_sample(list(results), sizes, int(mix["sample_rows"]), seed)
    xs = jnp.asarray(np.concatenate([views[i] for i in picked]))
    params = reference.cws_params(cfg, key_cws)
    code = reference.codes(xs, *params, b_i=cfg["b_i"])
    want = np.asarray(reference.logits(w, b, reference.indices(
        code, cfg["b_i"])))
    if ctx.control:
        # the reference in bfloat16 stands in the program's place
        lo = reference.codes(xs, *params, b_i=cfg["b_i"],
                             dtype=jnp.bfloat16)
        got = np.asarray(reference.logits(
            w.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
            reference.indices(lo, cfg["b_i"]))).astype(np.float32)
    else:
        got = np.concatenate([results[i] for i in picked])
    off = ~(np.max(np.abs(got - want), axis=1) <= float(mix["row_tol"]))

    ok = np.isfinite(lat)
    ms = lat[ok] * 1e3
    metrics = {}
    if ms.size:
        metrics = {"serve_p50_ms": float(np.percentile(ms, 50)),
                   "serve_p95_ms": float(np.percentile(ms, 95))}
    return Outcome(
        metrics=metrics, attempted=int(lat.size),
        failed=int(lat.size - ok.sum()),
        checks={"rows_off_share": float(np.mean(off))},
        layer={"requests": int(lat.size), "window_wall_s": wall,
               "send_lag_s": lag, "latency_s": lat, "nnz_per_row": nnz,
               "monitor_before": before, "monitor_after": after})
