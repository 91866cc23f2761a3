"""Serving drivers: the featurize→score online stack, and LM decode.

Two front ends share this entry point:

  * ``--bundle DIR`` — boot a ``repro.serving.ServingService`` replica
    from a served-model bundle (see ``export_served_model``), warm every
    bucket executable, optionally expose the JSON ``/stats`` endpoint,
    and drive synthetic request traffic through the gateway:

      PYTHONPATH=src python -m repro.launch.serve --bundle /tmp/model \
          --requests 200 --max-rows 48 --stats-port 0

  * the original LM path (prefill + decode with KV caches), same code
    path the decode_32k / long_500k dry-run cells lower:

      PYTHONPATH=src python -m repro.launch.serve --arch gemma3_12b \
          --variant smoke --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import setup_compile_cache


def serve_bundle(args) -> None:
    """The featurize→score service: load bundle, warm buckets, fire
    synthetic traffic, print the monitoring snapshot."""
    from repro.serving import ServingService

    buckets = (tuple(int(b) for b in args.buckets.split(","))
               if args.buckets else None)
    svc = ServingService.from_bundle(
        args.bundle, buckets=buckets,
        default_deadline_s=args.deadline_s,
        hard_timeout_s=args.hard_timeout_s)
    stats_url = None
    if args.stats_port is not None:
        stats_url = svc.start_stats_server(port=args.stats_port).url
        print(f"stats endpoint: {stats_url}")
    print(f"warmed {len(svc.runner.buckets)} bucket executables "
          f"{svc.runner.buckets} in {svc.warmup_s * 1e3:.1f} ms")

    rng = np.random.default_rng(args.seed)
    dim = svc.runner.pipe.dim
    futures = []
    t0 = time.perf_counter()
    for _ in range(args.requests):
        m = int(rng.integers(1, args.max_rows + 1))
        x = np.abs(rng.standard_normal((m, dim))).astype(np.float32)
        x *= rng.random((m, dim)) < 0.3          # sparse nonneg rows
        futures.append(svc.submit(x))
    for f in futures:
        f.result(timeout=args.deadline_s + 30.0)
    wall = time.perf_counter() - t0

    stats = svc.stats()
    print(f"{args.requests} requests ({stats['rows']} rows) in "
          f"{wall:.2f}s -> {args.requests / wall:,.1f} req/s")
    lat = stats["latency_ms"]
    print(f"latency p50 {lat['p50']:.2f} ms  p99 {lat['p99']:.2f} ms; "
          f"compiles {stats['compile_count']} "
          f"(= {len(svc.runner.buckets)} buckets, zero retraces)")
    print(json.dumps(stats, indent=1, sort_keys=True))
    svc.stop()


def serve_lm(args) -> None:
    from repro.configs import get_config
    from repro.launch.mesh import make_local_mesh
    from repro.models import init_model, init_caches
    from repro.models.sharding import make_rules, use_rules
    from repro.training import make_serve_steps

    cfg = get_config(args.arch, args.variant)
    mesh = make_local_mesh()
    rules = make_rules(mesh)
    max_len = args.prompt_len + args.gen

    with mesh:
        params = init_model(jax.random.PRNGKey(0), cfg)
        prefill_step, decode_one = make_serve_steps(cfg, rules)
        prefill_j = jax.jit(prefill_step)
        decode_j = jax.jit(decode_one, donate_argnums=3)

        rng = np.random.default_rng(0)
        if cfg.input_mode == "embeddings":
            prompts = jnp.asarray(rng.standard_normal(
                (args.batch, args.prompt_len, cfg.d_model)), jnp.float32)
        else:
            prompts = jnp.asarray(rng.integers(
                0, cfg.vocab, (args.batch, args.prompt_len)), jnp.int32)

        with use_rules(rules):
            caches = init_caches(cfg, args.batch, max_len)
        t0 = time.perf_counter()
        logits, caches = prefill_j(params, prompts, caches)
        jax.block_until_ready(logits)
        t_prefill = time.perf_counter() - t0

        tokens = jnp.argmax(logits[:, :cfg.vocab], axis=-1)[:, None]
        # ONE threaded sampling key for the whole decode, split per step:
        # a fresh PRNGKey(t) per step would sample from correlated,
        # attacker-predictable streams (keys 0, 1, 2, ... are not
        # independent draws; they are the whole keyspace prefix)
        sample_key = jax.random.PRNGKey(args.seed)
        outs = [np.asarray(tokens)]
        t0 = time.perf_counter()
        for t in range(args.gen - 1):
            step_in = tokens
            if cfg.input_mode == "embeddings":
                # stub frontends embed generated ids via the output table
                step_in = jnp.take(params["embed"]["tokens"],
                                   tokens, axis=0).astype(cfg.compute_dtype)
            logits, caches = decode_j(params, step_in,
                                      jnp.int32(args.prompt_len + t), caches)
            if args.temperature > 0:
                sample_key, sub = jax.random.split(sample_key)
                tokens = jax.random.categorical(
                    sub, logits[:, :cfg.vocab] / args.temperature)[:, None]
            else:
                tokens = jnp.argmax(logits[:, :cfg.vocab], axis=-1)[:, None]
            outs.append(np.asarray(tokens))
        jax.block_until_ready(logits)
        t_decode = time.perf_counter() - t0

    gen = np.concatenate(outs, axis=1)
    tok_s = args.batch * (args.gen - 1) / max(t_decode, 1e-9)
    print(f"prefill: {t_prefill*1e3:.1f} ms for "
          f"{args.batch}x{args.prompt_len} tokens")
    print(f"decode : {tok_s:,.1f} tok/s ({args.gen - 1} steps)")
    print("generated ids (first row):", gen[0][:16])


def main():
    ap = argparse.ArgumentParser()
    # featurize→score service
    ap.add_argument("--bundle", default=None,
                    help="served-model bundle dir -> run the online "
                    "featurize+score service instead of the LM path")
    ap.add_argument("--requests", type=int, default=100)
    ap.add_argument("--max-rows", type=int, default=32,
                    help="synthetic request sizes draw from [1, max-rows]")
    ap.add_argument("--buckets", default=None,
                    help="comma-separated bucket ladder override")
    ap.add_argument("--deadline-s", type=float, default=30.0)
    ap.add_argument("--hard-timeout-s", type=float, default=0.0)
    ap.add_argument("--stats-port", type=int, default=None,
                    help="expose GET /stats on this port (0 = pick free)")
    # LM decode
    ap.add_argument("--arch", default=None)
    ap.add_argument("--variant", default="smoke")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    setup_compile_cache()

    if args.bundle is not None:
        serve_bundle(args)
    elif args.arch is not None:
        serve_lm(args)
    else:
        ap.error("pass --bundle DIR (featurize→score service) or "
                 "--arch NAME (LM decode)")


if __name__ == "__main__":
    main()
