"""CWS hashing + min-max Gram throughput: Pallas kernel (interpret mode on
this CPU container — the BlockSpec tiling is what ships to TPU), the
chunked pure-JAX path, and the naive oracle. Also the regenerated-RNG
variant (zero-parameter-traffic CWS, DESIGN.md §7) and the FUSED
featurization pipeline (``ops.cws``, ``emit="index"``) against its staged
composition — emitted to BENCH_cws_fused.json, with the stored-vs-regen
trajectory (wall-clock + modeled bytes moved; parameter input traffic is
zero on the regen path) in BENCH_cws_regen.json.

Wall-times here are CPU numbers — meaningful relative to each other for
the JAX paths; the interpret-mode Pallas time measures the interpreter,
not TPU performance (the TPU roofline for the kernel is derived
analytically in DESIGN.md §2: the kernel is VPU/HBM-bound at ~8
flops/byte over 3 param matrices; fusing the encode step removes half the
output traffic for the 0-bit scheme).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import emit, save_json, timed
from repro.core import cws_hash, make_cws_params
from repro.core.cws import cws_hash_regen
from repro.kernels import ops, registry
from repro.core.kernels import minmax_gram
from repro.pipeline import FeaturePipeline, FeatureSpec


def rand_nonneg(key, shape, sparsity=0.5):
    k1, k2 = jax.random.split(key)
    return (jnp.exp(jax.random.normal(k1, shape)) *
            jax.random.bernoulli(k2, 1 - sparsity, shape))


def bench_fused_vs_staged(fast: bool) -> dict:
    """Time fused (one kernel pass -> final indices) vs staged
    (hash -> encode -> offsets) featurization on a fixed (n, D, k) grid.

    Both sides run the registry's fast path for this backend (pure-JAX
    reference on CPU, Mosaic on TPU) so the ratio isolates the pipeline
    structure, not the interpreter.  A small interpret-mode shape records
    the fused kernel-body cost for the correctness path.
    """
    grid = [(256, 128, 128)] if fast else [(512, 256, 256),
                                           (1024, 512, 512),
                                           (2048, 512, 1024)]
    b_i, b_t = 8, 0
    results = {"b_i": b_i, "b_t": b_t, "backend": registry.backend(),
               "grid": {}}
    for (n, d, k) in grid:
        x = rand_nonneg(jax.random.PRNGKey(n + k), (n, d))
        pipe = FeaturePipeline.create(jax.random.PRNGKey(7), d,
                                      FeatureSpec(k, b_i=b_i, b_t=b_t))

        def staged():
            i_s, t_s = pipe.hashes(x)
            return pipe.features_from_hashes(i_s, t_s)

        out_f, us_fused = timed(lambda: pipe.features(x), repeats=3)
        out_s, us_staged = timed(staged, repeats=3)
        assert (out_f == out_s).all(), "fused != staged"
        key = f"n{n}_d{d}_k{k}"
        results["grid"][key] = {"fused_us": round(us_fused, 1),
                                "staged_us": round(us_staged, 1),
                                "speedup": round(us_staged /
                                                 max(us_fused, 1e-9), 3)}
        emit(f"cws_fused/{key}", us_fused,
             f"staged={us_staged:.0f}us "
             f"x{us_staged / max(us_fused, 1e-9):.2f}")

    # interpret-mode kernel-body cost (tiny shape; correctness path only)
    n, d, k = 64, 128, 64
    x = rand_nonneg(jax.random.PRNGKey(3), (n, d))
    p = make_cws_params(jax.random.PRNGKey(4), d, k)
    _, us = timed(lambda: ops.cws(x, p, emit="index", b_i=b_i, bn=64, bk=64,
                                  bd=64, interpret=True), repeats=1)
    emit("cws_fused/pallas_interpret(64x128x64)", us,
         "kernel-body correctness path")
    results["interpret_us_64x128x64"] = round(us, 1)
    save_json("BENCH_cws_fused", results)
    return results


def _ceil_to(v: int, b: int) -> int:
    return -(-v // b) * b


def _tile_traffic(n, d, k, bn, bk, bd, *, stored: bool):
    """Modeled HBM input bytes for one fused featurization launch at the
    given blocks (padded grid): x tiles are re-read once per hash block,
    stored parameters once per row block; the regen path reads NO
    parameter bytes (they are derived in-kernel, DESIGN.md §7)."""
    np_, dp_, kp_ = _ceil_to(n, bn), _ceil_to(d, bd), _ceil_to(k, bk)
    x_bytes = (kp_ // bk) * 4 * np_ * dp_
    param_bytes = (np_ // bn) * 12 * dp_ * kp_ if stored else 0
    return {"x_bytes": x_bytes, "param_bytes": param_bytes,
            "total_in_bytes": x_bytes + param_bytes}


def bench_stored_vs_regen(fast: bool) -> dict:
    """Stored-parameter vs regenerated-parameter (zero-parameter-traffic)
    fused featurization: wall-clock on the backend's fast path plus the
    modeled bytes-moved at the families' chosen blocks — emitted to
    BENCH_cws_regen.json so the trajectory accumulates per PR.

    Per (BN, BK) tile the stored kernel reads 4·BN·BD + 12·BD·BK input
    bytes and the regen kernel 4·BN·BD: parameter input traffic is
    identically zero, which is the whole point.
    """
    grid = [(256, 128, 128)] if fast else [(512, 256, 256),
                                           (1024, 512, 512),
                                           (2048, 512, 1024)]
    b_i, b_t = 8, 0
    results = {"b_i": b_i, "b_t": b_t, "backend": registry.backend(),
               "grid": {}}
    for (n, d, k) in grid:
        x = rand_nonneg(jax.random.PRNGKey(n + k), (n, d))
        key = jax.random.PRNGKey(11)
        spec = FeatureSpec(k, b_i=b_i, b_t=b_t)
        stored = FeaturePipeline.create(key, d, spec)
        regen = FeaturePipeline.create_regen(key, d, spec)

        _, us_stored = timed(lambda: stored.features(x), repeats=3)
        _, us_regen = timed(lambda: regen.features(x), repeats=3)

        sb = registry.choose_blocks(n, d, k, op="cws")
        rb = registry.choose_blocks(n, d, k, op="cws_rng")
        entry = {
            "stored": {"wall_us": round(us_stored, 1), "blocks": list(sb),
                       **_tile_traffic(n, d, k, *sb, stored=True)},
            "regen": {"wall_us": round(us_regen, 1), "blocks": list(rb),
                      **_tile_traffic(n, d, k, *rb, stored=False)},
        }
        entry["input_traffic_ratio"] = round(
            entry["stored"]["total_in_bytes"] /
            max(entry["regen"]["total_in_bytes"], 1), 3)
        key_s = f"n{n}_d{d}_k{k}"
        results["grid"][key_s] = entry
        emit(f"cws_regen/{key_s}", us_regen,
             f"stored={us_stored:.0f}us param_bytes 0 vs "
             f"{entry['stored']['param_bytes']} "
             f"(in-traffic x{entry['input_traffic_ratio']})")

    # interpret-mode kernel-body parity + cost at a tiny shape: the regen
    # kernel must agree bit-exactly with its reference impl
    n, d, k = 64, 128, 64
    x = rand_nonneg(jax.random.PRNGKey(3), (n, d))
    key = jax.random.PRNGKey(12)
    out_ref = ops.cws(x, key, emit="index", num_hashes=k, b_i=b_i,
                      impl="reference")
    out_int, us = timed(lambda: ops.cws(
        x, key, emit="index", num_hashes=k, b_i=b_i, bn=64, bk=64, bd=64,
        interpret=True), repeats=1)
    assert (out_int == out_ref).all(), "regen kernel != counter oracle"
    emit("cws_regen/pallas_interpret(64x128x64)", us,
         "kernel-body correctness path, bit-exact vs oracle")
    results["interpret_us_64x128x64"] = round(us, 1)
    save_json("BENCH_cws_regen", results)
    return results


def run(fast: bool = False):
    n, d, k = (256, 256, 256) if fast else (1024, 512, 512)
    x = rand_nonneg(jax.random.PRNGKey(0), (n, d))
    params = make_cws_params(jax.random.PRNGKey(1), d, k)

    flops = n * d * k * 8  # ~8 VPU ops per (row, dim, hash)

    _, us = timed(lambda: cws_hash(x, params, row_block=256, hash_block=128),
                  repeats=3)
    emit("cws/chunked_jax", us, f"{flops/us/1e3:.2f} GFLOP/s_cpu")

    _, us = timed(lambda: cws_hash_regen(x, jax.random.PRNGKey(2), k,
                                         hash_block=128), repeats=3)
    emit("cws/regen_rng", us, f"{flops/us/1e3:.2f} GFLOP/s_cpu "
         f"(0 bytes of stored r/c/beta)")

    small = (64, 128, 64)
    xs = rand_nonneg(jax.random.PRNGKey(3), small[:2])
    ps = make_cws_params(jax.random.PRNGKey(4), small[1], small[2])
    _, us = timed(lambda: ops.cws(xs, ps, emit="hash", bn=64, bk=64, bd=64,
                                  interpret=True), repeats=1)
    emit("cws/pallas_interpret(64x128x64)", us, "correctness-path only")

    bench_fused_vs_staged(fast)
    bench_stored_vs_regen(fast)

    # min-max Gram: pallas-tiling ref vs pure-jnp oracle
    m = 256 if fast else 512
    y = rand_nonneg(jax.random.PRNGKey(5), (m, d))
    gflops = 2 * m * n * d
    _, us = timed(lambda: minmax_gram(x, y, block=128), repeats=3)
    emit("minmax_gram/chunked_jax", us, f"{gflops/us/1e3:.2f} GFLOP/s_cpu")
    return True


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes (CI bench-smoke job)")
    run(fast=ap.parse_args().smoke)
