#!/usr/bin/env python3
"""Find a serving cell's knee once: the highest offered rate at which the
backlog stays bounded through the window.

    python3 bench/knee.py --workload mnist-stored.serve --seconds 8 --rates 1000 2000 4000

One service, booted as the cell boots it, takes each rate's open-loop
schedule in turn (the cell's traffic file with ``rate_per_s`` replaced).
A rate holds when no request failed and the latency of the window's last
quarter (by due time) stays under twice that of its first quarter plus
5 ms: a growing queue makes late requests wait longer.  Prints one JSON
line per rate.  The benchmark's own runs never run this; the cell's
traffic file records the knee and the rate taken from it.
"""
import argparse
import json
import pathlib
import sys
import tempfile
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def holds(lat: np.ndarray) -> bool:
    q = max(len(lat) // 4, 1)
    if not np.all(np.isfinite(lat)):
        return False
    first, last = np.median(lat[:q]), np.median(lat[-q:])
    return bool(last < 2 * first + 5e-3)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    from bench import gen, harness
    from bench.drivers.serve import make_table, send
    from bench.program import make_pipeline
    from repro.core.linear_model import LinearParams
    from repro.serving import ServingService
    from repro.training import export_served_model

    cell = harness.load_cell(args.workload)
    harness.require_chips(int(cell["workload"]["chips"]))
    harness.setup_jax_cache()
    cfg, mix = cell["config"], dict(cell["traffic"])
    x, _ = gen.rows_for(cfg, args.seed, cfg["n_test"])
    x = np.asarray(x)
    wrapped = np.concatenate([x, x[:int(mix["rows_per_request"])]])
    w, b = make_table(cfg, args.seed)
    with tempfile.TemporaryDirectory() as d:
        bundle = pathlib.Path(d) / "bundle"
        export_served_model(LinearParams(w, b), make_pipeline(
            cfg, gen.sub_key(args.seed, gen.KEY_CWS)), bundle)
        with ServingService.from_bundle(bundle) as svc:
            for bucket in svc.runner.buckets:
                svc.score(wrapped[:bucket], timeout=60)
            for rate in args.rates:
                mix["rate_per_s"] = rate
                due, sizes = gen.request_schedule(mix, args.seed,
                                                  args.seconds)
                starts = np.concatenate([[0], np.cumsum(sizes)[:-1]]) % len(x)
                views = [wrapped[s:s + m] for s, m in zip(starts, sizes)]
                t0 = time.perf_counter() + 0.05
                lat, lag, _ = send(svc, views, due, t0, 30.0)
                ok = lat[np.isfinite(lat)] * 1e3
                print(json.dumps({
                    "rate_per_s": rate, "requests": len(lat),
                    "failed": int(np.sum(~np.isfinite(lat))),
                    "p50_ms": float(np.percentile(ok, 50)) if ok.size else None,
                    "p95_ms": float(np.percentile(ok, 95)) if ok.size else None,
                    "lag_p95_ms": float(np.percentile(lag, 95)) * 1e3,
                    "holds": holds(lat)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
