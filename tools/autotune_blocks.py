"""Measured block-size autotune harness for the kernel registry.

Sweeps (b1, b2, bd) candidates per op family on the LOCAL backend, times
each kernel launch, and persists the winners in ``BLOCK_TABLE`` format
(``registry.save_block_table`` JSON, replayable on any host via
``registry.load_block_table``).  This replaces the VMEM-model-seeded
entries with measured ones — run it on real TPU hardware to tune; on a
CPU container it exercises the exact same sweep through the Pallas
interpreter (mechanics + candidate legality, not TPU-representative
times, so keep shapes small).

Usage:

    # measure and persist (TPU: real Mosaic kernels)
    python -m tools.autotune_blocks \
        --families cws,cws_rng,cws_packed,cws_rng_packed,min_sum \
        --shapes 1024x512x512 4096x1024x1024 \
        --out benchmarks/results/block_table.json

    # CI smoke: enumerate candidates + heuristic picks, no timing, no I/O
    python -m tools.autotune_blocks --dry-run

Shapes are ``n x D x k`` for the cws families and ``m x D x n`` for
min_sum.  Winners are keyed on the pow2-bucketed shape, exactly like
``registry.choose_blocks`` lookups.
"""
from __future__ import annotations

import argparse
import pathlib
import sys

_ROOT = pathlib.Path(__file__).resolve().parents[1]
for p in (str(_ROOT), str(_ROOT / "src")):   # runnable as a bare script too
    if p not in sys.path:
        sys.path.insert(0, p)

import jax

from benchmarks.bench_cws_kernel import rand_nonneg
from benchmarks.common import timed
from repro.core.cws import make_cws_params
from repro.kernels import ops, registry

DEFAULT_SHAPES = {
    # small enough that an interpret-mode sweep stays tractable on CPU;
    # override with --shapes on TPU (e.g. 8192x65536x1024 for the paper's
    # word-vector scale)
    "cpu": ["256x128x128"],
    "tpu": ["1024x512x512", "4096x1024x1024"],
}


def _make_launcher(op: str, n: int, d: int, k: int):
    """A (blocks -> jax call) closure for one op family at one shape,
    pinned to the kernel-body impl of the local backend."""
    impl = registry.pallas_impl()
    x = rand_nonneg(jax.random.PRNGKey(0), (n, d))
    for source in registry.CWS_SOURCES:
        for emit in ("index", "packed"):
            if op == registry.cws_family(source, emit):
                state = (make_cws_params(jax.random.PRNGKey(1), d, k)
                         if source == "stored" else jax.random.PRNGKey(1))
                return lambda b: ops.cws(x, state, emit=emit, num_hashes=k,
                                         b_i=8, bn=b[0], bk=b[1], bd=b[2],
                                         impl=impl)
    if op == "min_sum":
        y = rand_nonneg(jax.random.PRNGKey(2), (k, d))
        return lambda b: ops.min_sum(x, y, bm=b[0], bn=b[1], bd=b[2],
                                     impl=impl)
    raise ValueError(f"unknown op family {op!r}")


def _clamp(blocks, n, d, k):
    return (min(blocks[0], n), min(blocks[1], k), min(blocks[2], d))


def tune(op: str, n: int, d: int, k: int, *, repeats: int,
         max_candidates: int = 0, dry_run: bool = False):
    """Sweep one (op, shape) cell; returns (winner_blocks, best_us, rows)."""
    cands = [_clamp(b, n, d, k)
             for b in registry.block_candidates(n, d, k, op=op)]
    cands = sorted(set(cands))
    if max_candidates and len(cands) > max_candidates:
        # evenly-spaced subsample keeps the sweep spanning small AND large
        # tiles (head-truncating the sorted list would only ever time the
        # smallest blocks and bias the persisted winner)
        step = len(cands) / max_candidates
        cands = [cands[int(i * step)] for i in range(max_candidates)]
    heur = registry.choose_blocks(n, d, k, op=op)
    print(f"[{op}] {n}x{d}x{k}: {len(cands)} candidates, "
          f"heuristic {heur}", flush=True)
    if dry_run:
        return heur, float("nan"), []

    launcher = _make_launcher(op, n, d, k)
    rows, best, best_us = [], None, float("inf")
    for b in cands:
        try:
            _, us = timed(lambda: launcher(b), repeats=repeats)
        except Exception as e:          # illegal tiling on this backend
            print(f"  {b}: SKIP ({type(e).__name__})", flush=True)
            continue
        rows.append((b, us))
        mark = ""
        if us < best_us:
            best, best_us, mark = b, us, "  <-- best"
        print(f"  {b}: {us:.0f} us{mark}", flush=True)
    if best is None:
        raise RuntimeError(f"no legal candidate for {op} at {n}x{d}x{k}")
    return best, best_us, rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--families", "--ops", dest="families",
                    default="cws,cws_rng,cws_packed,cws_rng_packed,min_sum",
                    help="comma-separated kernel families to sweep "
                         "(--ops is the legacy spelling)")
    ap.add_argument("--shapes", nargs="*", default=None,
                    help="problem shapes as NxDxK (default: per-backend)")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--max-candidates", type=int, default=0,
                    help="cap the per-cell sweep (0 = all)")
    ap.add_argument("--out", default="benchmarks/results/block_table.json")
    ap.add_argument("--dry-run", action="store_true",
                    help="enumerate candidates + heuristic picks only: no "
                         "timing, nothing written (CI smoke)")
    args = ap.parse_args(argv)

    backend = registry.backend()
    shapes = args.shapes or DEFAULT_SHAPES.get(backend,
                                               DEFAULT_SHAPES["cpu"])
    print(f"backend={backend} impl={registry.pallas_impl()} "
          f"shapes={shapes}", flush=True)

    entries = {}
    for op in args.families.split(","):
        op = op.strip()
        for s in shapes:
            n, d, k = (int(v) for v in s.lower().split("x"))
            best, best_us, _ = tune(op, n, d, k, repeats=args.repeats,
                                    max_candidates=args.max_candidates,
                                    dry_run=args.dry_run)
            if not args.dry_run:
                entries[registry.table_key(op, n, d, k)] = best
                print(f"[{op}] {s}: winner {best} @ {best_us:.0f} us",
                      flush=True)

    if args.dry_run:
        print("dry-run: no entries written")
        return 0

    registry.update_block_table(entries)
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    registry.save_block_table(out, entries)
    print(f"wrote {len(entries)} measured entries -> {out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
