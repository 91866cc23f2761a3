#!/usr/bin/env python3
"""Readings that set a cell's limits, over many seeds in one process.

    python3 bench/control.py --workload <name> --mode program --seconds 2 --seeds 101 102 ...
    python3 bench/control.py --workload <name> --mode control --seeds 201 202 203

``program`` runs the cell as ``bench/run.py`` does (with a short window)
and prints the numbers it compares; the largest over the seeds is a
limit's lower reading.  ``control`` puts the reference, computed in
bfloat16, in the program's place; the smallest over the seeds is the
upper reading, and the limit has to fail it.  Each seed prints one JSON
line; the last line gives the largest and the smallest of each number.
The benchmark's own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse   # noqa: E402
import json       # noqa: E402
import pathlib    # noqa: E402
import shutil     # noqa: E402
import sys        # noqa: E402
import tempfile   # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def readings(workload: str, seeds, mode: str, seconds: float, *,
             need_chip: bool = True, cell=None) -> list:
    """The numbers compared, one dict per seed."""
    from bench import harness
    cell = cell or harness.load_cell(workload)
    if need_chip:
        harness.require_chips(int(cell["workload"]["chips"]))
    harness.setup_jax_cache()
    counter = harness.CompileCounter()
    out = []
    for seed in seeds:
        scratch = pathlib.Path(tempfile.mkdtemp(prefix="bench-"))
        try:
            ctx = harness.Ctx(cell, seed=seed, seconds=seconds, trace=False,
                              t_start=time.perf_counter(), counter=counter,
                              scratch=scratch, control=mode == "control")
            driver = harness.load_module(
                harness.BENCH / "drivers" / f"{cell['traffic']['driver']}.py")
            got = driver.run(ctx)
            correct, _ = harness.judge(got.checks, cell["limits"])
            out.append({"seed": seed, "correct": correct,
                        "failed": got.failed, **got.checks})
        finally:
            shutil.rmtree(scratch, ignore_errors=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--mode", choices=("program", "control"), required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from bench import harness
    try:
        rows = readings(args.workload, args.seeds, args.mode, args.seconds)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for r in rows:
        print(json.dumps(r), flush=True)
    names = [k for k in rows[0] if k not in ("seed", "correct", "failed")]
    print(json.dumps({"mode": args.mode, "workload": args.workload,
                      "max": {k: max(r[k] for r in rows) for k in names},
                      "min": {k: min(r[k] for r in rows) for k in names},
                      "correct": [r["correct"] for r in rows]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
