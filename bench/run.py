#!/usr/bin/env python3
"""Run one benchmark cell once on the chip this process holds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the cell named in BENCHMARK.json from the seed, warms up, measures
for ``--seconds``, checks what the window produced against the plain
reference, and prints one JSON result line last on stdout.  The numbers
compared, each beside its limit, are the last lines on stderr.  With no
TPU, or fewer chips than the cell asks for, it exits 3 and prints no
result.
"""
import time

T_START = time.perf_counter()

import argparse   # noqa: E402
import json       # noqa: E402
import pathlib    # noqa: E402
import sys        # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    try:
        result, notes = harness.run(args.workload, args.seed, args.seconds,
                                    bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for line in notes:
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
