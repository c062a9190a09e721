"""Several runs of one cell in one process, for the knee sweep and the
readings the ``correct`` limits are set from.

    python3 benchmarks/chip/sweep.py --workload <cell> --seconds <s> \
        [--rates r1,r2,..] --seeds s1,s2,.. [--control adc] [--trace 1]

Each (rate, seed) pair is one call of run.py's ``main`` with ``--rate``
(the mix's own rate when no rates are given): the same loading, run and
result line, printed after a line ``{"rate": .., "seed": ..}``. Compiled
programs are shared between the runs, so their ``setup_s`` is not the
benchmark's.
"""
import argparse
import json
import sys
import time

import run as cli


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", choices=sorted(cli.CONTROLS))
    ap.add_argument("--trace", default="0")
    args = ap.parse_args(argv)
    rates = [r for r in args.rates.split(",") if r] or [None]
    for rate in rates:
        for seed in args.seeds.split(","):
            print(json.dumps({"rate": rate and float(rate), "seed": int(seed),
                              "control": args.control}), flush=True)
            rc = cli.main(
                ["--workload", args.workload, "--seed", seed,
                 "--seconds", args.seconds, "--trace", args.trace]
                + (["--rate", rate] if rate else [])
                + (["--control", args.control] if args.control else []),
                t_start=time.perf_counter())
            if rc:
                return rc
    return 0


if __name__ == "__main__":
    sys.exit(main())
