"""The check's two readings for a cell, on the chip, in one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--seconds 3]

For each seed one run of ``bench/run.py`` with ``--control 1``: set-up, a
short window at the cell's own load through the timed path, and the
check on the same sample a benchmark run checks, with the control in the
program's place.  Its result line reads ``"correct": false`` at the
committed limit; its checks give the control's gap (``logit_gap``, the
upper reading) and the program's own (``program_logit_gap``, a sound
run's lower reading).  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT)]
    from bench import run

    for seed in args.seeds.split(","):
        run.main(["--workload", args.workload, "--seed", seed,
                  "--seconds", str(args.seconds), "--trace", "0",
                  "--control", "1"])


if __name__ == "__main__":
    main()
