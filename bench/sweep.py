#!/usr/bin/env python3
"""Knee sweep: run one cell at several camera counts, in one process.

    python bench/sweep.py --workload <cell> --cameras 2,4,8 --seconds 8

Every count uses the same ``--seed``, so the detect program (which
embeds the weights) compiles once.  Prints one JSON line per count (the run's ``window`` summary and its
end-to-end metrics) and writes them all to ``--out``.  The knee is the
highest count at which no frame was interpolated and the median emit
latency of the window's last third is not above its first third's by
more than a frame period.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--cameras", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    rows = []
    for n in [int(c) for c in args.cameras.split(",")]:
        res = run.run_cell(args.workload, args.seed, args.seconds, False,
                           cameras=n, log=lambda s: None)
        rows.append({"cameras": n, "correct": res["correct"],
                     **res["window"],
                     **{k: v["value"] for k, v in res["metrics"].items()}})
        print(json.dumps(rows[-1]), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(rows, indent=1))


if __name__ == "__main__":
    main()
