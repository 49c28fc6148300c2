"""Read a cell's control on several seeds, at the cell's own size.

    python3 bench/control.py --workload sift-closed --seeds 11,12,13

The control is the configuration's reference with `control=True` put in the
program's place (SIFT: projections at bfloat16 three-pass precision, one
step below the float32 the configuration states; Adult: ties broken towards
high ids, against the (count desc, id asc) order it guarantees).  Its
answers for as many query rows as a run samples are compared with the
reference's exactly as a run compares the program's, and each seed prints
one JSON line of the compared numbers beside the configuration's limits.
The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import check
import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    args = ap.parse_args(argv)
    cell, cfg, traffic, _ = run.resolve(args.workload)
    run.start_jax()
    run.check_devices(int(cell["chips"]))
    build = run.module("configs", cfg["name"])
    reference = run.module("configs", cfg["name"] + "_reference")
    k = int(traffic["k"])
    rows = cfg["check_requests"] * int(traffic["rows"])
    for seed in (int(s) for s in args.seeds.split(",")):
        q = build.queries(cfg, seed, rows)
        ids, counts, _ = reference.reference(
            cfg, seed, q, np.zeros((rows, k), np.int32), k, control=True)
        want_ids, want_counts, recount = reference.reference(cfg, seed, q, ids, k)
        numbers = check.compare(ids, counts, want_ids, want_counts, recount)
        print(json.dumps({"seed": seed, "numbers": numbers,
                          "limits": cfg["limits"],
                          "fails": not check.judge(numbers, cfg["limits"])}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
