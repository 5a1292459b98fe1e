#!/usr/bin/env python3
"""Readings that set a cell's correctness limit: the numbers compared of
sound runs of the program on many seeds (the lower reading of the logit
gap is the largest of them) and of the control, the reference computed in
a lower precision and put in the program's place, on a few (the upper
reading is the smallest).  Every seed is one whole benchmark run of the
cell (``run.run``: its weights, deployment, load, window and check), all
in one process; the control goes through the same ``check`` as the
program, on the same prompts and served tokens.

    python3 bench/calibrate.py --workload nemotron15b-rag --seconds 20 \\
        --seeds 1,2,3 --control-seeds 1,2 --controls int8

One JSON line per seed: each side's numbers, each beside its limit, and
whether that side came out correct.
"""
import argparse
import json
import time

import run as R  # bench/ is on sys.path when run as a script


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--controls", default="int8")
    args = ap.parse_args(argv)
    cell = R.load_cell(args.workload)
    with_controls = {int(s) for s in args.control_seeds.split(",") if s}
    controls = tuple(q for q in args.controls.split(",") if q)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        res = R.run(args.workload, seed, args.seconds, False, cell=cell,
                    controls=controls if seed in with_controls else ())
        line = {"seed": seed, "attempted": res["attempted"],
                "failed": res["failed"], "correct": res["correct"],
                "checks": res["checks"]}
        for q, checks in res["_controls"].items():
            line[q] = {"correct": R.is_correct(checks),
                       "checks": {k: v for k, (v, _) in checks.items()}}
        line["seconds"] = time.perf_counter() - t
        print(json.dumps(line), flush=True)
        if res["_stuck"]:
            break


if __name__ == "__main__":
    main()
