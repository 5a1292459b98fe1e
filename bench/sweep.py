#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest arrival rate at which the
backlog does not grow over the window.  One deployment, one window per
rate, in increasing order; one JSON line per rate.

    python3 bench/sweep.py --workload nemotron15b-rag --seed 11 \\
        --seconds 40 --rates 6,7,8,9

Per rate: requests sent and completed, TTFT p50/p90 over the whole window
and over its first and last thirds (a backlog that grows shows as a last
third far above the first), TPOT p90, output tokens/s, and how long the
last request took to finish after the window closed.  The last line names
the knee, the highest rate sustained before the first that was not (every
request finished, within a quarter of the window after the close, and the
last third's median TTFT under twice the first third's, or one second),
and four fifths of it.
"""
import argparse
import asyncio
import copy
import json
import math

import numpy as np

import run as R  # bench/ is on sys.path when run as a script
from bench import traffic as TR


def sustained(line: dict, seconds: float) -> bool:
    return (line["completed"] == line["sent"]
            and line["drain_after_close_s"] <= seconds / 4
            and line["ttft_p50_last_third_ms"]
            <= 2 * max(line["ttft_p50_first_third_ms"], 1000.0))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = R.load_cell(args.workload)
    spec, mix = cell["spec"], cell["mix"]
    devices = R.require_chips(cell["cell"]["chips"])
    R.peak_of(devices[0].device_kind)
    R.enable_compile_cache()
    cfg = R.model_config(spec)
    dep = R.Deployment(spec, mix, cfg,
                       R.make_params(spec, cfg, args.seed, devices[0]),
                       devices)
    dep.warm(np.random.default_rng(args.seed))
    knee = None
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        m = copy.deepcopy(mix)
        m["arrival"]["rate"] = rate
        reqs = TR.schedule(m, args.seed + i, args.seconds, cfg.vocab_size)
        out = asyncio.run(R.drive(dep, reqs, args.seconds))
        recs, t0, tc = out["records"], out["t0"], out["t_close"]
        done = [r for r in recs if r.done]
        ttft = [(r.t_first - r.t_due) * 1e3 for r in done]
        third = [[(r.t_first - r.t_due) * 1e3 for r in done
                  if k * args.seconds / 3 <= r.t_due - t0
                  < (k + 1) * args.seconds / 3] for k in (0, 2)]
        e2e = R.end_to_end(recs, t0, tc, 0.0)
        line = {"rate": rate, "sent": len(recs), "completed": len(done),
                "ttft_p50_ms": e2e["ttft_p50_ms"],
                "ttft_p90_ms": e2e["ttft_p90_ms"],
                "ttft_p50_first_third_ms": TR.p_quantile(third[0], 50),
                "ttft_p50_last_third_ms": TR.p_quantile(third[1], 50),
                "tpot_p90_ms": e2e["tpot_p90_ms"],
                "output_tok_per_s": e2e["output_tok_per_s"],
                "drain_after_close_s": max((r.t_last for r in done),
                                           default=math.nan) - tc,
                "waves": len(dep.probe.waves)}
        dep.probe.waves.clear()
        print(json.dumps(line), flush=True)
        if out["stuck"] or not sustained(line, args.seconds):
            break
        knee = rate
    print(json.dumps({"knee": knee, "cell_rate": None if knee is None
                      else round(0.8 * knee, 2)}), flush=True)


if __name__ == "__main__":
    main()
