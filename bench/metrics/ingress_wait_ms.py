"""Ingress queue wait, frontend layer (``serving/frontend.py``): mean over
the window's requests of (start of the ``runtime.serve`` wave that carried
the request - the time it was due).  Read from the benchmark's span
around every serve wave.  Moves ``ttft_p90_ms``."""
import numpy as np


def read(ctx):
    start = {}
    for w in ctx.waves:
        for uid in w.uids:
            start.setdefault(uid, w.t_start)
    waits = [start[r.uid] - r.t_due for r in ctx.records if r.uid in start]
    return float(np.mean(waits)) * 1e3 if waits else None
