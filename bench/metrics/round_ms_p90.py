"""90th percentile of the wall time of every call in the window, dispatch
to materialized metrics (host clock), in milliseconds."""
import numpy as np


def read(rec):
    ms = [1e3 * (r["t2"] - r["t0"]) for r in rec.rounds]
    return float(np.percentile(ms, 90))
