"""95th percentile, over the window's finished requests, of the wait
from a request's due arrival to its admission by the engine, in ms."""
import numpy as np


def read(run):
    if run["kind"] != "serve" or not run["requests"]:
        return None
    waits = [max(r["admit_s"] - r["arrival_s"], 0.0)
             for r in run["requests"]]
    return float(np.percentile(waits, 95)) * 1e3
