"""Median of the engine's ``decode_batch`` spans (one pool's decode
call, its host sync included) in a traced serving run, in ms."""
import numpy as np


def read(run):
    if run["kind"] != "serve" or not run.get("decode_spans"):
        return None
    return float(np.median(run["decode_spans"])) * 1e3
