"""Model FLOP/s utilization of the training window: the forward and
backward FLOPs the model requires (3x forward, each sequence's attention
at its true length, recomputation not counted) of every step, over the
window's wall time times the chip's peak bf16 FLOP/s, in percent."""
from bench.lib import flops


def read(run):
    if run["kind"] != "train" or not run["steps"] or not run["peak_flops"]:
        return None
    work = sum(flops.train_model_flops(run["model"], s["lengths"])
               for s in run["steps"])
    return 100.0 * work / (run["window_s"] * run["peak_flops"])
