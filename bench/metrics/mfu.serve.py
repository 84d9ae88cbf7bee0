"""Model FLOP/s utilization of the serving window: the forward FLOPs of
every prompt whose first token came within the window (its layers at the
prompt's length, the head for one position) and of every decoded token
within it (attending to its context), over the window times the chip's
peak bf16 FLOP/s, in percent."""
from bench.lib import flops


def read(run):
    if run["kind"] != "serve" or not run["requests"] or not run["peak_flops"]:
        return None
    m, S = run["model"], run["window_s"]
    work = 0.0
    for r in run["requests"]:
        times = r["token_times"]
        if not times or times[0] > S:
            continue
        L = r["prompt"]
        work += (m["num_layers"] * flops.layer_fwd_flops(m, 1, L)
                 + flops.head_flops(m, 1))
        work += sum(flops.decode_token_flops(m, L + i)
                    for i, t in enumerate(times[1:]) if t <= S)
    return 100.0 * work / (S * run["peak_flops"])
