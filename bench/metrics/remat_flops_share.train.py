"""Forward FLOPs that the plans of the window's steps recompute, over
the model FLOPs of those steps (3x forward at the bucket's shape), in
percent.  A plan unit of ``num_layers / units`` blocks costs that many
blocks' forward at the step's (batch, bucket) shape; the output head is
never rematerialised."""
from bench.lib import flops


def read(run):
    if run["kind"] != "train" or not run["steps"]:
        return None
    m, B = run["model"], run["batch_size"]
    per_unit = m["num_layers"] / run["units"]
    redo = model = 0.0
    for s in run["steps"]:
        layer = flops.layer_fwd_flops(m, B, s["seq"])
        redo += s["remat_units"] * per_unit * layer
        model += 3.0 * (m["num_layers"] * layer
                        + flops.head_flops(m, B * s["seq"]))
    return 100.0 * redo / model
