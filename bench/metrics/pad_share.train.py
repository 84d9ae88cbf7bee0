"""Share of the computed tokens that are padding: 1 - effective over
bucket-shaped tokens of the window's steps (``StepStats``), in percent."""


def read(run):
    if run["kind"] != "train" or not run["steps"]:
        return None
    eff = sum(s["tokens"] for s in run["steps"])
    padded = sum(s["padded_tokens"] for s in run["steps"])
    return 100.0 * (1.0 - eff / padded)
