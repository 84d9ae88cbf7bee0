"""Share of the training window spent planning: the sum of the steps'
``StepStats.plan_time_s`` over the window's wall time, in percent."""


def read(run):
    if run["kind"] != "train" or not run["steps"]:
        return None
    return 100.0 * sum(s["plan_time_s"] for s in run["steps"]) \
        / run["window_s"]
