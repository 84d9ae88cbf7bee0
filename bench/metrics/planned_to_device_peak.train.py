"""The planner's predicted peak for the largest bucket of the window
(``StepStats.planned_peak_bytes``) over the chip's
``peak_bytes_in_use`` read right after the window, as a ratio."""


def read(run):
    if run["kind"] != "train" or not run["steps"] \
            or not run["memory_peak_bytes"]:
        return None
    top = max(s["seq"] for s in run["steps"])
    planned = max(s["planned_peak_bytes"] for s in run["steps"]
                  if s["seq"] == top)
    return planned / run["memory_peak_bytes"] if planned > 0 else None
