"""Idle share of the chip in the traced window of a serving run:
1 - (union of device operation intervals) / traced window, in percent."""


def read(run):
    tr = run.get("trace")
    if run["kind"] != "serve" or not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
