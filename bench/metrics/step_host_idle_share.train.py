"""Share of the traced window of a training run in which the chip waits
on the program's own host path: the idle gaps named by the program's
spans (``program:<span>``) over the traced window, in percent.

Idle time under the harness's spans (``bench:feed``, the stand-in for a
data loader, and ``bench:step``), launch gaps (``short_gaps``) and
unnamed gaps stay out.  Each gap goes whole to the innermost span open
at its midpoint (``devtrace``), so the sum over the program's spans is
sound and a split between them only indicative.  The trace keeps only
the ``devtrace.TOP`` largest names: a ``program:`` name that falls off
is smaller than every name kept, so the sum falls short by at most the
total of the names dropped.  None without a trace, and for serving."""

PREFIX = "program:"


def read(run):
    tr = run.get("trace")
    if run["kind"] != "train" or not tr or tr["window_s"] <= 0:
        return None
    idle = sum(s for name, s in tr["idle_gaps"] if name.startswith(PREFIX))
    return 100.0 * idle / tr["window_s"]
