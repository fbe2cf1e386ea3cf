"""Device: share of the traced window in which no operation ran on the
chip (1 - union of the ``XLA Ops`` intervals / window)."""

from chipbench import devtrace


def read(run):
    if not run.traces:
        return None
    dt = run.traces[0]
    return 100.0 * (1.0 - devtrace.busy_s(dt) / dt.window_s)
