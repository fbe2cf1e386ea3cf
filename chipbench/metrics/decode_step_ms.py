"""Model step (the decode program, ``jit__lambda`` in the trace; see
``devtrace.PROGRAMS``): mean device time per run of the decode step in the
traced window."""

from chipbench import devtrace


def read(run):
    if not run.traces:
        return None
    runs = devtrace.runs_of(run.traces[0], "decode", run.cell.dims.n_layers)
    if not runs:
        return None
    return devtrace.device_time(runs) / len(runs) * 1e3
