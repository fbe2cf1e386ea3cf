"""Model step (fused prefill-chunk programs: the ``jit_fn`` runs that hold
a pass over every layer; see ``devtrace.PROGRAMS``): device time per 1,000
prompt tokens in the traced window. Every prompt of the mix lies on the
chunk grid, so each run is one full chunk."""

from chipbench import devtrace


def read(run):
    if not run.traces:
        return None
    runs = devtrace.runs_of(run.traces[0], "chunk", run.cell.dims.n_layers)
    if not runs:
        return None
    tokens = len(runs) * run.cell.server["prefill_chunk"]
    return devtrace.device_time(runs) * 1e3 / (tokens / 1e3)
