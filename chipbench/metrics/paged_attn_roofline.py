"""Kernels (``kernels/paged_attention.py``, the ``paged_attention``
operations of the decode program in the trace): least time over device
time of the kernel's runs, over the decode ticks of the traced window.
The least time of one layer's call is the larger of its FLOPs and its
bytes (each active row's live keys and values in bfloat16, its query and
its output) over the chip's peaks (``flops.paged_attn_call``)."""

from chipbench import devtrace, flops


def read(run):
    if not run.traces:
        return None
    dt, d, pk = run.traces[0], run.cell.dims, run.peaks
    least = spent = 0.0
    ticks = devtrace.ticks_in(dt, run.spans, run.timelines)
    for _, ctxs, prog in devtrace.decode_runs_of_ticks(dt, ticks):
        evs = devtrace.kernel_events(dt, prog)
        if len(evs) != d.n_layers:
            continue
        f, b = flops.paged_attn_call(d, ctxs)
        least += d.n_layers * flops.least_seconds(
            f, b, pk["bf16_flops"], pk["hbm_bytes_per_s"])
        spent += devtrace.device_time(evs)
    return 100.0 * least / spent if spent else None
