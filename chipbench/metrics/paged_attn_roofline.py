"""Kernels (``kernels/paged_attention.py``, the ``paged_attention``
operations of the decode program in the trace): least time over device
time of the kernel's runs, over the decode ticks of the traced window.
A decode step calls the kernel once in each of the family's
``attention_layers``; a tick whose program shows another number of calls
is left out. The least time of one call is the larger of its FLOPs and its
bytes (each active row's live keys and values, its query and its output)
over the chip's peaks (the family's ``paged_attn_call``)."""

from chipbench import devtrace


def read(run):
    if not run.traces:
        return None
    dt, d, pk = run.traces[0], run.cell.dims, run.peaks
    fam = run.cell.family
    calls = fam.attention_layers(d)
    least = spent = 0.0
    ticks = devtrace.ticks_in(dt, run.spans, run.timelines)
    for _, ctxs, prog in devtrace.decode_runs_of_ticks(dt, ticks):
        evs = devtrace.kernel_events(dt, prog)
        if len(evs) != calls:
            continue
        f, b = fam.paged_attn_call(d, ctxs)
        least += calls * fam.least_seconds(
            f, b, pk["bf16_flops"], pk["hbm_bytes_per_s"])
        spent += devtrace.device_time(evs)
    return 100.0 * least / spent if spent else None
