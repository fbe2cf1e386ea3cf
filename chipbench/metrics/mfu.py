"""Whole step: model FLOPs of every prompt and output token processed in
the traced window, over the window's length times the chip's bf16 peak.
Decode ticks and prefill chunks count when their host span lies inside
the traced window."""

from chipbench import devtrace, flops


def read(run):
    if not run.traces:
        return None
    dt, d = run.traces[0], run.cell.dims
    total = 0
    for _, ctxs in devtrace.ticks_in(dt, run.spans, run.timelines):
        total += sum(flops.token_flops(d, c) for c in ctxs)
    chunk = run.cell.server["prefill_chunk"]
    for s in devtrace.spans_in(dt, run.spans, "prefill_chunk"):
        total += flops.chunk_flops(d, s.args["pos"] - chunk, chunk)
    return 100.0 * total / (dt.window_s * run.peaks["bf16_flops"])
