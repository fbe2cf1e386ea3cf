"""Whole step: model FLOPs of every prompt and output token processed in
the traced window, over the window's length times the chip's bf16 peak.
Decode ticks and prefill chunks count when their host span lies inside
the traced window, as the configuration's family counts them."""

from chipbench import devtrace


def read(run):
    if not run.traces:
        return None
    dt, d, fam = run.traces[0], run.cell.dims, run.cell.family
    total = 0
    for _, ctxs in devtrace.ticks_in(dt, run.spans, run.timelines):
        total += sum(fam.token_flops(d, c) for c in ctxs)
    chunk = run.cell.server["prefill_chunk"]
    for s in devtrace.spans_in(dt, run.spans, "prefill_chunk"):
        total += fam.chunk_flops(d, s.args["pos"] - chunk, chunk)
    return 100.0 * total / (dt.window_s * run.peaks["bf16_flops"])
