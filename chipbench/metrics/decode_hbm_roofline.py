"""Whole step (the decode program, ``jit__lambda``; see
``devtrace.PROGRAMS``): least time over device time of the decode runs of
the traced window's ticks. A tick's least time is the bytes its step must
move over the chip's HBM bandwidth, as the configuration's family counts
them (``decode_step_bytes``: every weight once, each active row's live
keys and values, its recurrent state read and written, and its logits).
None where the family counts no such bytes."""

from chipbench import devtrace


def read(run):
    fam = run.cell.family
    if not run.traces or not hasattr(fam, "decode_step_bytes"):
        return None
    dt, d = run.traces[0], run.cell.dims
    least = spent = 0.0
    ticks = devtrace.ticks_in(dt, run.spans, run.timelines)
    for _, ctxs, prog in devtrace.decode_runs_of_ticks(dt, ticks):
        least += fam.decode_step_bytes(d, ctxs) / run.peaks["hbm_bytes_per_s"]
        spent += devtrace.device_time([prog])
    return 100.0 * least / spent if spent else None
