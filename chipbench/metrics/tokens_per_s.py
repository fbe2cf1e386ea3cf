"""Output tokens that reached the host inside the window, per second of the
window."""

from chipbench.timelines import tokens_in


def read(run):
    n = tokens_in(run.timelines.values(), run.t0_ns, run.t1_ns)
    return n / run.window_s
