"""Scheduler (``runtime/serving.py``): 90th percentile, over the requests
due in the window, of due time to the start of their ``admit`` span."""

from chipbench.timelines import percentile


def read(run):
    vals = [(run.timelines[u].admit_ns - run.due_ns[u]) * 1e-6
            for u in run.window_uids
            if u in run.timelines and run.timelines[u].admit_ns]
    return percentile(vals, 90)
