"""90th percentile, over every request due in the window, of its due time
to its first token on the host. A request that never got one counts with
the time it waited until the run gave up."""

from chipbench.timelines import percentile


def read(run):
    if not run.due_ns:
        return None
    end = max(s.t1_ns for s in run.spans)
    vals = []
    for u in run.window_uids:
        tl = run.timelines.get(u)
        first = tl.first_ns if tl is not None and tl.first_ns else end
        vals.append((first - run.due_ns[u]) * 1e-6)
    return percentile(vals, 90)
