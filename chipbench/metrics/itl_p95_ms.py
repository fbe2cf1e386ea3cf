"""95th percentile over every gap between consecutive tokens of any request
that ends inside the window (host clock, at the fetch of each token)."""

from chipbench.timelines import gaps_ending_in, percentile


def read(run):
    gaps = [g for tl in run.timelines.values()
            for g in gaps_ending_in(tl, run.t0_ns, run.t1_ns)]
    v = percentile(gaps, 95)
    return None if v is None else v * 1e3
