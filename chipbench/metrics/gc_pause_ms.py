"""Host loop (``runtime/serving.py``): ms the window spent in Python
collections, the summed ``gc`` spans (``host`` track) that start in it;
0 when none ran. None when the program records no ``host`` track, and so
no collections."""


def read(run):
    if not any(s.track == "host" for s in run.spans):
        return None
    return sum(s.t1_ns - s.t0_ns for s in run.spans if s.name == "gc"
               and run.t0_ns <= s.t0_ns < run.t1_ns) * 1e-6
