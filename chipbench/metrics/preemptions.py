"""Cache (``runtime/kv_cache.py``): requests preempted for pages during the
window (the engine counter ``serving.preemptions``)."""


def read(run):
    key = "serving.preemptions"
    return float(run.counters1.get(key, 0) - run.counters0.get(key, 0))
