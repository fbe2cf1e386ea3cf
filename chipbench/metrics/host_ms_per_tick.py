"""Host loop (``runtime/serving.py``): mean host time per decode tick, in
ms. For each ``decode_tick`` span that starts in the window, the spans of
the ``host`` track that carry its ``tick`` are summed (``page_fault``,
``stage``, ``dispatch``, ``emit``), leaving out ``fetch_wait``, where the
host waits for the device. This is the host time that a synchronous
dispatch puts between two decode runs. None when the program records no
such phases."""


def read(run):
    host_ns: dict = {}
    for s in run.spans:
        tick = s.args.get("tick")
        if s.track == "host" and tick is not None and s.name != "fetch_wait":
            host_ns[tick] = host_ns.get(tick, 0) + (s.t1_ns - s.t0_ns)
    ticks = [s.args.get("tick") for s in run.spans if s.name == "decode_tick"
             and run.t0_ns <= s.t0_ns < run.t1_ns]
    if not host_ns or not ticks:
        return None
    return sum(host_ns.get(t, 0) for t in ticks) / len(ticks) * 1e-6
