"""Experts (``models/moe.py`` ``moe_apply_held``): token rows each held
expert computed per decode tick, on average over the run's window. The
decode program itself counts, on the device, the token-expert pairs of its
live rows that fell on held experts (counter ``moe.decode_pairs_here``);
its change over the window is divided by the family's ``expert_units``
(held experts times expert layers) and by the decode ticks that started in
the window. None where the program keeps no such counter."""


def read(run):
    key = "moe.decode_pairs_here"
    fam = run.cell.family
    if key not in run.counters1 or not hasattr(fam, "expert_units"):
        return None
    ticks = sum(1 for s in run.spans
                if s.name == "decode_tick" and s.t0_ns >= run.t0_ns)
    if not ticks:
        return None
    pairs = run.counters1[key] - run.counters0.get(key, 0)
    return pairs / (fam.expert_units(run.cell.dims) * ticks)
