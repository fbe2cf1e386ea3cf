"""When each request's tokens reached the host, from the engine's spans.

The arithmetic of ``repro.obs.requests.reconstruct_timelines``, kept here
so that the yardstick stays with the benchmark: the ``admit`` span ends
when a request's first token has been fetched, and each decode tick span
(``decode_tick``/``spec_tick``) ends when its tokens have been fetched; its
``uids``/``toks`` lists say whose tokens, and how many each (a burst of
``n`` tokens counts ``n`` tokens at the span's end).

Also the percentile used by every latency metric: the nearest-rank
percentile of all samples, with no interpolation and no buckets.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Iterable

_TICKS = ("decode_tick", "spec_tick")


@dataclasses.dataclass
class Timeline:
    uid: int
    admit_ns: int = 0  # admission began (queue pop)
    prompt_len: int = 0
    token_ns: list[int] = dataclasses.field(default_factory=list)
    token_counts: list[int] = dataclasses.field(default_factory=list)
    evictions: int = 0

    @property
    def first_ns(self) -> int | None:
        return self.token_ns[0] if self.token_ns else None


def reconstruct(spans: Iterable) -> dict[int, Timeline]:
    """uid -> Timeline from the engine's ``Tracer`` spans."""
    tls: dict[int, Timeline] = {}

    def get(uid: int) -> Timeline:
        if uid not in tls:
            tls[uid] = Timeline(uid)
        return tls[uid]

    for s in sorted(spans, key=lambda s: (s.t1_ns, s.t0_ns)):
        a = s.args
        if s.name == "admit" and a.get("uid") is not None:
            tl = get(a["uid"])
            tl.admit_ns = s.t0_ns
            tl.prompt_len = int(a.get("prompt_len", 0))
            tl.token_ns.insert(0, s.t1_ns)
            tl.token_counts.insert(0, 1)
        elif s.name in _TICKS:
            for uid, n in zip(a.get("uids") or [], a.get("toks") or []):
                if int(n) > 0:
                    tl = get(uid)
                    tl.token_ns.append(s.t1_ns)
                    tl.token_counts.append(int(n))
        elif s.name == "evict" and a.get("uid") is not None:
            get(a["uid"]).evictions += 1
    return tls


def gaps_ending_in(tl: Timeline, t0_ns: int, t1_ns: int) -> list[float]:
    """Seconds between consecutive tokens of one request, for each gap that
    ends in ``[t0_ns, t1_ns)``; a burst of ``n`` tokens splits its gap into
    ``n`` equal gaps."""
    out = []
    for i in range(1, len(tl.token_ns)):
        end = tl.token_ns[i]
        if t0_ns <= end < t1_ns:
            n = tl.token_counts[i]
            out.extend([(end - tl.token_ns[i - 1]) * 1e-9 / n] * n)
    return out


def tokens_in(tls: Iterable[Timeline], t0_ns: int, t1_ns: int) -> int:
    return sum(c for tl in tls for t, c in zip(tl.token_ns, tl.token_counts)
               if t0_ns <= t < t1_ns)


def percentile(values: Iterable[float], q: float) -> float | None:
    """Nearest-rank ``q``-th percentile (0 < q <= 100); None when empty."""
    vals = sorted(values)
    if not vals:
        return None
    return vals[max(0, math.ceil(q / 100.0 * len(vals)) - 1)]
