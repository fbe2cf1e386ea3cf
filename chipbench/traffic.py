"""The one traffic generator; each mix is a data file of its parameters.

A mix (``traffic/<name>.json``) names its loop (``"open"``: requests fall
due on a schedule whatever the server does; ``"closed"``: clients that each
send their next request as soon as the last one has finished) and its
length distributions; an open loop also its arrival rate, the lead of
arrivals before the window and the longest drain after it.

Every run sends the same work. The mix is cut into one *period* of
requests that hold the stratified quantiles of each distribution (prompt
lengths, output lengths and, in an open loop, the exponential gaps of a
Poisson stream), put in an order fixed by the mix's ``"order"`` (not by the
run's seed). The period repeats:

- open loop: the period is as long as the window, ``round(rate x window)``
  requests whose gaps add up to the window exactly, so the window holds one
  whole period whatever the seed; the seed chooses the phase, that is which
  request of the period is the first due in the window;
- closed loop: the period has one request per client (``clients``, the
  server's slots); client ``c`` sends the period's requests in turn from
  request ``c``. The first request of each client is cut to a share of its
  output, fixed per request of the period, as in a batch that has run for
  a while. The order is the same for every seed: once the pool is full,
  the order of admission decides which request is preempted, and with it
  how much is done in the window.

So two seeds send the same requests (in an open loop from another phase),
with other prompt tokens: the seed draws those.

Parameters::

    {"loop": "open", "rate_per_s": 1.3, "lead_s": 15.0, "drain_s": 60.0,
     "prompt": {"dist": "lognormal", "median": 512, "sigma": 0.8,
                "round_up": 256, "min": 256, "max": 3072},
     "output": {"dist": "uniform", "min": 16, "max": 512},
     "order": 0, "check": {"requests": 8, "tokens": 400}}
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import math
import os
from statistics import NormalDist
from typing import Any, Iterator

import numpy as np

_STD_NORMAL = NormalDist()


@dataclasses.dataclass(frozen=True)
class Request:
    due_s: float  # offset from the first arrival (open loop; 0 when closed)
    prompt: np.ndarray  # int32 token ids
    max_new: int


@dataclasses.dataclass(frozen=True)
class Period:
    """One period of a mix, in its fixed order."""

    gaps_s: np.ndarray | None  # open loop: gap after each request
    prompts: np.ndarray
    outputs: np.ndarray
    first_share: np.ndarray  # closed loop: share of the output a client's
    #                          first request asks for

    def __len__(self) -> int:
        return len(self.prompts)


def load_mix(path: str | os.PathLike) -> dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def quantile(spec: dict[str, Any], u: float) -> float:
    """Inverse CDF of a length distribution at ``u`` in (0, 1)."""
    if spec["dist"] == "lognormal":
        return spec["median"] * math.exp(spec["sigma"]
                                         * _STD_NORMAL.inv_cdf(u))
    if spec["dist"] == "uniform":
        return spec["min"] + u * (spec["max"] - spec["min"])
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def lengths(spec: dict[str, Any], n: int) -> np.ndarray:
    """The ``n`` stratified quantiles of a length distribution, rounded up
    to ``round_up`` (default 1) and clipped to ``[min, max]``."""
    step = int(spec.get("round_up", 1))
    out = []
    for i in range(n):
        x = math.ceil(quantile(spec, (i + 0.5) / n) / step) * step
        out.append(min(max(x, spec["min"]), spec["max"]))
    return np.asarray(out, np.int64)


def period_size(mix: dict[str, Any], window_s: float, clients: int) -> int:
    """Requests in one period: the window's arrivals (open loop) or one
    per client (closed loop)."""
    if mix["loop"] == "open":
        return max(1, round(float(mix["rate_per_s"]) * window_s))
    return clients


def period(mix: dict[str, Any], n: int, window_s: float | None = None
           ) -> Period:
    """The mix's period of ``n`` requests, in the mix's fixed order; an
    open loop's gaps add up to ``window_s``."""
    rng = np.random.default_rng([int(mix.get("order", 0)), 0x0de7])
    prompts = lengths(mix["prompt"], n)[rng.permutation(n)]
    outputs = lengths(mix["output"], n)[rng.permutation(n)]
    share = ((np.arange(n) + 0.5) / n)[rng.permutation(n)]
    gaps = None
    if mix["loop"] == "open":
        g = np.asarray([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
        gaps = (g / g.sum() * window_s)[rng.permutation(n)]
    return Period(gaps_s=gaps, prompts=prompts, outputs=outputs,
                  first_share=share)


def _tokens(rng, n: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, size=n, dtype=np.int32)


def generate(mix: dict[str, Any], seed: int, n: int, vocab: int,
             window_s: float) -> list[Request]:
    """The first ``n`` requests of an open-loop mix for ``seed``. The
    request at the seed's phase falls due just after ``lead_s``, so the
    window ``[lead_s, lead_s + window_s)`` holds the period once."""
    per = period(mix, period_size(mix, window_s, 0), window_s)
    p = len(per)
    rng = np.random.default_rng([seed, 0x7eaf])
    phase = int(rng.integers(p))
    starts = np.concatenate([[0.0], np.cumsum(per.gaps_s)])  # p + 1 points

    def at(q: int) -> float:  # arrival time of cyclic request q
        return window_s * (q // p) + float(starts[q % p])

    # Half the shortest gap after the lead, so that rounding to the clock
    # never moves the period's first or next request across an edge.
    lead = float(mix["lead_s"]) + 0.5 * float(per.gaps_s.min())
    q = phase
    while lead + at(q - 1) - at(phase) >= 0:
        q -= 1
    out = []
    for k in range(q, q + n):
        j = k % p
        out.append(Request(due_s=lead + at(k) - at(phase),
                           prompt=_tokens(rng, int(per.prompts[j]), vocab),
                           max_new=int(per.outputs[j])))
    return out


def streams(mix: dict[str, Any], seed: int, clients: int, vocab: int
            ) -> list[Iterator[Request]]:
    """A closed-loop mix for ``seed``: one endless stream of requests for
    each of ``clients`` clients, in the order the clients are first
    admitted. Each client draws its tokens from a generator of its own, so
    what it sends does not hang on when its requests finish."""
    per = period(mix, clients)

    def client(c: int) -> Iterator[Request]:
        rng = np.random.default_rng([seed, 0x7eaf, c])
        for k in itertools.count():
            j = (c + k) % clients
            new = int(per.outputs[j])
            if k == 0:
                new = max(1, math.ceil(new * float(per.first_share[j])))
            yield Request(due_s=0.0, prompt=_tokens(
                rng, int(per.prompts[j]), vocab), max_new=new)

    return [client(c) for c in range(clients)]


def prompt_grid(mix: dict[str, Any], n: int) -> list[int]:
    """Every prompt length a period of ``n`` requests sends (the shapes to
    warm up)."""
    return sorted(set(lengths(mix["prompt"], n).tolist()))


def longest(mix: dict[str, Any], n: int) -> int:
    """The most cache rows one request of a period of ``n`` can hold."""
    return int(lengths(mix["prompt"], n).max()
               + lengths(mix["output"], n).max())
