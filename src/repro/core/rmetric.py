"""The paper's R metric and streaming-necessity decision, adapted to TPU rooflines.

The paper (S3) measures a heterogeneous code stage-by-stage (H2D, KEX, D2H) and
defines the data-transfer ratio

    R = T_H2D / (T_H2D + T_KEX + T_D2H)

as the indicator of whether multiple streams are worthwhile:

  * R small (< ~0.1): not worthwhile -- pipeline fill/drain overhead and the
    programming effort outweigh the hidable transfer time (paper S3.4).
  * R in the middle band: stream it; the ideal gain is bounded by R.
  * R too large (> ~0.9): offloading itself is unprofitable (paper S3.4).

On a TPU pod the "transfer" stages are the memory and interconnect roofline
terms rather than PCIe copies.  ``StageTimes`` therefore carries the three
roofline terms derived from a compiled XLA executable:

    compute    = HLO_FLOPs / (chips * peak_FLOPs)       (the paper's KEX)
    memory     = HLO_bytes / (chips * HBM_bw)           (HBM <-> core "H2D")
    collective = collective_bytes / (chips * link_bw)   (inter-chip "H2D/D2H")

The paper's overlap model is kept verbatim:

    T_single-stream = sum(stages)                         (stage-by-stage)
    T_multi-stream  = max(stages) + fill/drain            (perfect pipeline)

with fill/drain = (n_streams-1)/n_streams * (sum(stages)-max(stages))/n_streams
approximated per Gomez-Luna et al. [4] as (sum-max)/n_streams.
"""

from __future__ import annotations

import dataclasses
import enum
import math
import re
from typing import Mapping

# ----------------------------------------------------------------------------
# Hardware model (TPU v5e per-chip numbers from the assignment).
# ----------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """Per-chip peak numbers for the roofline denominator."""

    name: str = "tpu-v5e"
    peak_flops: float = 197e12  # bf16 FLOP/s
    hbm_bw: float = 819e9  # bytes/s
    ici_bw: float = 50e9  # bytes/s per link
    hbm_bytes: float = 16 * 1024**3  # capacity, for fit checks
    vmem_bytes: float = 128 * 1024**2

    # Host-link numbers used only by the host-prefetch (true H2D) model.
    pcie_bw: float = 32e9


TPU_V5E = HardwareSpec()


# ----------------------------------------------------------------------------
# Stage times (the paper's H2D / KEX / D2H triple, generalized).
# ----------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StageTimes:
    """Seconds per stage for one task (or one step at cluster scale).

    ``h2d``/``d2h`` are the transfer-like stages; ``kex`` the compute stage.
    At cluster scale we map memory->h2d and collective->d2h by convention so
    the paper's formulas apply unchanged; use ``from_roofline`` for clarity.
    """

    h2d: float
    kex: float
    d2h: float = 0.0

    @property
    def total(self) -> float:
        return self.h2d + self.kex + self.d2h

    @property
    def stages(self) -> tuple[float, float, float]:
        return (self.h2d, self.kex, self.d2h)

    def ratio(self) -> float:
        """The paper's R = transfer / total (H2D flavour, R_{H2D})."""
        if self.total <= 0.0:
            return 0.0
        return self.h2d / self.total

    def transfer_ratio(self) -> float:
        """R counting both transfer stages (used for the decision)."""
        if self.total <= 0.0:
            return 0.0
        return (self.h2d + self.d2h) / self.total


@dataclasses.dataclass(frozen=True)
class RooflineTerms:
    """The three roofline terms (seconds) for one (arch, shape, mesh) cell."""

    compute: float
    memory: float
    collective: float

    @property
    def total_serial(self) -> float:
        """Unstreamed model: stages serialize (paper's single-stream time)."""
        return self.compute + self.memory + self.collective

    @property
    def total_overlapped(self) -> float:
        """Perfectly streamed model: max of stages (paper's T_multi, no fill)."""
        return max(self.compute, self.memory, self.collective)

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.compute,
            "memory": self.memory,
            "collective": self.collective,
        }
        return max(terms, key=terms.__getitem__)

    def as_stage_times(self) -> StageTimes:
        """Map roofline terms onto the paper's stage triple."""
        return StageTimes(h2d=self.memory, kex=self.compute, d2h=self.collective)

    def roofline_fraction(self) -> float:
        """Useful-compute fraction of the overlapped step time.

        1.0 means the step is exactly compute-bound at peak; lower means the
        dominant transfer term exceeds compute (the cell is transfer-bound).
        """
        t = self.total_overlapped
        return self.compute / t if t > 0 else 0.0


# ----------------------------------------------------------------------------
# Streaming-necessity decision (paper S3.4).
# ----------------------------------------------------------------------------


class StreamDecision(enum.Enum):
    NOT_WORTHWHILE = "not-worthwhile"  # R too small: overheads dominate
    STREAM = "stream"  # middle band: stream it
    OFFLOAD_UNPROFITABLE = "offload-unprofitable"  # R too large


# Paper S3.4: >50% of 223 configs sit below R=0.1, deemed not worthwhile;
# R ~ 0.9 deemed offload-unprofitable.
R_LOW = 0.10
R_HIGH = 0.90


def streaming_decision(
    times: StageTimes, *, r_low: float = R_LOW, r_high: float = R_HIGH
) -> StreamDecision:
    r = times.transfer_ratio()
    if r < r_low:
        return StreamDecision.NOT_WORTHWHILE
    if r > r_high:
        return StreamDecision.OFFLOAD_UNPROFITABLE
    return StreamDecision.STREAM


# ----------------------------------------------------------------------------
# Pipeline (multi-stream) time model.
# ----------------------------------------------------------------------------


def single_stream_time(times: StageTimes) -> float:
    """Stage-by-stage execution: stages serialize (paper's baseline)."""
    return times.total


def multi_stream_time(times: StageTimes, n_streams: int) -> float:
    """The paper's pipelined execution time with ``n_streams`` streams.

    The total work is split into ``n_streams`` equal tasks; stage s of task i
    overlaps stage s' of task j.  Steady state is bound by the largest stage;
    the pipeline additionally pays fill/drain of the non-dominant stages once.

      T = max_stage + (sum_stages - max_stage) / n_streams
    """
    if n_streams <= 1:
        return single_stream_time(times)
    s = times.total
    m = max(times.stages)
    return m + (s - m) / n_streams


def optimal_streams(
    times: StageTimes, *, max_streams: int = 64, overhead_per_task: float = 0.0
) -> int:
    """Pick the stream count minimizing modeled time (Gomez-Luna-style [4]).

    ``overhead_per_task`` models per-task launch/management cost, which makes
    very large stream counts counterproductive (paper S3.4 factor (1)).
    """
    best_n, best_t = 1, single_stream_time(times)
    for n in range(2, max_streams + 1):
        t = multi_stream_time(times, n) + overhead_per_task * n
        if t < best_t - 1e-12:
            best_n, best_t = n, t
    return best_n


def streaming_speedup(times: StageTimes, n_streams: int) -> float:
    """Modeled improvement of multi-stream over single-stream, as a fraction.

    Matches the paper's reported "performance improvement" figures:
    improvement = 1 - T_multi / T_single.
    """
    t1 = single_stream_time(times)
    tn = multi_stream_time(times, n_streams)
    if t1 <= 0.0:
        return 0.0
    return 1.0 - tn / t1


# ----------------------------------------------------------------------------
# Deriving roofline terms from a compiled executable (dry-run path).
# ----------------------------------------------------------------------------

# HLO collective ops whose operand bytes count as inter-chip traffic.
_COLLECTIVE_OPS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# e.g. "bf16[16,512,4096]{2,1,0}" -> dtype plus dims
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "pred": 1,
    "s4": 1,
    "u4": 1,
    "s8": 1,
    "u8": 1,
    "f8e4m3fn": 1,
    "f8e5m2": 1,
    "s16": 2,
    "u16": 2,
    "bf16": 2,
    "f16": 2,
    "s32": 4,
    "u32": 4,
    "f32": 4,
    "s64": 8,
    "u64": 8,
    "f64": 8,
    "c64": 8,
    "c128": 16,
}


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _line_output_bytes(line: str) -> int:
    """Bytes of the result shape(s) on an HLO instruction line.

    HLO lines look like::

      %ag = bf16[16,4096]{1,0} all-gather(%x), replica_groups=...
      %ar = (f32[8,128]{1,0}, f32[8,128]{1,0}) all-reduce(...)

    We count the *output* shapes (left of the op name), which for collectives
    equals the per-participant payload actually moved onto the wire for
    all-gather / all-to-all / collective-permute, and the reduced tensor for
    all-reduce (we then apply the 2x ring factor for all-reduce below).
    """
    head = line.split("=", 1)
    if len(head) != 2:
        return 0
    lhs_rhs = head[1]
    # Shapes appear before the op name; find the op position.
    total = 0
    for m in _SHAPE_RE.finditer(lhs_rhs):
        # Stop once we're past the op name (operands repeat shapes in some
        # dumps; outputs always come first).
        prefix = lhs_rhs[: m.start()]
        if any(op in prefix for op in _COLLECTIVE_OPS):
            break
        total += _shape_bytes(m.group(1), m.group(2))
    return total


def collective_bytes_from_hlo(hlo_text: str) -> dict[str, int]:
    """Sum output bytes of every collective op in an HLO text dump.

    Returns a dict op-kind -> bytes (plus "total").  all-reduce counts 2x
    (ring all-reduce moves ~2x the payload: reduce-scatter + all-gather).
    """
    per_op: dict[str, int] = {op: 0 for op in _COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if stripped.startswith("%") or stripped.startswith("ROOT"):
            for op in _COLLECTIVE_OPS:
                # Match " op(" or " op-start(" / " op-done(" forms.
                if f" {op}(" in stripped or f" {op}-start(" in stripped:
                    per_op[op] += _line_output_bytes(stripped)
                    break
    per_op["all-reduce"] *= 2
    per_op["total"] = sum(per_op[op] for op in _COLLECTIVE_OPS)
    return per_op


def roofline_from_cost(
    *,
    hlo_flops: float,
    hlo_bytes: float,
    collective_bytes: float,
    n_chips: int,
    hw: HardwareSpec = TPU_V5E,
) -> RooflineTerms:
    """Build the three roofline terms for one compiled step.

    ``hlo_flops`` / ``hlo_bytes`` are whole-program numbers from
    ``compiled.cost_analysis()`` (already per-device under SPMD: XLA reports
    the partitioned module).  ``collective_bytes`` comes from
    ``collective_bytes_from_hlo`` (also per-device payloads).
    """
    del n_chips  # cost_analysis is already per-partition under SPMD.
    return RooflineTerms(
        compute=hlo_flops / hw.peak_flops,
        memory=hlo_bytes / hw.hbm_bw,
        collective=collective_bytes / hw.ici_bw,
    )


def cost_analysis_scalars(cost: Mapping[str, float]) -> tuple[float, float]:
    """Extract (flops, bytes accessed) from compiled.cost_analysis()."""
    return float(cost.get("flops", 0.0)), float(cost.get("bytes accessed", 0.0))


def model_flops(n_params: float, n_tokens: float, *, backward: bool = True) -> float:
    """MODEL_FLOPS = 6*N*D for train (2*N*D forward-only)."""
    per_token = 6.0 * n_params if backward else 2.0 * n_params
    return per_token * n_tokens


def lavamd_counterexample() -> tuple[StageTimes, float]:
    """The paper's measured lavaMD negative case (S5).

    Returns the measured single-stream stage times and the measured
    multi-stream total (0.7242 s) which *exceeds* the single-stream total --
    the halo bytes ~= payload bytes regime where streaming loses.
    """
    return StageTimes(h2d=0.3476, kex=0.3380, d2h=0.0), 0.7242
