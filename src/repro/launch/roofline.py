"""Roofline term derivation from compiled dry-run artifacts.

Hardware peaks come from :data:`PEAKS`, one row per ``device_kind`` (as
``jax.devices()[0].device_kind`` names the chip) with its source; a
device that is not in the table raises rather than borrowing another
chip's numbers.

Terms (seconds, per step, per chip — the SPMD module is per-device, so
``cost_analysis`` flops/bytes are already per-chip):

    compute    = flops / peak
    memory     = bytes_accessed / hbm_bw
    collective = wire_bytes / (links × link_bw)

``wire_bytes`` comes from parsing the post-optimization HLO: for each
collective op we take the tensor bytes ``T`` (result shape; operands for
reduce-scatter) and apply the standard ring cost on the participating
group of size n: all-reduce 2·T·(n-1)/n, all-gather/reduce-scatter
T·(n-1)/n, all-to-all T·(n-1)/n, collective-permute T.
"""

from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, List, Optional

__all__ = ["HW", "PEAKS", "peaks", "parse_collectives", "roofline",
           "RooflineReport"]

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
}


@dataclasses.dataclass(frozen=True)
class HW:
    peak_flops: float                # bf16 FLOP/s per chip
    hbm_bw: float                    # HBM bytes/s per chip
    link_bw: float                   # ICI bytes/s per link
    links: int                       # ICI links per chip engaged
    source: str = ""


#: per-chip peaks keyed by ``device_kind``
PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM
    # at 819 GB/s, 1,600 Gbit/s of interchip interconnect per chip
    # (= 4 links x 50 GB/s)
    "TPU v5 lite": HW(peak_flops=197e12, hbm_bw=819e9, link_bw=50e9,
                      links=4, source="cloud.google.com/tpu/docs/v5e"),
}


def peaks(device_kind: str) -> HW:
    """The peaks row for ``device_kind``; KeyError for an unknown chip."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)})") from None


_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUP_RE = re.compile(r"replica_groups=\{(.*?)\}")
_GROUP_RE2 = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _group_size(line: str, total_devices: int) -> int:
    m = _GROUP_RE2.search(line)
    if m:  # iota format [n_groups, group_size]
        return int(m.group(2))
    m = _GROUP_RE.search(line)
    if m:
        first = m.group(1).split("}")[0].strip("{} ")
        if first:
            return len([t for t in first.split(",") if t.strip() != ""])
    return total_devices


_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.*?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")


def parse_collectives(hlo_text: str, total_devices: int) -> List[Dict]:
    """Extract every collective op: kind, tensor bytes, group size, wire
    bytes under the ring model.

    The result type sits between '=' and the op name; tuple-typed
    collectives (variadic all-reduce/all-to-all) sum all member shapes.
    Async pairs are counted once at the ``-start`` (or the sync form); the
    ``-done`` is skipped.
    """
    out = []
    for line in hlo_text.splitlines():
        m = _OP_RE.match(line)
        if not m:
            continue
        type_str, kind = m.group(1), m.group(2)
        shapes = _SHAPE_RE.findall(type_str)
        if not shapes:
            continue
        t_bytes = sum(_shape_bytes(dt, dims) for dt, dims in shapes)
        n = max(_group_size(line, total_devices), 1)
        ring = (n - 1) / n if n > 1 else 0.0
        factor = {"all-reduce": 2 * ring, "all-gather": ring,
                  "reduce-scatter": ring, "all-to-all": ring,
                  "collective-permute": 1.0}[kind]
        out.append({"kind": kind, "tensor_bytes": t_bytes, "group": n,
                    "wire_bytes": t_bytes * factor})
    return out


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_chip: float
    bytes_per_chip: float        # fusion-aware (see hlo_analysis)
    wire_bytes_per_chip: float
    bytes_all_per_chip: float    # pessimistic no-fusion upper bound
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops: float           # 6·N·D or 2·N·D (global)
    peak_flops: float            # per chip, from the peaks row used
    collectives: List[Dict] = dataclasses.field(default_factory=list)
    memory_analysis: Optional[Dict] = None
    raw_cost_analysis: Optional[Dict] = None

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time(self) -> float:
        """Perfect-overlap lower bound: max of the three terms."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_fraction(self) -> float:
        total = self.flops_per_chip * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def mfu(self) -> float:
        """Model-flops utilization at the roofline step time."""
        denom = self.step_time * self.chips * self.peak_flops
        return self.model_flops / denom if denom else 0.0

    def to_json(self) -> Dict:
        d = dataclasses.asdict(self)
        d.update(bottleneck=self.bottleneck, step_time=self.step_time,
                 useful_flops_fraction=self.useful_flops_fraction,
                 mfu=self.mfu)
        return d


def roofline(*, arch: str, shape: str, mesh: str, chips: int,
             cost: Dict, hlo_text: str, model_flops: float,
             hw: HW, memory_analysis: Optional[Dict] = None
             ) -> RooflineReport:
    """Roofline terms from the loop-corrected HLO analysis.

    ``cost`` (raw ``compiled.cost_analysis()``) is recorded alongside for
    reference, but the terms use :mod:`repro.launch.hlo_analysis`, which
    scales while-loop bodies by their trip counts — XLA's cost analysis
    counts scan bodies once, which would undercount our scan-heavy
    programs by 1–2 orders of magnitude.
    """
    from .hlo_analysis import analyze_hlo

    a = analyze_hlo(hlo_text, chips)
    rep = RooflineReport(
        arch=arch, shape=shape, mesh=mesh, chips=chips,
        flops_per_chip=a.flops, bytes_per_chip=a.bytes,
        wire_bytes_per_chip=a.wire_bytes, bytes_all_per_chip=a.bytes_all,
        compute_s=a.flops / hw.peak_flops,
        memory_s=a.bytes / hw.hbm_bw,
        collective_s=a.wire_bytes / (hw.links * hw.link_bw),
        model_flops=model_flops, peak_flops=hw.peak_flops,
        collectives=a.collectives,
        memory_analysis=memory_analysis,
    )
    rep.raw_cost_analysis = {"flops": float(cost.get("flops", 0.0)),
                             "bytes_accessed":
                                 float(cost.get("bytes accessed", 0.0))}
    return rep
