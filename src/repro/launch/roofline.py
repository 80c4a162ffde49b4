"""Roofline-term extraction from a compiled (dry-run) executable.

Three terms per (arch x shape x mesh), in seconds:

  compute    = HLO_FLOPs            / (chips * 197e12)     [bf16 MXU]
  memory     = HLO_bytes_accessed   / (chips * 819e9)      [HBM]
  collective = per-device collective traffic / 50e9        [ICI link]

FLOPs / bytes come from ``compiled.cost_analysis()`` (per-device on the
host backend — verified empirically).  Collective traffic is NOT in
cost_analysis: we parse ``compiled.as_text()`` (post-SPMD-partitioning
HLO) and apply ring accounting per op:

  all-reduce      2 * size * (g-1)/g      (reduce-scatter + all-gather)
  all-gather      size_out * (g-1)/g      (receives everyone else's shard)
  reduce-scatter  size_out * (g-1)        (sends/combines g-1 shards)
  all-to-all      size * (g-1)/g
  collective-permute  size
"""

from __future__ import annotations

import re
from typing import NamedTuple

from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(?P<lhs>\(?[^=]*?)\s*"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")
_SHAPE_RE = re.compile(r"(?P<dt>[a-z]+[0-9]*(?:e[0-9]+m[0-9]+(?:fn)?)?)"
                       r"\[(?P<dims>[0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


class CollectiveStats(NamedTuple):
    bytes_by_op: dict[str, float]    # per-device traffic, ring-accounted
    count_by_op: dict[str, int]

    @property
    def total_bytes(self) -> float:
        return sum(self.bytes_by_op.values())


def _shape_bytes(text: str) -> float:
    total = 0.0
    for m in _SHAPE_RE.finditer(text):
        dt = m.group("dt")
        if dt not in _DTYPE_BYTES:
            continue
        dims = m.group("dims")
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_BRACE_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return default


def parse_collectives(hlo_text: str, n_devices: int) -> CollectiveStats:
    bytes_by_op: dict[str, float] = {}
    count_by_op: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m or "-done" in line:
            continue
        op = m.group("op")
        size = _shape_bytes(m.group("lhs"))
        g = max(_group_size(line, n_devices), 1)
        if op == "all-reduce":
            traffic = 2.0 * size * (g - 1) / g
        elif op == "all-gather":
            traffic = size * (g - 1) / g
        elif op == "reduce-scatter":
            traffic = size * (g - 1)
        elif op == "all-to-all":
            traffic = size * (g - 1) / g
        else:  # collective-permute
            traffic = size
        bytes_by_op[op] = bytes_by_op.get(op, 0.0) + traffic
        count_by_op[op] = count_by_op.get(op, 0) + 1
    return CollectiveStats(bytes_by_op, count_by_op)


class Roofline(NamedTuple):
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float
    t_compute: float
    t_memory: float
    t_collective: float
    bottleneck: str
    model_flops: float
    useful_ratio: float          # MODEL_FLOPS / (HLO_FLOPs * chips)

    def as_dict(self) -> dict:
        return self._asdict()


def roofline_from_terms(flops: float, bts: float, coll_bytes: float,
                        n_devices: int, model_flops: float) -> Roofline:
    """Per-device (flops, bytes, collective bytes) -> roofline terms."""
    t_c = flops / PEAK_FLOPS_BF16
    t_m = bts / HBM_BW
    t_x = coll_bytes / ICI_BW
    terms = {"compute": t_c, "memory": t_m, "collective": t_x}
    bottleneck = max(terms, key=terms.get)
    total_flops = flops * n_devices
    return Roofline(flops, bts, coll_bytes, t_c, t_m, t_x, bottleneck,
                    model_flops,
                    model_flops / total_flops if total_flops else 0.0)


def analyze(compiled, n_devices: int, model_flops: float,
            flops_correction: float = 0.0) -> Roofline:
    """``flops_correction``: GLOBAL FLOPs for scan bodies that
    cost_analysis counted once (intra-attention chunk loops); bytes are
    corrected at an assumed 100 FLOP/B intensity for those regions
    (fused online-softmax tiles are compute-leaning; documented
    approximation in EXPERIMENTS.md)."""
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0)) + flops_correction / n_devices
    bts = float(cost.get("bytes accessed", 0.0)) \
        + flops_correction / n_devices / 100.0
    coll = parse_collectives(compiled.as_text(), n_devices)
    return roofline_from_terms(flops, bts, coll.total_bytes, n_devices,
                               model_flops)
