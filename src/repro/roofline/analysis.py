"""Roofline analysis from compiled dry-run artifacts (EXPERIMENTS.md
§Roofline).

Three terms, in seconds, for a step on the target TPU v5e pod:

    compute    = HLO_FLOPs_total   / (chips × 197e12 FLOP/s bf16)
    memory     = HLO_bytes_total   / (chips × 819e9  B/s HBM)
    collective = collective_bytes  / (chips × 50e9   B/s ICI link)

``cost_analysis()`` on the SPMD-partitioned executable reports the
*per-device* program; we report totals (× num chips) and divide back per
the formulas above. Collective bytes are NOT in cost_analysis: we parse the
post-SPMD HLO text and sum operand bytes of every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute (the per-device payload —
we deliberately do not model algorithm factors like ring 2(n-1)/n; the
relative comparisons that drive §Perf are unaffected).

MODEL_FLOPS (the "useful work" yardstick): 6·N·D for training, 2·N·D for
prefill, 2·N_active·B for one decode token; MoE archs use active params.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Optional

import jax
import numpy as np

PEAK_FLOPS = 197e12        # bf16 / chip
HBM_BW = 819e9             # B/s / chip
ICI_BW = 50e9              # B/s / link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def cost_analysis_dict(compiled) -> Dict[str, float]:
    """``compiled.cost_analysis()``: the compiled program's cost dict."""
    return compiled.cost_analysis()


def _shape_bytes(shape_str: str) -> int:
    """Bytes of one 'bf16[8,128]{...}'-style shape (tuples: sum parts)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum output-shape bytes per collective kind from post-SPMD HLO."""
    out = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        ls = line.lstrip()
        # lines look like:  %x = bf16[...] all-reduce(bf16[...] %y), ...
        m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|[a-z0-9]+\[[^\]]*\][^ ]*)\s+([a-z\-]+)", ls)
        if not m:
            continue
        op = m.group(2)
        if op.endswith("-start"):
            op = op[:-6]
        if op in _COLLECTIVES:
            out[op] += _shape_bytes(m.group(1))
            out["count"] += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


def count_params(params_shape: Any) -> int:
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params_shape))


def count_active_params(cfg, params_shape: Any) -> int:
    """MoE-aware: expert weights count at top_k/n_experts utilization."""
    flat, _ = jax.tree_util.tree_flatten_with_path(params_shape)
    total = 0
    for path, leaf in flat:
        n = int(np.prod(leaf.shape))
        ps = jax.tree_util.keystr(path)
        if cfg.moe and "moe" in ps and any(
                w in ps for w in ("w_in", "w_out", "w_gate")):
            n = int(n * cfg.top_k / cfg.n_experts)
        total += n
    return total


def model_flops(cfg, params_shape: Any, kind: str, tokens: int) -> float:
    n_active = count_active_params(cfg, params_shape)
    # embedding lookups are gathers, not FLOPs: subtract the embed table
    embed = cfg.vocab * cfg.d_model
    n_mm = max(n_active - embed, 1)
    if kind == "train":
        return 6.0 * n_mm * tokens
    return 2.0 * n_mm * tokens          # prefill / decode forward


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_total: float
    bytes_total: float
    coll_bytes_per_chip: float
    coll_count: int
    model_flops: float
    mem_per_device: Optional[float] = None

    @property
    def t_compute(self) -> float:
        return self.flops_total / (self.chips * PEAK_FLOPS)

    @property
    def t_memory(self) -> float:
        return self.bytes_total / (self.chips * HBM_BW)

    @property
    def t_collective(self) -> float:
        return self.coll_bytes_per_chip / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_ratio(self) -> float:
        return self.model_flops / max(self.flops_total, 1.0)

    @property
    def roofline_fraction(self) -> float:
        """MODEL_FLOPS-based fraction of peak at the step's critical time."""
        t = max(self.t_compute, self.t_memory, self.t_collective)
        return (self.model_flops / (self.chips * PEAK_FLOPS)) / max(t, 1e-30)

    def to_dict(self) -> Dict:
        d = dataclasses.asdict(self)
        d.update(t_compute=self.t_compute, t_memory=self.t_memory,
                 t_collective=self.t_collective, bottleneck=self.bottleneck,
                 useful_ratio=self.useful_ratio,
                 roofline_fraction=self.roofline_fraction)
        return d


def analyze(arch: str, shape: str, mesh_name: str, chips: int,
            compiled, cfg, params_shape, kind: str, tokens: int,
            hlo_text: Optional[str] = None) -> Roofline:
    cost = cost_analysis_dict(compiled)
    # cost_analysis is per-device on the partitioned module
    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))
    text = hlo_text if hlo_text is not None else compiled.as_text()
    coll = collective_bytes(text)
    mem = None
    try:
        ma = compiled.memory_analysis()
        mem = float(getattr(ma, "temp_size_in_bytes", 0)
                    + getattr(ma, "argument_size_in_bytes", 0)
                    + getattr(ma, "output_size_in_bytes", 0))
    except Exception:
        pass
    return Roofline(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        flops_total=flops_dev * chips, bytes_total=bytes_dev * chips,
        coll_bytes_per_chip=float(coll["total"]), coll_count=coll["count"],
        model_flops=model_flops(cfg, params_shape, kind, tokens),
        mem_per_device=mem)
