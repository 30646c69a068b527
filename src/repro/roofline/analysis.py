"""Roofline analysis from compiled XLA artifacts.

Three terms per (arch x shape x mesh), all PER DEVICE (the compiled SPMD
module is the per-device program, so cost_analysis numbers and HLO shapes
are already local), against the published peaks of the chip named by
``device_kind`` (``launch/mesh.py:PEAKS``; default: the production
meshes' TPU v5e; a kind without published peaks raises):

    compute_s    = HLO_FLOPs / PEAK_FLOPS
    memory_s     = HLO_bytes / HBM_BW
    collective_s = sum(bytes(op) * hops(op)) / (ICI_BW * ICI_LINKS)

``collective_bytes`` parses the post-SPMD optimized HLO text: every
all-reduce / all-gather / reduce-scatter / all-to-all / collective-permute
instruction contributes its result-buffer bytes (x2 for all-reduce: a ring
all-reduce moves ~2x the buffer).

Scan-body caveat (measured, DESIGN.md §Roofline-method): XLA's
cost_analysis counts a while-loop body ONCE, so a scanned-over-layers
model under-reports by ~num_layers.  The dry-run therefore assembles
totals *compositionally*: per-layer-signature functions are lowered
separately (with the q-chunk scan disabled) and scaled by layer counts,
plus the embed/loss head and the optimizer update.  Time-recurrent cores
(mamba / rwkv6) additionally report their scan cost analytically
(``ssm.recurrence_cost`` / ``rwkv6.recurrence_cost``) because no unrolled
lowering of 32k sequential steps is tractable.  The composition is
validated against a fully-unrolled small model in
tests/test_roofline.py.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Tuple

from repro.configs.base import ModelConfig
from repro.configs.shapes import InputShape
from repro.launch.mesh import TARGET_KIND, ChipPeaks, peaks_for

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:[a-z0-9]+\[[^\]]*\][^ ]*))\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

# effective traffic multiplier per collective kind (ring algorithms)
_FACTOR = {
    "all-reduce": 2.0,
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        nbytes = _DTYPE_BYTES.get(dt)
        if nbytes is None:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * nbytes
    return total


def collective_bytes(hlo_text: str) -> Tuple[float, Dict[str, float]]:
    """-> (weighted_bytes_total, raw bytes per collective kind)."""
    per_kind: Dict[str, float] = {}
    weighted = 0.0
    for m in _COLL_RE.finditer(hlo_text):
        shape_str, kind = m.group(1), m.group(2)
        b = _shape_bytes(shape_str)
        per_kind[kind] = per_kind.get(kind, 0.0) + b
        weighted += b * _FACTOR[kind]
    return weighted, per_kind


@dataclasses.dataclass
class RooflineResult:
    flops: float                 # per device
    hbm_bytes: float             # per device
    coll_bytes_weighted: float   # per device
    coll_by_kind: Dict[str, float]
    device_kind: str = TARGET_KIND

    @property
    def peaks(self) -> ChipPeaks:
        return peaks_for(self.device_kind)

    @property
    def compute_s(self) -> float:
        return self.flops / self.peaks.flops_bf16

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes / self.peaks.hbm_bw

    @property
    def collective_s(self) -> float:
        return self.coll_bytes_weighted / (self.peaks.ici_bw *
                                           self.peaks.ici_links)

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    def terms(self) -> Dict[str, float]:
        return {"compute_s": self.compute_s, "memory_s": self.memory_s,
                "collective_s": self.collective_s, "dominant": self.dominant}


def roofline_terms(flops: float, hbm_bytes: float, hlo_text: str,
                   device_kind: str = TARGET_KIND) -> RooflineResult:
    peaks_for(device_kind)                  # unknown chip: raise here
    w, kinds = collective_bytes(hlo_text)
    return RooflineResult(flops, hbm_bytes, w, kinds, device_kind)


# ---------------------------------------------------------------- analytic


def model_flops(cfg: ModelConfig, shape: InputShape) -> float:
    """MODEL_FLOPS (GLOBAL): 6*N*D for training, 2*N_active*D for a decode
    step, 2*N_active*D for prefill — the 'useful' FLOPs yardstick the
    HLO total is compared against (ratio catches remat/redundancy waste)."""
    n_active = cfg.active_param_count()
    if shape.mode == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.mode == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch
