"""Roofline-term extraction from compiled dry-run artifacts.

Per (arch x shape x mesh):

    compute    = FLOPs_per_device / peak_flops_per_chip
    memory     = bytes_per_device / hbm_bw_per_chip
    collective = moved_bytes_per_device / ici_link_bw

FLOPs and memory bytes come from ``compiled.cost_analysis()`` of the
SPMD-partitioned (per-device) module. Collective bytes are NOT in
cost_analysis: we parse the partitioned HLO text and apply ring-algorithm
movement factors per op (all-reduce moves ~2x its payload, gather/scatter
~1x, all-to-all/permute ~1x of the local shard).

Hardware constants come from ``CHIP_PEAKS``, keyed by the target chip's
``jax.Device.device_kind``; a kind that is not in the table is an error,
never a default.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, List, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops: float             # bf16 FLOP/s per chip
    hbm_bw: float            # HBM bytes/s per chip
    ici_bw: float            # ICI bytes/s per link (per direction)


# Published per-chip peaks keyed by ``jax.Device.device_kind``.
# "TPU v5 lite" is TPU v5e — Google Cloud documentation, "TPU v5e":
# 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
# interconnect (4 links -> 50 GB/s per link per direction).
CHIP_PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """The peak table row for ``device_kind``; unknown kinds raise."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{sorted(CHIP_PEAKS)} (add a sourced row to CHIP_PEAKS)"
        ) from None

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

# result-shape patterns like: bf16[16,512]{1,0} or (f32[8], f32[8])
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLLECTIVE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|\S+)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(", re.M)

_MOVE_FACTOR = {
    "all-reduce": 2.0,        # ring reduce-scatter + all-gather
    "all-gather": 1.0,        # output bytes ~ moved bytes
    "reduce-scatter": 1.0,    # input bytes ~ moved bytes (we count result*n?)
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}


def _shape_bytes(sig: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(sig):
        dt, dims = m.groups()
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Per-op-kind {count, bytes, moved_bytes} from partitioned HLO text.

    ``bytes`` = result payload of each collective (per-device); ``moved`` =
    payload x ring movement factor. ``-done`` ops are skipped so async pairs
    are not double-counted.
    """
    out: Dict[str, Dict[str, float]] = {}
    for m in _COLLECTIVE_RE.finditer(hlo_text):
        sig, kind = m.groups()
        full = m.group(0)
        if "-done(" in full:
            continue
        b = _shape_bytes(sig)
        rec = out.setdefault(kind, {"count": 0, "bytes": 0.0, "moved": 0.0})
        rec["count"] += 1
        rec["bytes"] += b
        rec["moved"] += b * _MOVE_FACTOR[kind]
    return out


@dataclasses.dataclass
class RooflineTerms:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float
    collectives: Dict[str, Dict[str, float]]
    peak_memory_bytes: Optional[float] = None
    model_flops: Optional[float] = None          # 6*N*D (global)
    model_bytes: Optional[float] = None          # HBM floor (global), decode
    kind: str = "train"                          # train | prefill | decode
    device_kind: str = "TPU v5 lite"             # target chip (CHIP_PEAKS)

    @property
    def peaks(self) -> ChipPeaks:
        return chip_peaks(self.device_kind)

    @property
    def t_compute(self) -> float:
        return self.flops_per_device / self.peaks.flops

    @property
    def t_memory(self) -> float:
        return self.bytes_per_device / self.peaks.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.collective_bytes_per_device / self.peaks.ici_bw

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def roofline_s(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_ratio(self) -> Optional[float]:
        """MODEL_FLOPS / HLO_FLOPs — catches remat/redundancy waste."""
        if not self.model_flops:
            return None
        return self.model_flops / (self.flops_per_device * self.chips)

    @property
    def useful_bytes_ratio(self) -> Optional[float]:
        """model_bytes / HLO_bytes — how much HBM traffic is irreducible
        (params + state read once per step). The decode-side waste metric."""
        if not self.model_bytes:
            return None
        return self.model_bytes / (self.bytes_per_device * self.chips)

    @property
    def roofline_fraction(self) -> Optional[float]:
        """Useful-work time / achievable step time (the score).

        Train/prefill are compute-normalised (useful = MODEL_FLOPS at peak).
        Decode is memory-normalised: one token must stream params + decode
        state through HBM once, so useful = model_bytes at full bandwidth —
        a FLOPs-normalised fraction would be ~0 by construction and wouldn't
        measure the implementation at all."""
        if self.kind == "decode":
            if not self.model_bytes:
                return None
            t_useful = self.model_bytes / (self.chips * self.peaks.hbm_bw)
            return t_useful / self.roofline_s
        if not self.model_flops:
            return None
        t_useful = self.model_flops / (self.chips * self.peaks.flops)
        return t_useful / self.roofline_s

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d.update(t_compute=self.t_compute, t_memory=self.t_memory,
                 t_collective=self.t_collective, bottleneck=self.bottleneck,
                 roofline_s=self.roofline_s,
                 useful_flops_ratio=self.useful_flops_ratio,
                 useful_bytes_ratio=self.useful_bytes_ratio,
                 roofline_fraction=self.roofline_fraction)
        return d


def model_bytes_for(cfg, shape, n_params: int, model=None) -> float:
    """Irreducible HBM bytes per decode step (global): every parameter and
    every decode-state byte is read exactly once to emit one token/seq."""
    import numpy as np

    dtype_bytes = 2 if cfg.dtype == "bfloat16" else 4
    total = n_params * dtype_bytes
    if model is not None and shape.kind == "decode":
        structs, _ = model.decode_state_shapes(shape, False)
        import jax
        for leaf in jax.tree.leaves(structs):
            total += np.prod(leaf.shape) * leaf.dtype.itemsize
    return float(total)


def model_flops_for(cfg, shape, n_params: int, n_active: Optional[int] = None
                    ) -> float:
    """6*N*D for training; 2*N*D_new for serving steps (decode: D_new =
    global_batch tokens; prefill: the full prompt)."""
    n = n_active if (n_active and cfg.family == "moe") else n_params
    if shape.kind == "train":
        return 6.0 * n * shape.tokens
    if shape.kind == "prefill":
        return 2.0 * n * shape.tokens
    return 2.0 * n * shape.global_batch          # decode: one token per seq


def kernel_mode_for_target(platform: Optional[str] = None) -> str:
    """Crossbar kernel mode for a roofline sweep cell on ``platform``.

    TPU cells lower the real Mosaic crossbar (``interpret=False`` — the HLO
    the sweep costs is the HLO the chip runs); every other target uses the
    XLA scatter data plane, which lowers the *same* flat address route so
    ``cost_analysis`` sees address-routed dispatch rather than an
    interpreter stand-in.  Pass the result to ``build_step(kernel_mode=...)``
    — call sites never branch on platform themselves.
    """
    import jax
    plat = platform or jax.default_backend()
    return "pallas" if plat == "tpu" else "xla"


def dense_routing_bytes(hlo_text: str, tokens: int, ports_x_capacity: int,
                        min_dtype_bytes: int = 2) -> int:
    """Bytes of the largest [T, P*C]-sized intermediate found in ``hlo_text``.

    The fabric's claim is that forward *and backward* route by flat address
    — no dense [tokens, n_ports*capacity] selection tensor is ever
    materialised (that tensor is the Mesh-TF one-hot formulation the
    scatter path exists to avoid).  Bench gating calls this on the lowered
    train-step HLO and asserts 0.  Returns the byte size of the worst
    offender so failures are actionable.

    A shape counts iff it has a ``tokens`` dim and its remaining dims
    multiply to exactly ``ports_x_capacity`` — that matches every layout of
    the selection tensor ([T,P*C], [T,P,C], [P,C,T], ...) while ordinary
    activations ([T, d_model], [T, d_ff]) only collide if the probe
    geometry makes a feature dim equal P*C (pick geometries that don't).
    """
    worst = 0
    for m in _SHAPE_RE.finditer(hlo_text):
        dt, dims = m.groups()
        if dt not in _DTYPE_BYTES or _DTYPE_BYTES[dt] < min_dtype_bytes:
            continue
        sizes = [int(d) for d in dims.split(",") if d]
        if tokens not in sizes:
            continue
        n = 1
        for d in sizes:
            n *= d
        if n == tokens * ports_x_capacity:
            worst = max(worst, n * _DTYPE_BYTES[dt])
    return worst


def extract(compiled, lowered=None) -> Tuple[float, float, Dict, Optional[float]]:
    """(flops, bytes, collectives, peak_mem) from a compiled artifact."""
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    text = compiled.as_text()
    colls = parse_collectives(text)
    peak = None
    try:
        mem = compiled.memory_analysis()
        peak = float(mem.temp_size_in_bytes + mem.argument_size_in_bytes
                     + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    except Exception:
        pass
    return flops, byts, colls, peak
