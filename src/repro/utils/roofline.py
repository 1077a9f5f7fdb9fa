"""Roofline-term computation from compiled dry-run artifacts.

Peak rates live in :data:`PEAKS`, keyed by ``jax.devices()[0].device_kind``
with their published source. A device kind that is not in the table gets
no modeled roofline: every term below is ``None`` for it, never another
chip's peaks.

Terms (seconds, per chip — cost_analysis FLOPs/bytes are whole-program, so
divide by chip count):
    compute    = HLO_FLOPs   / (chips * peak bf16 FLOP/s)
    memory     = HLO_bytes   / (chips * HBM bytes/s)
    collective = coll_bytes  / (chips * ICI bytes/s per link)
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    bf16_flops: float     # FLOP/s
    int8_ops: float       # OP/s
    hbm_bw: float         # bytes/s
    ici_bw: float         # bytes/s per inter-chip link
    source: str


PEAKS: dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(
        bf16_flops=197e12, int8_ops=393e12, hbm_bw=819e9, ici_bw=50e9,
        source='Google Cloud documentation, "TPU v5e": per chip 197 TFLOP/s '
               'bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s '
               'ICI over 4 links (50 GB/s each)'),
}


def peaks_for(device_kind: str) -> DevicePeaks | None:
    """Published peaks of ``device_kind``, or None where none are known."""
    return PEAKS.get(device_kind)


@dataclasses.dataclass
class Roofline:
    flops: float                 # whole-program HLO FLOPs
    hbm_bytes: float             # whole-program HLO bytes accessed
    coll_bytes: float            # summed collective operand bytes
    chips: int
    peaks: DevicePeaks | None    # None: device without published peaks
    model_flops: float = 0.0     # analytic "useful" FLOPs (6ND etc.)

    def _term(self, amount: float, rate: str) -> float | None:
        if self.peaks is None:
            return None
        return amount / (self.chips * getattr(self.peaks, rate))

    @property
    def t_compute(self) -> float | None:
        return self._term(self.flops, "bf16_flops")

    @property
    def t_memory(self) -> float | None:
        return self._term(self.hbm_bytes, "hbm_bw")

    @property
    def t_collective(self) -> float | None:
        return self._term(self.coll_bytes, "ici_bw")

    @property
    def bottleneck(self) -> str | None:
        if self.peaks is None:
            return None
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float | None:
        """Roofline-model step time (no overlap assumption = max)."""
        if self.peaks is None:
            return None
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_frac(self) -> float:
        return self.model_flops / self.flops if self.flops else 0.0

    @property
    def roofline_fraction(self) -> float | None:
        """Fraction of the ideal (useful-compute-only) time: how close the
        whole program is to the pure-MFU roofline."""
        if self.peaks is None:
            return None
        ideal = self._term(self.model_flops, "bf16_flops")
        return ideal / self.t_bound if self.t_bound > 0 else 0.0

    def as_dict(self) -> dict:
        return {
            "flops": self.flops, "hbm_bytes": self.hbm_bytes,
            "coll_bytes": self.coll_bytes, "chips": self.chips,
            "model_flops": self.model_flops,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "useful_flops_frac": self.useful_flops_frac,
            "roofline_fraction": self.roofline_fraction,
        }


def model_flops_train(n_params_active: float, tokens: float) -> float:
    """6·N·D for a train step (fwd 2ND + bwd 4ND)."""
    return 6.0 * n_params_active * tokens


def model_flops_decode(n_params_active: float, tokens: float,
                       kv_read_flops: float = 0.0) -> float:
    """2·N per generated token (+ attention score flops if significant)."""
    return 2.0 * n_params_active * tokens + kv_read_flops
