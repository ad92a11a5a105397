"""The predicted-vs-measured cost audit: close the loop on the memory
model that sizes chunks.

``runtime.memory`` fits an affine peak-bytes model from two compile-only
probes (c=1 and c=2) and the scheduler trusts the interpolation to pick
chunk sizes — but until now nothing ever checked the model against the
chunks that actually ran.  The audit joins every traced chunk to two
ground truths:

  peak_ratio   affine-model predicted peak bytes at the chunk's actual
               size vs the probed temporary bytes of the compiled
               program AT that size — how good the two-probe
               interpolation is where the scheduler used it (1.0 =
               perfect; the acceptance bar is *finite*, the report makes
               drift visible);
  time_ratio   measured wall-clock (span duration, ``block_until_ready``
               honest) vs the roofline lower bound
               max(FLOPs/peak_flops, bytes/hbm_bw) from the same
               compiled HLO — the fraction-of-roofline lens the serving
               layer's latency SLOs will inherit.

The roofline needs the peaks of the chip the chunks ran on: pass
``peaks=launch.roofline.peaks_for(device.device_kind)``.  Without
peaks (a CPU run, or a chip with no published entry) the audit reports
the memory side only — a time ratio against another chip's peaks would
be a wrong number, so it is left out rather than defaulted.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

from repro.launch.roofline import ChipPeaks

_EPS = 1e-12


@dataclasses.dataclass(frozen=True)
class ChunkAudit:
    """One traced chunk joined to its compile-time cost predictions."""

    label: str
    chunk_index: int
    chunk_size: int
    predicted_peak_bytes: float  # affine memory model at chunk_size
    probed_peak_bytes: float  # probed temp bytes at chunk_size
    flops: float  # hlo_cost.analyze roofline FLOPs
    hbm_bytes: float  # hlo_cost.analyze HBM traffic
    measured_s: float  # span duration (block_until_ready honest)

    @property
    def peak_ratio(self) -> float:
        """Affine-predicted / HLO-measured peak bytes (finite, > 0)."""
        return max(self.predicted_peak_bytes, _EPS) / max(
            self.probed_peak_bytes, _EPS
        )

    def roofline_s(self, peaks: ChipPeaks) -> float:
        """Roofline lower bound for one execution of the chunk program."""
        return max(self.flops / peaks.flops, self.hbm_bytes / peaks.hbm_bw)

    def time_ratio(self, peaks: ChipPeaks) -> float:
        """Measured / roofline seconds (>= ~1 when the model is sane)."""
        return max(self.measured_s, _EPS) / max(self.roofline_s(peaks), _EPS)


class CostAudit:
    """Accumulates :class:`ChunkAudit` rows across a traced run and
    renders them as a table / bench-JSON summary.  ``peaks`` (the
    chip's published peaks) enables the time side of the audit."""

    def __init__(self, peaks: Optional[ChipPeaks] = None):
        self.peaks = peaks
        self.rows: List[ChunkAudit] = []

    def record(self, row: ChunkAudit) -> None:
        self.rows.append(row)

    def __len__(self) -> int:
        return len(self.rows)

    def as_dicts(self) -> List[Dict]:
        rows = []
        for r in self.rows:
            d = {
                "label": r.label,
                "chunk_index": r.chunk_index,
                "chunk_size": r.chunk_size,
                "predicted_peak_bytes": r.predicted_peak_bytes,
                "probed_peak_bytes": r.probed_peak_bytes,
                "peak_ratio": r.peak_ratio,
                "flops": r.flops,
                "hbm_bytes": r.hbm_bytes,
                "measured_s": r.measured_s,
            }
            if self.peaks is not None:
                d["roofline_s"] = r.roofline_s(self.peaks)
                d["time_ratio"] = r.time_ratio(self.peaks)
            rows.append(d)
        return rows

    def summary(self) -> Dict:
        """Rollup for BENCH_results.json's ``obs.audit`` section."""
        if not self.rows:
            return {"n_chunks": 0}
        pr = [r.peak_ratio for r in self.rows]
        out = {
            "n_chunks": len(self.rows),
            "labels": sorted({r.label for r in self.rows}),
            "peak_ratio_min": min(pr),
            "peak_ratio_max": max(pr),
            "peak_ratio_mean": sum(pr) / len(pr),
        }
        if self.peaks is not None:
            tr = [r.time_ratio(self.peaks) for r in self.rows]
            out["time_ratio_min"] = min(tr)
            out["time_ratio_max"] = max(tr)
        return out

    def table(self) -> str:
        """Human-readable audit: one line per chunk, predicted vs
        measured side by side."""
        head = (
            f"{'label':<24} {'#':>3} {'size':>5} {'pred_peak':>10} "
            f"{'hlo_peak':>10} {'ratio':>6} {'meas_ms':>8} {'time_x':>9}"
        )
        lines = [head, "-" * len(head)]
        for r in self.rows:
            tx = ("not measured" if self.peaks is None
                  else f"{r.time_ratio(self.peaks):.1f}")
            lines.append(
                f"{r.label[:24]:<24} {r.chunk_index:>3} {r.chunk_size:>5} "
                f"{r.predicted_peak_bytes:>10.0f} {r.probed_peak_bytes:>10.0f} "
                f"{r.peak_ratio:>6.2f} {r.measured_s * 1e3:>8.2f} {tx:>9}"
            )
        return "\n".join(lines)
