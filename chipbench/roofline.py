"""Peaks of the chip and the least work of a DHLP-2 superstep.

The count is that of the work, not of one implementation's traffic, so a
better kernel can only read a higher share and never one above 100%:

* bytes: the operator once per superstep, ``nnz × 8`` (a 4-byte index and
  a 4-byte float32 weight per nonzero), and per column-iteration
  ``N × 4 × 3`` (read F, read the seed term, write F);
* flops: ``2·nnz + 4·N`` per column-iteration (a multiply-add per nonzero;
  scale, add, difference and max per row).

``nnz`` is the operator's nonzeros, never padded slots.
"""
from __future__ import annotations

from typing import Dict

#: Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.
#: "TPU v5 lite" is TPU v5e. Source: Google Cloud documentation, "TPU v5e":
#: 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of interconnect.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}


def superstep_work(nnz: int, n: int, supersteps: int, col_iters: int) -> Dict[str, float]:
    """Compulsory bytes and flops of ``supersteps`` supersteps that advance
    ``col_iters`` column-iterations in all."""
    return {
        "bytes": float(supersteps * nnz * 8 + col_iters * n * 4 * 3),
        "flops": float(col_iters * (2 * nnz + 4 * n)),
    }


def least_time(work: Dict[str, float], device_kind: str) -> Dict[str, object]:
    """The least seconds the chip could take, and which peak bounds it."""
    peak = PEAKS[device_kind]  # an unknown chip is an error, not a default
    t_bytes = work["bytes"] / peak["hbm_bw"]
    t_flops = work["flops"] / peak["flops"]
    bound = "bytes" if t_bytes >= t_flops else "flops"
    return {"seconds": max(t_bytes, t_flops), "bound": bound}
