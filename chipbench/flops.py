"""Operations and bytes of the served forward, from the configuration's
shapes alone (``models/<family>.layers``).

* FLOPs are 2 x MACs of every conv and dense layer.
* A conv launch's bytes are the least it can move: its input, weights,
  bias, the residual it adds (where its epilogue adds one) and its output
  (pooled, where the 2x2 max-pool is fused), each once, at 4 bytes an
  element.
* A launch's roofline-minimum time is the larger of FLOPs over the peak
  FLOP/s and bytes over the peak HBM bandwidth.
"""
from __future__ import annotations

from typing import Dict, List

BYTES = 4   # float32 operands


def conv_out(ly: dict) -> int:
    return (ly["h"] + 2 * ly["pad"] - ly["k"]) // ly["stride"] + 1


def conv_launch(ly: dict, batch: int) -> Dict[str, float]:
    """FLOPs and least bytes of one conv layer's launch at ``batch``."""
    p = q = conv_out(ly)
    macs = batch * ly["cout"] * ly["cin"] * ly["k"] * ly["k"] * p * q
    out_hw = (p // 2) * (q // 2) if ly["pool"] else p * q
    elems = (batch * ly["cin"] * ly["h"] * ly["w"]
             + ly["cout"] * ly["cin"] * ly["k"] * ly["k"] + ly["cout"]
             + batch * ly["cout"] * out_hw
             + (batch * ly["cout"] * p * q if ly["residual"] else 0))
    return {"name": ly["name"], "flops": 2.0 * macs,
            "bytes": float(BYTES * elems)}


def conv_launches(layers: List[dict], batch: int) -> List[Dict[str, float]]:
    """One entry per conv launch of a forward at ``batch``, in order."""
    return [conv_launch(ly, batch) for ly in layers if ly["kind"] == "conv"]


def flops_per_image(layers: List[dict]) -> float:
    total = 0.0
    for ly in layers:
        if ly["kind"] == "conv":
            total += conv_launch(ly, 1)["flops"]
        else:
            total += 2.0 * ly["din"] * ly["dout"]
    return total


def roofline_min_s(launch: Dict[str, float], peak: Dict[str, float]) -> float:
    return max(launch["flops"] / peak["flops_per_s"],
               launch["bytes"] / peak["hbm_bytes_per_s"])
