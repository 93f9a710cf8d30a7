"""The comparison that decides ``correct``.

Every answered request's logits, as they came over HTTP, are compared
with the plain reference (``models/<family>.py``, float32 at HIGHEST) of
the same images.  Per image the error is ``max|served - reference| /
max|reference|``; the number compared is the largest over all images of
the run.  A request that was not answered 200 by the primary path
(``served_by == "primary"``, the fold kernels), or whose logits are not
finite or have the wrong shape, counts as failed, and the limit on
failures is 0.
"""
from __future__ import annotations

import base64
from typing import Dict, List, Tuple

import numpy as np


def decode_reference(ref: Dict[str, str]
                     ) -> Dict[Tuple[int, int], np.ndarray]:
    """The child's reference logits, keyed by body."""
    out = {}
    for key, b64 in ref.items():
        n, i = (int(v) for v in key.split(","))
        out[(n, i)] = np.frombuffer(base64.b64decode(b64),
                                    np.float32).reshape(n, -1)
    return out


def compare(records, ref: Dict[Tuple[int, int], np.ndarray]) -> dict:
    """``records`` are parsed ``loadgen.Record``s."""
    worst, failed, images = 0.0, 0, 0
    for rec in records:
        if rec.status != 200 or rec.served_by != "primary" \
                or rec.logits is None:
            failed += 1
            continue
        got = np.asarray(rec.logits, np.float32)
        want = ref.get(rec.key)
        if want is None or got.shape != want.shape \
                or not np.isfinite(got).all():
            failed += 1
            continue
        err = (np.abs(got - want).max(axis=1)
               / np.abs(want).max(axis=1))
        worst = max(worst, float(err.max()))
        images += len(got)
    return {"logit_err": worst, "failed": failed, "images": images}


def checks(result: dict, cfg: dict) -> List[Tuple[str, float, float]]:
    """(name, value, limit) of every number compared."""
    return [("logit_err", result["logit_err"],
             float(cfg["check"]["logit_err_limit"])),
            ("failed", result["failed"], 0)]


def correct(result: dict, cfg: dict) -> bool:
    return result["images"] > 0 and all(
        v <= lim for _, v, lim in checks(result, cfg))
