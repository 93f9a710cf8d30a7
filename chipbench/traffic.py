"""The one traffic generator: reads a mix (``traffic/<name>.json``) and
makes, from ``--seed``, the request bodies and their schedule.

Keys of a mix:

* ``loop``: ``"closed"`` (``clients`` callers, each sending its next
  request when the last is answered) or ``"open"`` (arrivals at
  ``rate_rps``, whatever the server does);
* ``sizes``: images per request, ``{"min", "max", "power"}`` with
  P(n) proportional to n**-power on min..max;
* ``bodies_per_size``: distinct request bodies made per size.  Requests
  reuse them, so bodies are encoded once, before the window, and the
  reference runs once per distinct image;
* ``buckets``: the server's batch widths for this cell.

The work does not depend on the seed, only its order: an open loop of
``rate_rps * seconds`` requests always has the same sizes and the same
inter-arrival gaps (exponential quantiles at (i + 0.5) / n, scaled to
the window), which the seed permutes; the seed also draws the images.
"""
from __future__ import annotations

import base64
import json
import math
from typing import Dict, List, Tuple

import numpy as np

Key = Tuple[int, int]       # (images in the request, body index)


def _rng(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), *salt])


def size_probs(mix: dict) -> Dict[int, float]:
    s = mix["sizes"]
    ns = range(int(s["min"]), int(s["max"]) + 1)
    w = {n: n ** -float(s.get("power", 0.0)) for n in ns}
    total = sum(w.values())
    return {n: v / total for n, v in w.items()}


def pool_keys(mix: dict) -> List[Key]:
    k = int(mix["bodies_per_size"])
    return [(n, i) for n in size_probs(mix) for i in range(k)]


def pool_images(seed: int, key: Key, cfg: dict) -> np.ndarray:
    """The images of one body: standard normal, NCHW float32."""
    n, i = key
    shape = (n, int(cfg["channels"]), int(cfg["img"]), int(cfg["img"]))
    return _rng(seed, 1, n, i).standard_normal(shape, np.float32)


def body(images: np.ndarray) -> bytes:
    """The ``POST /v1/infer`` JSON body: base64 of the raw float32
    buffer."""
    arr = np.ascontiguousarray(images, np.float32)
    return json.dumps({"shape": list(arr.shape), "dtype": "float32",
                       "data_b64": base64.b64encode(arr.tobytes())
                       .decode("ascii")}).encode()


def closed_plan(mix: dict, seed: int) -> List[List[Key]]:
    """Per client, the cycle of bodies it sends: one seeded order of the
    pool, each client starting at its own offset."""
    keys = pool_keys(mix)
    order = [keys[i] for i in _rng(seed, 2).permutation(len(keys))]
    clients = int(mix["clients"])
    return [order[c * len(order) // clients:] + order[:c * len(order)
                                                       // clients]
            for c in range(clients)]


def _counts(probs: Dict[int, float], total: int) -> Dict[int, int]:
    """Largest-remainder rounding of ``total * p`` per size."""
    raw = {n: total * p for n, p in probs.items()}
    counts = {n: int(math.floor(v)) for n, v in raw.items()}
    short = total - sum(counts.values())
    for n in sorted(raw, key=lambda n: (counts[n] - raw[n], n))[:short]:
        counts[n] += 1
    return counts


def open_schedule(mix: dict, seed: int, seconds: float,
                  rate_rps: float = None) -> List[Tuple[float, Key]]:
    """(due time from the window's start, body) for every request of an
    open loop of ``seconds`` at ``rate_rps`` (the mix's rate by
    default)."""
    rate = float(mix["rate_rps"] if rate_rps is None else rate_rps)
    n_req = max(1, int(round(rate * seconds)))
    sizes = [n for n, c in sorted(_counts(size_probs(mix), n_req).items())
             for _ in range(c)]
    rng = _rng(seed, 3)
    sizes = [sizes[i] for i in rng.permutation(n_req)]
    u = (np.arange(n_req) + 0.5) / n_req
    gaps = -np.log1p(-u)
    gaps = gaps[rng.permutation(n_req)] * (seconds / gaps.sum())
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    k = int(mix["bodies_per_size"])
    picks = rng.integers(0, k, n_req)
    return [(float(t), (int(n), int(i)))
            for t, n, i in zip(due, sizes, picks)]
