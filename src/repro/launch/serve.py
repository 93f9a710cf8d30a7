"""Token serving launcher: a stream of token requests through the
``BatchEngine`` (``serve/engine.py``).

    python -m repro.launch.serve --arch qwen3-4b --requests 8

Images are served over HTTP by ``launch/server.py``.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs.registry import get_config
from repro.models import api
from repro.serve.engine import BatchEngine, Request


def token_main(args) -> None:
    cfg = get_config(args.arch, reduced=not args.full)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    engine = BatchEngine(cfg, params, batch=args.batch,
                         max_len=args.max_len)
    rng = np.random.default_rng(0)
    t0 = time.monotonic()
    reqs = []
    for i in range(args.requests):
        r = Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, args.prompt_len,
                                        dtype=np.int32),
                    max_new_tokens=args.new_tokens)
        reqs.append(r)
        engine.submit(r)
    engine.run()
    dt = time.monotonic() - t0
    done = sum(r.done for r in reqs)
    toks = sum(len(r.output) for r in reqs)
    print(f"served {done}/{len(reqs)} requests, {toks} tokens "
          f"in {dt:.2f}s ({toks/dt:.1f} tok/s)")
    for r in reqs[:3]:
        print(f"  req {r.rid}: {list(r.prompt)} -> {r.output}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=12)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    token_main(args)


if __name__ == "__main__":
    main()
