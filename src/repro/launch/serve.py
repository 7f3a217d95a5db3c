"""Serving driver: continuous-batching engine over a reduced model.

    python -m repro.launch.serve --arch qwen3-0.6b --requests 16 \
        --slots 4 --max-new 24
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import ARCH_IDS
from repro.configs.registry import reduced_config
from repro.launch.compile_cache import use_compile_cache
from repro.models.model import Model
from repro.serving import RequestQueue, ServeEngine


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen3-0.6b")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = reduced_config(args.arch)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    engine = ServeEngine(model, params, n_slots=args.slots,
                         max_len=args.max_len,
                         temperature=args.temperature)

    rng = np.random.default_rng(0)
    queue = RequestQueue()
    extras = {}
    if cfg.family == "audio":
        extras["frames"] = jax.random.normal(
            jax.random.key(1), (1, cfg.n_frontend_tokens, cfg.d_model),
            cfg.jdtype)
    if cfg.family == "vlm":
        extras["patches"] = jax.random.normal(
            jax.random.key(1), (1, cfg.n_frontend_tokens, cfg.d_model),
            cfg.jdtype)
    for _ in range(args.requests):
        queue.submit(rng.integers(0, cfg.vocab,
                                  size=int(rng.integers(4, 17))),
                     max_new_tokens=args.max_new)

    t0 = time.perf_counter()
    results = engine.run(queue, extra_inputs=extras)
    dt = time.perf_counter() - t0
    toks = sum(len(r.tokens) for r in results)
    print(f"{cfg.name}: served {len(results)} requests, {toks} tokens "
          f"in {dt:.2f}s ({toks / dt:.1f} tok/s, {args.slots} slots)")
    for r in results[:4]:
        print(f"  req {r.uid}: {r.tokens[:10]}{'...' if len(r.tokens) > 10 else ''}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
