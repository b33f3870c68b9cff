"""Serving CLI: ``python -m repro.launch.serve --arch <id> [--reduced]``

Runs batched prefill + decode on the config (at its published widths, or
its tiny ``reduced()`` variant) and reports tokens/s.
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from repro.configs import get_config
from repro.launch.cache import enable_compile_cache
from repro.serve.engine import EngineConfig, Request, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    eng = ServeEngine(cfg, EngineConfig(batch_size=args.batch,
                                        max_len=args.prompt_len + args.new_tokens))
    rng = np.random.default_rng(0)
    reqs = [
        Request(prompt=rng.integers(0, cfg.vocab, size=args.prompt_len).astype(np.int32),
                max_new_tokens=args.new_tokens)
        for _ in range(args.batch)
    ]
    t0 = time.perf_counter()
    out = eng.generate(reqs)
    dt = time.perf_counter() - t0
    total = sum(len(r.generated) for r in out)
    print(f"generated {total} tokens in {dt:.2f}s ({total/dt:.1f} tok/s, "
          f"batch={args.batch})")
    print("sample:", out[0].generated[:8])


if __name__ == "__main__":
    main()
