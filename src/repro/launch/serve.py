"""Serving entry point: continuous-batched generation at the published widths.

Drives the full serving path (per-request prefill -> slot insert ->
shared decode steps) for any ``--arch``; families without a batch serving
path fall back to the legacy lockstep groups inside the engine.  Params
are random, made from seed 0.  ``--smoke`` serves the tiny config of the
same family, for CPU runs.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b
  PYTHONPATH=src JAX_PLATFORMS=cpu python -m repro.launch.serve --smoke
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.models import ARCH_IDS, Model, get_config
from repro.serve import Request, ServingEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="llama3.2-3b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-slots", "--batch-size", dest="max_slots",
                    type=int, default=4)
    ap.add_argument("--smoke", action="store_true",
                    help="serve the tiny config (CPU runs)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    model = Model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    engine = ServingEngine(model, params, max_slots=args.max_slots,
                           max_len=args.prompt_len + args.new_tokens)
    rng = np.random.default_rng(0)
    reqs = [
        Request(req_id=i,
                prompt=rng.integers(0, cfg.vocab, args.prompt_len).astype(np.int32),
                max_new_tokens=args.new_tokens)
        for i in range(args.requests)
    ]
    t0 = time.time()
    engine.generate(reqs)
    dt = time.time() - t0
    total = sum(len(r.out_tokens) for r in reqs)
    print(f"{args.arch}: {len(reqs)} requests, {total} tokens "
          f"in {dt:.2f}s ({total/dt:.1f} tok/s) "
          f"stats={engine.stats}")
    for r in reqs[:3]:
        print(f"  req{r.req_id}: {r.out_tokens[:8]}...")


if __name__ == "__main__":
    main()
