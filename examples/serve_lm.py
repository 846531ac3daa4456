"""Batched serving example: prefill + greedy decode over every cache kind.

Runs reduced configs of four cache families — standard KV (deepseek),
rolling SWA ring (danube), pure SSM state (mamba2), hybrid (jamba) — and
prints tokens/s for batched greedy generation.

  PYTHONPATH=src python examples/serve_lm.py [--max-new 16]
"""

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models.lm import build_model
from repro.serve.serve_step import greedy_generate

ARCHS = ["deepseek-7b", "h2o-danube-1.8b", "mamba2-370m", "jamba-v0.1-52b"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    args = ap.parse_args()

    for name in ARCHS:
        cfg = get_config(name).reduced()
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        toks = jnp.zeros((args.batch, args.prompt_len), jnp.int32)
        batch = {"tokens": toks}
        # warmup (compile)
        greedy_generate(model, cfg, params, batch, max_new=2)
        t0 = time.perf_counter()
        out = greedy_generate(model, cfg, params, batch, max_new=args.max_new)
        dt = time.perf_counter() - t0
        rate = args.batch * args.max_new / dt
        print(f"{name:18s} generated {out.shape} in {dt:5.2f}s "
              f"({rate:7.1f} tok/s, reduced config)")


if __name__ == "__main__":
    from repro.cache import enable_compile_cache
    enable_compile_cache()
    main()
