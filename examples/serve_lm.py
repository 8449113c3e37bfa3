"""Serve a small LM with batched requests (length-bucketed batching).

    PYTHONPATH=src python examples/serve_lm.py
"""
import time

import jax
import numpy as np

from repro import compile_cache
from repro.configs import get_config
from repro.models import Model
from repro.serving.llm_demo import Request, ServeEngine


def main():
    compile_cache.enable()
    cfg = get_config("gemma-2b", smoke=True)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, max_batch=4)
    rng = np.random.default_rng(0)
    t0 = time.time()
    n_req = 8
    for i in range(n_req):
        ln = 12 if i % 2 else 20
        eng.submit(
            Request(
                request_id=i,
                prompt=rng.integers(0, cfg.vocab_size, ln).tolist(),
                max_new_tokens=12,
                temperature=0.8 if i >= 6 else 0.0,
                top_k=20,
            )
        )
    results = eng.run()
    dt = time.time() - t0
    toks = sum(len(v) for v in results.values())
    for rid in sorted(results):
        print(f"req {rid}: {results[rid]}")
    print(f"{toks} tokens for {n_req} requests in {dt:.1f}s ({toks/dt:.1f} tok/s)")


if __name__ == "__main__":
    main()
