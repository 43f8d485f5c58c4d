"""Batched serving example: prefill + lockstep decode waves with greedy and
temperature sampling, EOS handling, and the engine's counters.

    PYTHONPATH=src python examples/serve_batch.py --arch qwen3-8b
"""
import argparse
import time

import jax
import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--max-new", type=int, default=16)
    args = ap.parse_args()

    from repro.configs import get_arch
    from repro.models import init_params, model_pspecs
    from repro.serving import Request, ServingEngine

    cfg = get_arch(args.arch).config.reduced()
    print(f"serving reduced {args.arch}: {cfg.n_params/1e6:.1f}M params "
          f"(same block structure as the full model)")
    params = init_params(jax.random.PRNGKey(0), model_pspecs(cfg))
    engine = ServingEngine(cfg, params, batch_size=args.batch,
                           max_seq=args.prompt_len + args.max_new)

    rng = np.random.default_rng(0)
    reqs = [
        Request(
            prompt=rng.integers(0, cfg.vocab_size, size=args.prompt_len).astype(np.int32),
            max_new_tokens=args.max_new,
            temperature=0.0 if i % 2 == 0 else 0.8,
        )
        for i in range(args.requests)
    ]
    t0 = time.perf_counter()
    engine.serve(reqs)
    for i, r in enumerate(reqs[:4]):
        print(f"req{i} (T={r.temperature}): {r.output[:10].tolist()}...")
    s = engine.stats
    print(
        f"\n{s.requests} requests in {s.waves} waves | "
        f"prefill {s.prompt_tokens} tok (+{s.pad_tokens} padding) | "
        f"{s.decode_steps} decode steps delivered {s.decode_rows_live} tok | "
        f"{sum(len(r.output) for r in reqs) / (time.perf_counter() - t0):,.0f} output tok/s "
        f"(compiles included)"
    )


if __name__ == "__main__":
    main()
