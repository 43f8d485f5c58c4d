"""Serving launcher: batched request serving of a registry architecture.

``python -m repro.launch.serve --arch olmo-1b --requests 8``

Serves the published config with random weights from a fixed seed;
``--smoke`` serves the reduced config instead (CPU rehearsal).
"""
import argparse
from typing import List


def make_requests(cfg, n: int, prompt_len: int, max_new: int) -> List:
    """``n`` random prompts; odd requests sample at temperature 0.7, even
    ones are greedy."""
    import numpy as np

    from ..serving import Request

    rng = np.random.default_rng(0)
    return [
        Request(
            prompt=rng.integers(0, cfg.vocab_size, size=prompt_len).astype(np.int32),
            max_new_tokens=max_new,
            temperature=0.7 if i % 2 else 0.0,
        )
        for i in range(n)
    ]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--smoke", action="store_true", help="serve the reduced config")
    args = ap.parse_args()

    import jax

    from ..configs import get_arch
    from ..models import init_params, model_pspecs
    from ..serving import ServingEngine
    from .compile_cache import use_compile_cache

    use_compile_cache()
    cfg = get_arch(args.arch).config
    if args.smoke:
        cfg = cfg.reduced()
    params = init_params(jax.random.PRNGKey(0), model_pspecs(cfg))
    engine = ServingEngine(cfg, params, batch_size=args.batch,
                           max_seq=args.prompt_len + args.max_new)
    engine.serve(make_requests(cfg, args.requests, args.prompt_len, args.max_new))
    s = engine.stats
    print(
        f"served {s.requests} requests in {s.waves} waves on "
        f"{jax.devices()[0].platform}: "
        f"{s.prompt_tokens} prompt tokens (+{s.pad_tokens} padding) prefilled; "
        f"{s.decode_steps} decode steps delivered {s.decode_rows_live} tokens"
    )


if __name__ == "__main__":
    main()
