"""Record a tiny traced serve for the readers' tests: the olmo-1b
configuration at its rehearsal sizes, two waves through ``ServingEngine``
under the profiler with the harness's default options, each wave in a
``serve_wave`` annotation as ``bench/kinds/serve.py`` has it.

    python3 bench/tests/record_spans_trace.py [--out bench/tests/data]

Writes ``serve_spans_tiny.xplane.pb`` and, beside it,
``serve_spans_tiny.json``: the record of the traced waves as
``bench/kinds/serve.py`` keeps it, the configuration as run (without its
prose notes), and the decode program's scope map taken from its compiled
text (``.lower(...).compile().as_text()`` at the run's shapes).
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

NAME = "serve_spans_tiny"
WAVES = [[(16, 8), (16, 5), (16, 2), (16, 6)], [(16, 4), (16, 7)]]   # (prompt, output) per request
BATCH, MAX_SEQ = 4, 40


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default=os.path.join(HERE, "data"))
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import TraceAnnotation

    from bench import harness, program_trace, weights
    from bench.kinds import common
    from repro.serving import Request, ServingEngine

    with open(os.path.join(ROOT, "bench", "configs", "olmo-1b.json")) as f:
        c = json.load(f)
    c.update(c.pop("rehearsal"))
    c.pop("assumed")
    cfg = common.model_config(c)
    table = harness.arch(c).shapes(c)
    params = jax.jit(lambda k: weights.make(table, k))(weights.seed_key(7))
    engine = ServingEngine(cfg, params, batch_size=BATCH, max_seq=MAX_SEQ, rng_seed=7)
    rng = np.random.default_rng(7)

    def requests(wave):
        return [Request(prompt=rng.integers(0, c["vocab_size"], size=P).astype(np.int32),
                        max_new_tokens=T, temperature=0.7 * (i % 2)) for i, (P, T) in enumerate(wave)]

    for wave in WAVES:                                   # compile every program first
        engine.serve(requests(wave))
    tmp = tempfile.mkdtemp(prefix="record_trace_")
    jax.profiler.start_trace(tmp)
    for wave in WAVES:
        with TraceAnnotation("serve_wave"):
            engine.serve(requests(wave))
    jax.profiler.stop_trace()
    [found] = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)

    _, cache = jax.eval_shape(engine._prefill, params, jax.ShapeDtypeStruct((BATCH, 16), jnp.int32))
    args_ = (params, jax.ShapeDtypeStruct((BATCH, 1), jnp.int32), cache,
             jax.ShapeDtypeStruct((), jnp.int32), jax.random.PRNGKey(0),
             jax.ShapeDtypeStruct((BATCH,), jnp.float32))
    text = engine._decode.lower(*args_).compile().as_text()
    record = {"waves": [{"prompt": [P for P, _ in w], "out": [T for _, T in w]} for w in WAVES],
              "batch_size": BATCH}
    os.makedirs(args.out, exist_ok=True)
    shutil.copy(found, os.path.join(args.out, NAME + ".xplane.pb"))
    with open(os.path.join(args.out, NAME + ".json"), "w") as f:
        json.dump({"device_kind": jax.devices()[0].device_kind, "record": record, "c": c,
                   "decode_scopes": program_trace.scopes_from_text(text)}, f, indent=0, sort_keys=True)
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"wrote {NAME}.xplane.pb ({os.path.getsize(os.path.join(args.out, NAME + '.xplane.pb'))} B) "
          f"and {NAME}.json to {args.out}; engine {engine.stats}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
