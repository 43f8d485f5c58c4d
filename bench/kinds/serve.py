"""Serving under open-loop arrivals, through ``ServingEngine.serve``.

Set-up makes the weights on the device from the seed (the weight table of
the configuration's architecture module, ``harness.arch``), builds the
engine at the traffic's batch and cache size, and serves one warm-up wave
per prompt bucket so that every program the window runs is compiled.  The
window releases each request at its due time; whenever the engine is free
it takes up to ``batch_size`` requests that are due, oldest first, and
serves them as one wave.  Arrivals stop at ``--seconds``; the run then
drains what is due.  ``serve_tokens_per_s`` is the output tokens delivered
over the time from the window's opening to the last completion.

Correctness: once the window has closed, a sample of the finished requests
drawn from the seed, with the longest greedy one in it, is scored by the
float32 reference of the architecture's module over each request's own
prompt and its served tokens: ``logit_gap`` is the widest amount by which a
served token's reference logit lies below the reference's best at that
position.  Greedy requests are scored at every served token, sampled ones
at their first, which the engine takes greedily from the prefill.
"""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

from bench import traffic_gen, weights
from bench.harness import Outcome, Run, arch
from bench.kinds import common


def run(run: Run) -> Outcome:
    import jax
    from jax.profiler import TraceAnnotation

    from repro.serving import ServingEngine
    from repro.serving.engine import Request

    c, t = run.c, run.t
    cfg = common.model_config(c)
    ref = arch(c)
    table = ref.shapes(c)
    B, max_seq = t["batch_size"], t["max_seq"]

    params = jax.jit(lambda k: weights.make(table, k))(weights.seed_key(run.seed))
    common.check_layout(cfg, params)
    engine = ServingEngine(cfg, params, batch_size=B, max_seq=max_seq, rng_seed=run.seed % 2**32)
    for P in t["prompt_buckets"]:
        engine.serve([Request(prompt=np.ones(P, np.int32), max_new_tokens=2, temperature=0.5)])
    schedule = traffic_gen.serve_schedule(t, run.seed, run.seconds, c["vocab_size"])
    reqs = [Request(prompt=a.prompt, max_new_tokens=a.max_new_tokens, temperature=a.temperature)
            for a in schedule]
    n = len(reqs)
    setup_s = common.now() - run.t_start
    run.note(f"set-up {setup_s:.3f} s, of which tracing and compiling {run.compiles.s:.3f} s "
             f"({run.compiles.n} events); {n} requests due in {run.seconds} s")

    waves: List[Dict] = []
    done_at = [math.nan] * n
    compiles_before = run.compiles.n
    tracing = False
    trace_from = min(t["trace_after_s"], 0.3 * run.seconds)
    trace_to = trace_from + t["trace_seconds"]
    t_open = common.now()
    i = 0
    while i < n:
        now = common.now() - t_open
        if run.trace and not tracing and now >= trace_from and not any(w["traced"] for w in waves):
            jax.profiler.start_trace(run.trace_dir)
            tracing = True
        elif tracing and now >= trace_to:
            jax.profiler.stop_trace()
            tracing = False
        if schedule[i].due_s > now:
            with TraceAnnotation("wait_arrivals"):
                _sleep_until(t_open + schedule[i].due_s)
            continue
        j = i
        while j < n and j - i < B and schedule[j].due_s <= now:
            j += 1
        waiting = j - i
        while waiting < n - i and schedule[i + waiting].due_s <= now:
            waiting += 1
        wave = reqs[i:j]
        start = common.now() - t_open
        with TraceAnnotation("serve_wave"):
            engine.serve(wave)
        end = common.now() - t_open
        for k in range(i, j):
            done_at[k] = end
        waves.append({"start": start, "end": end, "traced": tracing, "waiting": waiting,
                      "prompt": [len(r.prompt) for r in wave], "out": [r.max_new_tokens for r in wave]})
        i = j
    if tracing:
        jax.profiler.stop_trace()
    in_window = run.compiles.n - compiles_before
    run.note(f"compilations inside the window: {in_window}")

    failed = [k for k, r in enumerate(reqs)
              if r.output is None or len(r.output) != r.max_new_tokens
              or not ((r.output >= 0) & (r.output < c["vocab_size"])).all()]
    failed_set = set(failed)
    lat = np.array([done_at[k] - schedule[k].due_s for k in range(n)])
    lat[failed] = np.inf
    last = max(w["end"] for w in waves)
    tokens = sum(r.max_new_tokens for k, r in enumerate(reqs) if k not in failed_set)
    backlog = max([w["waiting"] for w in waves if w["start"] <= run.seconds] or [0])
    run.note(f"queue at wave starts inside the window: largest {backlog} (batch {B}); "
             f"at every wave start: {[w['waiting'] for w in waves]}")
    run.note(f"{n} requests in {len(waves)} waves; latency p50 {np.percentile(lat, 50):.4f} s, "
             f"p95 {np.percentile(lat, 95):.4f} s, max {lat.max():.4f} s; {tokens} output tokens; "
             f"last completion {last:.3f} s after the window opened")
    mem = common.memory_peak(run.devices)
    record = {"waves": [w for w in waves if w["traced"]], "batch_size": B}
    metrics = {"serve_tokens_per_s": tokens / last, "setup_s": setup_s}

    # -- correctness, once the window has closed and the program's state is freed
    done = [k for k in range(n) if k not in failed_set]
    greedy = [k for k in done if reqs[k].temperature == 0]
    sampled = [k for k in done if reqs[k].temperature > 0]
    rng = np.random.default_rng(np.random.SeedSequence([run.seed, 3]))
    m = t["check_requests"]
    pick = []
    if greedy:
        longest = max(greedy, key=lambda k: reqs[k].max_new_tokens)
        rest = [k for k in greedy if k != longest]
        pick = [longest] + list(rng.choice(rest, size=min(len(rest), m - 1), replace=False))
    firsts = list(rng.choice(sampled, size=min(len(sampled), m), replace=False)) if sampled else []
    seqs = [(np.concatenate([reqs[k].prompt, reqs[k].output]), len(reqs[k].prompt)) for k in pick]
    seqs += [(np.concatenate([reqs[k].prompt, reqs[k].output[:1]]), len(reqs[k].prompt)) for k in firsts]
    del engine, params
    if not seqs:
        run.note("no request finished: nothing to compare")
        return Outcome(metrics, n, len(failed), {"logit_gap": [None, run.cell.limits["logit_gap"]]},
                       record, mem, run.trace)
    t_ref = common.now()
    w = jax.jit(lambda k: weights.make(table, k))(weights.seed_key(run.seed))
    gaps = _gaps(ref, c, w, seqs, max_seq)
    checks = {"logit_gap": [max(g.max() for g, _ in gaps), run.cell.limits["logit_gap"]]}
    served = sum(len(g) for g, _ in gaps)
    run.note(f"reference over {len(pick)} greedy and {len(firsts)} sampled requests, "
             f"{served} tokens, {common.now() - t_ref:.3f} s: widest gap {checks['logit_gap'][0]!r}")
    if run.control:
        cgaps = _gaps(ref, c, w, seqs, max_seq, quant="fp8")
        checks["control_logit_gap"] = [max(g.max() for _, g in cgaps), run.cell.limits["logit_gap"]]
    return Outcome(metrics, n, len(failed), checks, record, mem, run.trace)


def _sleep_until(t_abs: float) -> None:
    import time

    d = t_abs - common.now()
    if d > 0:
        time.sleep(d)


def _gaps(ref, c, w, seqs, max_seq, quant=None):
    """Per sequence: (gap of each served token under the reference, gap of
    the token that ``quant``'s logits put first); both against the float32
    reference's best at that position."""
    import jax
    import jax.numpy as jnp

    f32 = jax.jit(lambda w, tok: ref.logits(c, w, tok))
    low = jax.jit(lambda w, tok: ref.logits(c, w, tok, quant=quant)) if quant else None
    out = []
    for seq, S in seqs:
        L = len(seq)
        tok = jnp.asarray(np.pad(seq, (0, max_seq - L)))
        lg = np.asarray(f32(w, tok))[S - 1:L - 1]
        served = seq[S:L]
        best = lg.max(axis=-1)
        g = best - lg[np.arange(len(served)), served]
        if low is not None:
            first = np.asarray(low(w, tok))[S - 1:L - 1].argmax(axis=-1)
            out.append((g, best - lg[np.arange(len(served)), first]))
        else:
            out.append((g, None))
    return out

