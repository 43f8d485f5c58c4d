"""Serving of a model whose experts are chosen by top-k routing: the same
run as ``kinds/serve.py`` (the same window, metrics and record, the same
sample of finished requests scored by the float32 reference), judged on
two readings of the scored tokens' logit gaps in place of the widest of
all, ``logit_gap``:

- ``clear_logit_gap``, the widest gap over the tokens whose choice of held
  experts is clear: at every expert layer of the float32 reference, no
  held expert's selection value lies within the limits file's
  ``near_tie`` of the boundary between the chosen and the rest
  (``route_margins`` of the reference module);
- ``mean_logit_gap``, the mean gap over every scored token, near-ties
  included.

Why: where a token's k-th and (k+1)-th experts nearly tie, any precision
below float32 may choose the other one, and where one of the two is held
here the layer's output moves by that expert's whole gated term.  Such a
token's gap is as wide under the bfloat16 program as under a float8
computation, so the widest gap of a run, which is one such token out of
some 900, does not tell the two apart.  Moonlight at one TPU v5e chip's
share, 10 runs: every program gap above 0.1 lies at a margin below
0.007 (the widest of each run below 0.002); the widest gap of all reads
0.49-1.20 for the program and 0.96-1.43 for the float8 control; off
margins below 0.01 (about 60% of the scored tokens are near-ties there,
since the held experts' choice is made afresh at every expert layer) the
program reads 0.022-0.069 and the control 0.49-1.21; the mean reads
0.0050-0.0105 against 0.054-0.091.  The mean over every token bounds how
many near-ties go the other way and by how much.  The run notes how many
scored tokens were near-ties, and the widest gap of all with its margin.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np

from bench.harness import Outcome, Run
from bench.kinds import serve


def run(run: Run) -> Outcome:
    scored = []
    gaps = serve._gaps

    def keep(ref, c, w, seqs, max_seq, quant=None):
        out = gaps(ref, c, w, seqs, max_seq, quant=quant)
        scored.append((ref, c, w, seqs, max_seq, out))
        return out

    # serve.run scores the sample through serve._gaps; its widest-gap check
    # is replaced by the two below and has no limit of its own
    inner = dataclasses.replace(run, cell=dataclasses.replace(run.cell, limits={"logit_gap": None}))
    serve._gaps = keep
    try:
        out = serve.run(inner)
    finally:
        serve._gaps = gaps
    lim = run.cell.limits
    if not scored:
        out.checks = {"clear_logit_gap": [None, lim["clear_logit_gap"]],
                      "mean_logit_gap": [None, lim["mean_logit_gap"]]}
        return out
    ref, c, w, seqs, max_seq, program = scored[0]
    tok = tokens(ref, c, w, seqs, max_seq, program, scored[1][5] if run.control else None)
    near = tok["margin"] < lim["near_tie"]
    widest = int(tok["program"].argmax())
    run.note(f"{tok['program'].size} scored tokens, {int(near.sum())} at a near-tie "
             f"(margin < {lim['near_tie']!r}); widest gap {float(tok['program'][widest])!r} "
             f"at margin {float(tok['margin'][widest])!r}")
    out.checks = {}
    for name, g in [("", tok["program"])] + ([("control_", tok["control"])] if run.control else []):
        clear = g[~near]
        out.checks[f"{name}clear_logit_gap"] = [float(clear.max()) if clear.size else None,
                                                lim["clear_logit_gap"]]
        out.checks[f"{name}mean_logit_gap"] = [float(g.mean()), lim["mean_logit_gap"]]
    return out


def tokens(ref, c, w, seqs, max_seq, program, control=None) -> Dict[str, np.ndarray]:
    """Every scored token's program gap, control gap (where given) and the
    reference's routing margin at the position that predicts it."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda w, tok: ref.route_margins(c, w, tok))
    margin: List[np.ndarray] = []
    for seq, S in seqs:
        L = len(seq)
        margin.append(np.asarray(f(w, jnp.asarray(np.pad(seq, (0, max_seq - L)))))[S - 1:L - 1])
    out = {"program": np.concatenate([g for g, _ in program]), "margin": np.concatenate(margin)}
    if control is not None:
        out["control"] = np.concatenate([g for _, g in control])
    return out
