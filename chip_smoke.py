"""Smoke run of the model stack on the TPU through its normal entry points.

olmo-1b at its published config (16 layers, d_model 2048, 16 heads, d_ff
8192, vocab 50304), random weights from a fixed seed:

    python chip_smoke.py                # one chip: serve, Pallas prefill, train
    python chip_smoke.py --four-chips   # four chips: sharded training only

CPU rehearsal at tiny sizes (the reduced config); nothing else runs off the chip:

    JAX_PLATFORMS=cpu python chip_smoke.py --tiny
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python chip_smoke.py --tiny --four-chips

Everything runs in this one process, which holds the chip(s) throughout.
Every phase raises on a failed check.  Only when all of them passed does the
last line of standard output name the device as one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
import argparse
import dataclasses
import json
import math
import os
import sys
import time

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

# Logits are compared one token at a time: RMS of the difference over the
# RMS of the reference, over the vocabulary, and the worst token counts.  The
# model computes in bfloat16 (8-bit significand, 2**-8 relative step); two
# paths that round in a different order drift apart by a few such steps per
# layer, about 1e-2 of the logits' scale after 16 layers.  A wrong cache
# slot, position or mask moves a token's logits by their own scale (~1).
LOGIT_TOL = 5e-2
# Losses of the same steps on one device and on the (data, model) mesh: the
# mesh sums partial products in another order (bfloat16 again), so the
# losses (about ln(vocab) ~ 10.8 at init) agree to a few parts in 1e3.
LOSS_TOL = 2e-2


@dataclasses.dataclass(frozen=True)
class Sizes:
    full_layers: int      # the served model and the sharded full-model run
    train_layers: int     # depth cut of the one-chip trainer
    batch: int
    prompt_len: int
    max_new: int


REAL = Sizes(full_layers=16, train_layers=6, batch=8, prompt_len=1024, max_new=32)
TINY = Sizes(full_layers=4, train_layers=2, batch=8, prompt_len=64, max_new=8)


def check_device(args):
    import jax

    devs = jax.devices()
    d = devs[0]
    print(f"device: {d.platform} kind={d.device_kind!r} count={len(devs)} "
          f"jax={jax.__version__}", flush=True)
    if d.platform != "tpu" and not args.tiny:
        sys.exit(f"chip_smoke: JAX found no TPU (platform {d.platform!r}); "
                 f"only --tiny runs off the chip")
    if args.four_chips and len(devs) < 4:
        sys.exit(f"chip_smoke: --four-chips needs 4 devices, JAX found {len(devs)}")
    return devs


class Compiles:
    """Seconds JAX spent tracing, lowering and compiling, from its own
    monitoring events."""

    def __init__(self):
        import jax

        self.s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name.startswith("/jax/core/compile/"):
            self.s += secs

    def since(self, mark):
        return self.s - mark


def mem(dev, key="peak_bytes_in_use"):
    stats = dev.memory_stats()   # None on the CPU
    return "not reported" if stats is None else stats[key]


def logit_error(ref, got):
    """Worst token's RMS(ref - got) / RMS(ref) over the vocabulary (see
    LOGIT_TOL)."""
    import numpy as np

    ref = np.asarray(ref, np.float64)
    got = np.asarray(got, np.float64)
    if ref.shape != got.shape:
        raise AssertionError(f"logit shapes {ref.shape} and {got.shape}")
    if not (np.isfinite(ref).all() and np.isfinite(got).all()):
        raise AssertionError("non-finite logits")
    rms = lambda x: np.sqrt(np.mean(x * x, axis=-1))
    return float((rms(ref - got) / rms(ref)).max())


def check_close(what, ref, got, tag):
    err = logit_error(ref, got)
    print(f"{tag} {what}: worst token rms(diff)/rms = {err:.3e} (tolerance {LOGIT_TOL})",
          flush=True)
    if not err <= LOGIT_TOL:
        raise AssertionError(f"{what}: {err:.3e} > {LOGIT_TOL}")


def phase_serve(cfg, sz, tag, compiles, dev):
    """8 requests through ServingEngine, then decode-through-cache and the
    Pallas prefill checked against forward/reference prefill."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.serve import make_requests
    from repro.models import init_params, model_pspecs
    from repro.models.transformer import decode_step, forward, prefill
    from repro.serving import ServingEngine

    P, T, B = sz.prompt_len, sz.max_new, sz.batch
    S = P + T
    params = init_params(jax.random.PRNGKey(0), model_pspecs(cfg))
    engine = ServingEngine(cfg, params, batch_size=B, max_seq=S)
    reqs = make_requests(cfg, B, P, T)
    mark, t0 = compiles.s, time.perf_counter()
    engine.serve(reqs)
    wall = time.perf_counter() - t0
    for i, r in enumerate(reqs):
        if r.output.shape != (T,):
            raise AssertionError(f"request {i}: {r.output.shape[0]} tokens, wanted {T}")
        if not ((r.output >= 0) & (r.output < cfg.vocab_size)).all():
            raise AssertionError(f"request {i}: id outside the vocabulary")
    print(f"{tag} serve: {len(reqs)} requests (batch {B}, prompt {P}, {T} new, "
          f"{sum(r.temperature == 0 for r in reqs)} greedy) answered; "
          f"wall {wall:.3f} s of which compile {compiles.since(mark):.3f} s; "
          f"peak_bytes_in_use {mem(dev)}", flush=True)

    # prefill then decode through the cache, against forward over the same
    # tokens (the prompts followed by what the engine generated)
    toks = jnp.asarray(np.concatenate(
        [np.stack([r.prompt for r in reqs]), np.stack([r.output for r in reqs])], axis=1))
    mark, t0 = compiles.s, time.perf_counter()
    pre = jax.jit(lambda p, t: (lambda lg, c: (lg[:, -1], c))(
        *prefill(cfg, p, tokens=t, max_seq=S)))
    dec = jax.jit(lambda p, t, c, pos: decode_step(cfg, p, t, c, pos), donate_argnums=2)
    # one attention block over the whole sequence: S = P + T is not a
    # multiple of the default query block
    cfg_f = dataclasses.replace(cfg, attn_block_q=S)
    fwd = jax.jit(lambda p, t: forward(cfg_f, p, tokens=t)[0][:, P - 1:S - 1])
    last, cache = pre(params, toks[:, :P])
    ref_last = np.asarray(last)
    steps = [ref_last]
    for i in range(T - 1):
        lg, cache = dec(params, toks[:, P + i:P + i + 1], cache, jnp.int32(P + i))
        steps.append(np.asarray(lg[:, 0]))
    del cache
    got = np.stack(steps, axis=1)
    want = np.asarray(fwd(params, toks))
    print(f"{tag} cache check: prefill + {T - 1} decode steps vs forward over "
          f"{B}x{S} tokens; compile {compiles.since(mark):.3f} s, "
          f"wall {time.perf_counter() - t0:.3f} s", flush=True)
    check_close("decode-through-cache vs forward logits", want, got, tag)

    # the same prefill with the Pallas flash-attention kernel
    cfg_p = dataclasses.replace(cfg, attention_impl="pallas")
    mark = compiles.s
    compiled = jax.jit(lambda p, t: prefill(cfg_p, p, tokens=t, max_seq=S)[0][:, -1]).lower(
        params, toks[:, :P]).compile()
    kernel = "tpu_custom_call" in compiled.as_text()
    if dev.platform == "tpu" and not kernel:
        raise AssertionError("Pallas prefill compiled without a tpu_custom_call")
    pal_last = np.asarray(compiled(params, toks[:, :P]))
    print(f"{tag} pallas prefill: tpu_custom_call in compiled HLO: {kernel}"
          f"{'' if dev.platform == 'tpu' else ' (interpret mode off the chip)'}; "
          f"compile {compiles.since(mark):.3f} s", flush=True)
    check_close("pallas vs reference prefill last-position logits", ref_last, pal_last, tag)
    print(f"{tag} after serve phases: peak_bytes_in_use {mem(dev)}", flush=True)


def train(cfg, steps, mesh=None):
    """``steps`` Trainer steps at the launcher's learning rate, warmed up
    over one step; returns (losses, step times, state)."""
    from repro.training import AdamWConfig, TrainConfig
    from repro.training.trainer import Trainer, TrainerConfig

    tc = TrainConfig(adamw=AdamWConfig(lr=3e-4, total_steps=steps, warmup_steps=1))
    trainer = Trainer(cfg, tc, TrainerConfig(total_steps=steps, log_every=1),
                      mesh=mesh)
    state = trainer.run()
    losses = [m["loss"] for m in trainer.metrics_log]
    if len(losses) != steps or not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"losses {losses}")
    return losses, trainer.step_times, state


def phase_train(cfg, sz, tag, compiles, dev):
    cfg_t = dataclasses.replace(cfg, n_layers=sz.train_layers)
    mark = compiles.s
    losses, times, _ = train(cfg_t, 4)
    print(f"{tag} train: {cfg_t.n_layers} layers, batch 8x256, 4 steps, losses "
          f"{losses} all finite; step times after the first {times[1:]} s; "
          f"compile {compiles.since(mark):.3f} s; peak_bytes_in_use {mem(dev)}", flush=True)


def phase_four_chips(cfg, sz, tag, compiles, devs):
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 2), ("data", "model"))
    # the whole model first, so each device's peak is this run's alone
    losses, times, state = train(cfg, 2, mesh=mesh)
    print(f"{tag} mesh (data=2, model=2), {cfg.n_layers} layers, 2 steps: losses "
          f"{losses}; step time after the first {times[1]} s", flush=True)
    for d in devs:
        print(f"{tag} device {d.id}: bytes_in_use with the state live "
              f"{mem(d, 'bytes_in_use')}, peak_bytes_in_use {mem(d)}", flush=True)
    del state

    cut = dataclasses.replace(cfg, n_layers=sz.train_layers)
    one, _, _ = train(cut, 3)
    sharded, _, _ = train(cut, 3, mesh=mesh)
    diff = max(abs(a - b) for a, b in zip(one, sharded))
    print(f"{tag} {cut.n_layers} layers, 3 steps: one device {one}; "
          f"mesh {sharded}; max |diff| {diff:.3e} (tolerance {LOSS_TOL})", flush=True)
    if not diff <= LOSS_TOL:
        raise AssertionError(f"one-device and mesh losses differ by {diff:.3e}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded training path, on a 2x2 mesh")
    ap.add_argument("--tiny", action="store_true",
                    help="reduced config at tiny sizes; the only way to run off the chip")
    args = ap.parse_args()

    devs = check_device(args)
    sys.path.insert(0, SRC)
    from repro.configs import get_arch
    from repro.launch.compile_cache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}", flush=True)
    sz = TINY if args.tiny else REAL
    base = get_arch("olmo-1b").config
    cfg = base.reduced(n_layers=sz.full_layers) if args.tiny else base
    dev = devs[0]
    tag = f"[{dev.platform} {dev.device_kind}]"
    compiles = Compiles()
    t0 = time.perf_counter()
    if args.four_chips:
        phase_four_chips(cfg, sz, tag, compiles, devs[:4])
    else:
        phase_serve(cfg, sz, tag, compiles, dev)
        phase_train(cfg, sz, tag, compiles, dev)
    print(f"{tag} all phases passed in {time.perf_counter() - t0:.1f} s "
          f"(compile {compiles.s:.1f} s)", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devs)}}))


if __name__ == "__main__":
    main()
