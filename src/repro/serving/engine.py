"""Batched serving engine: request queue -> prefill -> lockstep batched
decode with greedy/temperature sampling, EOS + max-length termination.

The engine serves fixed-size batch waves (static batching): requests are
grouped into waves of ``batch_size``, each wave shares one KV cache and
decodes in lockstep — the pattern the decode_32k dry-run cells lower.

The engine annotates its own work for the profiler (``TraceAnnotation``,
which costs a flag check while no profiler runs): ``engine.wave`` around a
wave, and inside it ``engine.prefill`` and, per decode step,
``engine.dispatch``, ``engine.token_sync`` and ``engine.bookkeeping``; each
carries ``wave=<n>``, the per-step ones ``step=<k>`` too.  The decode
program's sampling runs under the named scope ``sample``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..models.config import ModelConfig
from ..models.transformer import decode_step, init_cache, prefill


@dataclasses.dataclass
class Request:
    prompt: np.ndarray                 # (S,) int32
    max_new_tokens: int = 32
    temperature: float = 0.0           # 0 = greedy
    eos_id: Optional[int] = None
    # filled by the engine, times by time.perf_counter() as each token
    # reaches the host:
    output: Optional[np.ndarray] = None
    first_token_s: Optional[float] = None
    done_s: Optional[float] = None


@dataclasses.dataclass
class EngineStats:
    waves: int = 0
    requests: int = 0
    prompt_tokens: int = 0       # requests' own prompt tokens prefilled
    pad_tokens: int = 0          # prefill slots holding padding or no request
    decode_steps: int = 0        # executions of the decode program
    decode_rows_live: int = 0    # decode rows that delivered a token a request asked for


class ServingEngine:
    def __init__(
        self,
        cfg: ModelConfig,
        params: Any,
        batch_size: int = 4,
        max_seq: int = 512,
        rng_seed: int = 0,
    ):
        self.cfg = cfg
        self.params = params
        self.batch_size = batch_size
        self.max_seq = max_seq
        self._key = jax.random.PRNGKey(rng_seed)
        self.stats = EngineStats()

        self._prefill = jax.jit(
            lambda p, toks: prefill(cfg, p, tokens=toks, max_seq=max_seq)
        )

        def _decode(p, toks, cache, pos, key, temps):
            logits, cache = decode_step(cfg, p, toks, cache, pos)
            logits = logits[:, 0, :]
            with jax.named_scope("sample"):
                greedy = jnp.argmax(logits, axis=-1)
                sampled = jax.random.categorical(key, logits / jnp.maximum(temps, 1e-6)[:, None])
                nxt = jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)
            return nxt[:, None], cache

        self._decode = jax.jit(_decode, donate_argnums=(2,))

    # -- wave execution -------------------------------------------------------

    def _run_wave(self, wave: List[Request]) -> None:
        n = self.stats.waves
        with TraceAnnotation("engine.wave", wave=n):
            self._wave(wave, n)
        self.stats.waves += 1
        self.stats.requests += len(wave)

    def _wave(self, wave: List[Request], n: int) -> None:
        B = self.batch_size
        with TraceAnnotation("engine.prefill", wave=n):
            S = max(len(r.prompt) for r in wave)
            prompts = np.zeros((B, S), np.int32)
            for i, r in enumerate(wave):
                prompts[i, S - len(r.prompt):] = r.prompt  # left-pad into lockstep
            logits, cache = self._prefill(self.params, jnp.asarray(prompts))
            temps = jnp.asarray(
                [r.temperature for r in wave] + [0.0] * (B - len(wave)), jnp.float32
            )
            toks = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
            first = np.asarray(toks)
            now = time.perf_counter()
            outputs = [[] for _ in range(B)]
            done = np.zeros(B, bool)
            for i, r in enumerate(wave):
                outputs[i].append(int(first[i, 0]))
                r.first_token_s = now
                if (r.eos_id is not None and first[i, 0] == r.eos_id) or r.max_new_tokens <= 1:
                    done[i] = True
                    r.done_s = now
        prompt_tokens = sum(len(r.prompt) for r in wave)
        self.stats.prompt_tokens += prompt_tokens
        self.stats.pad_tokens += B * S - prompt_tokens

        max_new = max(r.max_new_tokens for r in wave)
        for step in range(1, max_new):
            if all(done[: len(wave)]):
                break
            with TraceAnnotation("engine.dispatch", wave=n, step=step):
                self._key, sub = jax.random.split(self._key)
                toks, cache = self._decode(
                    self.params, toks, cache, jnp.int32(S + step - 1), sub, temps
                )
            with TraceAnnotation("engine.token_sync", wave=n, step=step):
                host = np.asarray(toks)[:, 0]
            now = time.perf_counter()
            self.stats.decode_steps += 1
            with TraceAnnotation("engine.bookkeeping", wave=n, step=step):
                for i, r in enumerate(wave):
                    if done[i]:
                        continue
                    outputs[i].append(int(host[i]))
                    self.stats.decode_rows_live += 1
                    if (r.eos_id is not None and host[i] == r.eos_id) or len(outputs[i]) >= r.max_new_tokens:
                        done[i] = True
                        r.done_s = now

        for i, r in enumerate(wave):
            r.output = np.asarray(outputs[i][: r.max_new_tokens], np.int32)

    def serve(self, requests: List[Request]) -> List[Request]:
        for i in range(0, len(requests), self.batch_size):
            self._run_wave(requests[i : i + self.batch_size])
        return requests
