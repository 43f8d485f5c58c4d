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

The decode program carries the sampling key and the position itself, so a
step needs nothing from the host but the previous step's tokens, which it
reads on the device.  The engine therefore dispatches step k+1 before it
fetches step k's tokens whenever some live row is certain to need step
k+1 (a row with no ``eos_id`` ends at a length known beforehand), and the
device runs that step while the host reads and books step k.  Where every
live row could end on the token not yet fetched, it fetches first: no step
runs that a fetch-first loop would not run.
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
    decode_ahead: int = 0        # decode executions dispatched before the tokens they follow reached the host


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
                key, sub = jax.random.split(key)
                greedy = jnp.argmax(logits, axis=-1)
                sampled = jax.random.categorical(sub, logits / jnp.maximum(temps, 1e-6)[:, None])
                nxt = jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)
                pos = pos + 1
            return nxt[:, None], cache, pos, key

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
        # the last step some live row is certain to need: a row with no
        # eos_id ends at its length, whatever it samples
        certain = max((r.max_new_tokens - 1 for r in wave if r.eos_id is None), default=0)
        outputs = [[] for _ in range(B)]
        done = np.zeros(B, bool)

        def dispatch(step, toks, cache, pos):
            with TraceAnnotation("engine.dispatch", wave=n, step=step):
                toks, cache, pos, self._key = self._decode(
                    self.params, toks, cache, pos, self._key, temps
                )
            return toks, cache, pos

        def dispatch_ahead(step, toks, cache, pos):
            """Step ``step``, dispatched before the tokens it follows reach
            the host, if some live row is certain to need it; else None."""
            if step > certain:
                return None
            self.stats.decode_ahead += 1
            return dispatch(step, toks, cache, pos)

        def book(host, now) -> int:
            """Append each live row's token; returns how many rows were live."""
            live = 0
            for i, r in enumerate(wave):
                if done[i]:
                    continue
                live += 1
                outputs[i].append(int(host[i]))
                if (r.eos_id is not None and host[i] == r.eos_id) or len(outputs[i]) >= r.max_new_tokens:
                    done[i] = True
                    r.done_s = now
            return live

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
            pos = jnp.int32(S)
            ahead = dispatch_ahead(1, toks, cache, pos)
            first = np.asarray(toks)[:, 0]
            now = time.perf_counter()
            for r in wave:
                r.first_token_s = now
            book(first, now)
        prompt_tokens = sum(len(r.prompt) for r in wave)
        self.stats.prompt_tokens += prompt_tokens
        self.stats.pad_tokens += B * S - prompt_tokens

        max_new = max(r.max_new_tokens for r in wave)
        for step in range(1, max_new):
            if all(done[: len(wave)]):
                break
            toks, cache, pos = ahead or dispatch(step, toks, cache, pos)
            ahead = dispatch_ahead(step + 1, toks, cache, pos)
            with TraceAnnotation("engine.token_sync", wave=n, step=step):
                host = np.asarray(toks)[:, 0]
            now = time.perf_counter()
            self.stats.decode_steps += 1
            with TraceAnnotation("engine.bookkeeping", wave=n, step=step):
                self.stats.decode_rows_live += book(host, now)

        for i, r in enumerate(wave):
            r.output = np.asarray(outputs[i][: r.max_new_tokens], np.int32)

    def serve(self, requests: List[Request]) -> List[Request]:
        for i in range(0, len(requests), self.batch_size):
            self._run_wave(requests[i : i + self.batch_size])
        return requests
