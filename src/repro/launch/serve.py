"""Serving driver: continuous batching over the slotted ragged-MoE path.

The production entry point is :class:`repro.serving.Engine` — request-level
admission/eviction over a persistent slot cache, decode through the ragged
dispatch + grouped SwiGLU kernel (see ``repro/serving/engine.py`` for the
scheduler semantics).

:class:`FixedBatchServer` (the former continuous-batching-lite ``Server``) is
kept as the decode-parity reference: it groups requests into fixed-size
batches with one scalar cache position, which is exactly the token-for-token
baseline the engine is tested against (tests/test_serving_engine.py).

    PYTHONPATH=src python -m repro.launch.serve --requests 16 --n-slots 4
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.launch import steps as ST
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as MD
from repro.models.numerics import set_activation_mesh
from repro.serving import Engine, EngineConfig, Request, poisson_trace


@dataclasses.dataclass
class ServeConfig:
    arch: str = "qwen3-moe-30b-a3b"
    reduced: bool = True
    batch_size: int = 4
    prompt_len: int = 32
    max_new_tokens: int = 16
    temperature: float = 0.0
    seed: int = 0


class FixedBatchServer:
    """Fixed-batch reference loop (the seed repo's ``Server``).

    All requests in a batch share one prompt length and one scalar cache
    position; a batch must fully finish before the next one starts. Kept as
    the numerical baseline for the continuous-batching parity tests and for
    the quickstart example — new serving code should use
    :class:`repro.serving.Engine`.
    """

    def __init__(self, sc: ServeConfig, cfg=None, params=None):
        self.sc = sc
        self.cfg = cfg if cfg is not None else (
            configs.get(sc.arch).reduced() if sc.reduced
            else configs.get(sc.arch))
        # single-device, like the engine it is the reference for
        set_activation_mesh(None)
        self.params = params if params is not None else MD.init(
            self.cfg, jax.random.PRNGKey(sc.seed))
        s_max = sc.prompt_len + sc.max_new_tokens
        self._prefill = jax.jit(ST.make_serve_prefill(self.cfg, s_max=s_max))
        self._step = jax.jit(ST.make_serve_step(self.cfg))

    def generate(self, prompts: np.ndarray,
                 extra_batch: Optional[dict] = None) -> np.ndarray:
        """prompts: [B, prompt_len] int32 -> [B, max_new_tokens] int32."""
        sc = self.sc
        batch = {"tokens": jnp.asarray(prompts)}
        if extra_batch:
            batch.update(extra_batch)
        if self.cfg.family == "audio" and "frames" not in batch:
            batch["frames"] = jnp.zeros(
                (prompts.shape[0], self.cfg.n_audio_ctx, self.cfg.d_model),
                self.cfg.param_dtype)
        logits, cache = self._prefill(self.params, batch)
        outs = []
        key = jax.random.PRNGKey(sc.seed)
        for t in range(sc.max_new_tokens):
            if sc.temperature > 0:
                key, sub = jax.random.split(key)
                tok = jax.random.categorical(sub, logits / sc.temperature)
            else:
                tok = jnp.argmax(logits, axis=-1)
            outs.append(np.asarray(tok))
            logits, cache = self._step(self.params, cache,
                                       tok.astype(jnp.int32))
        return np.stack(outs, axis=1)


# Back-compat alias (quickstart / system tests predate the engine).
Server = FixedBatchServer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-moe-30b-a3b")
    ap.add_argument("--n-slots", type=int, default=4)
    ap.add_argument("--s-max", type=int, default=128)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=0.5,
                    help="Poisson arrival rate, requests per decode step")
    args = ap.parse_args()
    enable_compile_cache()

    ec = EngineConfig(arch=args.arch, n_slots=args.n_slots, s_max=args.s_max,
                      prefill_buckets=(args.prompt_len,))
    eng = Engine(ec)
    rng = np.random.default_rng(0)
    arrivals = poisson_trace(args.requests, rate=args.rate, seed=1)
    for i in range(args.requests):
        eng.submit(rng.integers(0, eng.cfg.vocab_size, size=args.prompt_len,
                                dtype=np.int32),
                   max_new_tokens=args.max_new_tokens,
                   arrival_time=float(arrivals[i]))
    t0 = time.perf_counter()
    done = eng.run()
    dt = time.perf_counter() - t0
    total = sum(len(r.out_tokens) for r in done)
    print(f"[serve] {len(done)} requests, {total} tokens in {dt:.2f}s "
          f"({total / dt:.1f} tok/s, {eng.ec.n_slots} slots, "
          f"dispatch={eng.cfg.moe.dispatch if eng.cfg.moe else 'dense-mlp'})")
    for r in done[:4]:
        print(f"  req {r.uid}: arrived@{r.arrival_time:.1f} "
              f"admitted@{r.t_admitted:.0f} done@{r.t_finished:.0f} "
              f"[{r.finish_reason}] first tokens {r.out_tokens[:6]}")


if __name__ == "__main__":
    main()
