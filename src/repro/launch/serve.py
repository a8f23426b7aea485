"""Batched serving driver: continuous-batching-lite prefill/decode loop.

Requests arrive with prompts; the scheduler packs up to `max_batch` active
sequences, prefills new arrivals, and steps decode for the whole batch.
The decode step is compiled once (static cache length); finished sequences
free their slot for waiting requests.

    PYTHONPATH=src python -m repro.launch.serve --arch hymba-1.5b --smoke \
        --requests 8 --max-new 16
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, smoke_config
from repro.launch.compile_cache import use_compile_cache
from repro.models import api as model_api
from repro.models import transformer


@dataclass
class Request:
    rid: int
    prompt: np.ndarray            # [P] int32
    max_new: int
    generated: List[int] = field(default_factory=list)
    done: bool = False


class BatchedServer:
    """Slot-based batched decoder (static shapes, compiled once)."""

    def __init__(self, cfg, params, *, max_batch=8, cache_len=512):
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.cache_len = cache_len
        self.cache = transformer.init_cache(cfg, max_batch, cache_len,
                                            windowed=False)
        self.pos = np.zeros(max_batch, np.int64)
        self.slots: List[Optional[Request]] = [None] * max_batch

        def step(params, cache, tokens, pos_vec):
            # per-slot positions: decode uses the max (cache written per-slot
            # via the shared scalar path; slots are kept position-aligned by
            # the scheduler for this lite implementation)
            return model_api.decode_step(cfg, params, cache, tokens,
                                         pos_vec)
        self._decode = jax.jit(step, donate_argnums=(1,))

    def prefill_into_slot(self, slot: int, req: Request):
        """Run the prompt through decode steps (aligned-batch lite path)."""
        self.slots[slot] = req
        self.pos[slot] = 0
        for t in req.prompt:
            tok = np.zeros((self.max_batch, 1), np.int32)
            tok[slot, 0] = t
            logits, self.cache = self._decode(
                self.params, self.cache, jnp.asarray(tok),
                jnp.int32(int(self.pos[slot])))
            self.pos[slot] += 1
        req._last_logits = np.asarray(logits[slot, 0])

    def decode_round(self) -> None:
        active = [i for i, r in enumerate(self.slots) if r and not r.done]
        if not active:
            return
        tok = np.zeros((self.max_batch, 1), np.int32)
        for i in active:
            r = self.slots[i]
            last = r.generated[-1] if r.generated else int(
                np.argmax(r._last_logits))
            tok[i, 0] = last
        pos = int(max(self.pos[i] for i in active))
        logits, self.cache = self._decode(self.params, self.cache,
                                          jnp.asarray(tok), jnp.int32(pos))
        lg = np.asarray(logits[:, 0])
        for i in active:
            r = self.slots[i]
            nxt = int(np.argmax(lg[i]))
            r.generated.append(nxt)
            self.pos[i] += 1
            if len(r.generated) >= r.max_new or self.pos[i] >= self.cache_len - 1:
                r.done = True
                self.slots[i] = None if r.done else r


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    if cfg.family == "encdec":
        raise SystemExit("serve driver targets decoder-only families")
    params = model_api.init_params(cfg, 0)
    server = BatchedServer(cfg, params, max_batch=args.max_batch,
                           cache_len=max(64, args.prompt_len + args.max_new + 2))

    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, args.prompt_len,
                                    dtype=np.int32), args.max_new)
            for i in range(args.requests)]
    t0 = time.perf_counter()
    queue = list(reqs)
    done: List[Request] = []
    while queue or any(server.slots):
        for slot in range(server.max_batch):
            if server.slots[slot] is None and queue:
                server.prefill_into_slot(slot, queue.pop(0))
        server.decode_round()
    dt = time.perf_counter() - t0
    total_tokens = sum(len(r.generated) for r in reqs)
    print(f"[serve] {len(reqs)} requests, {total_tokens} tokens "
          f"in {dt:.2f}s ({total_tokens/dt:.1f} tok/s)")
    for r in reqs[:2]:
        print(f"  req {r.rid}: {list(r.prompt[:4])}... -> {r.generated[:8]}")


if __name__ == "__main__":
    main()
