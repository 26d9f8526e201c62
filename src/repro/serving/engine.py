"""Batched serving engine: prefill + continuous-batching decode.

Fixed-slot continuous batching: ``max_batch`` decode slots; finished
streams free their slot, the queue refills it, and the next prefill is
inserted into the shared cache at that slot.  Greedy sampling for
determinism.  This is the serving-side end-to-end driver (deliverable
(b)); on real hardware the same engine runs under pjit with the decode
cache sharded per models/sharding.cache_specs.

Observability.  Host spans (``jax.profiler.TraceAnnotation``, named
``engine.*``) land in a profiler trace on the device ops' clock and cost
about a microsecond each when no profiler runs: ``engine.admit`` (one
admission round that admits, ``n`` admitted), ``engine.prefill`` (one
request's prefill, its splice into the batch cache and its first-token
read, ``rid``), inside it ``engine.splice`` (placing the request's
state into its slot, ``rid``), ``engine.decode`` (one decode step,
``slots`` active) and its three parts ``engine.decode.dispatch``,
``engine.decode.wait`` and ``engine.decode.sample``.  ``run()`` returns
the engine's cumulative counters (``_stats``), and the model's own
counters that its decode step keeps in the cache, summed on the device
and returned as device scalars, so that no step waits to read them.

The cache is donated to the decode program, which writes each step's
K/V position and recurrent state into the buffers it was given.  A step
therefore deletes the cache it was passed; the model's counters go in
as a separate, undonated argument, so that the scalars ``run()`` returned
stay readable after later steps.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.models.config import ModelConfig
from repro.models.transformer import LM


@dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new_tokens: int = 8
    out_tokens: list[int] = field(default_factory=list)
    done: bool = False
    #: ``time.perf_counter()`` at ``ServingEngine.submit``
    submitted_at: float = 0.0


#: the compile events JAX reports (their names in jax/_src/dispatch.py);
#: one jaxpr-to-MLIR event is one lowering
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_LOWERING_EVENT = _COMPILE_EVENTS[1]


class _CompileTally:
    """Lowerings and seconds of compile events in this process, from every
    thread; engines take the change across their own calls.  A trace
    nested in another (a jitted call traced inside a scan) counts inside
    both: on the eager prefill that is under 1% of the seconds."""

    def __init__(self):
        self._lock = threading.Lock()
        self._lowerings = 0
        self._seconds = 0.0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            with self._lock:
                self._seconds += duration
                self._lowerings += event == _LOWERING_EVENT

    def read(self) -> tuple[int, float]:
        with self._lock:
            return self._lowerings, self._seconds


_COMPILE_TALLY = _CompileTally()
jax.monitoring.register_event_duration_secs_listener(_COMPILE_TALLY)


def _splice_leaves(batch: dict, single: dict, slot, axes: dict) -> dict:
    """Each leaf of a single-stream cache placed at ``slot`` of the batch
    cache's leaf of that name, along the leaf's batch axis."""
    return {k: jax.lax.dynamic_update_slice_in_dim(
                batch[k], single[k].astype(batch[k].dtype), slot, axes[k])
            for k in batch}


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
        # one program for every prompt (all are padded to prompt_len):
        # run eagerly, each scan of the prefill would be lowered again
        # for every request
        self._prefill = jax.jit(self.lm.prefill, static_argnames=("max_len",))
        axes = self.lm.cache_batch_axes()
        # the leaves that hold per-stream state, and the scalars that the
        # decode step sums over steps (all but the length); the batch
        # leaves are spliced in place
        self._per_stream = {k: a for k, a in axes.items() if a is not None}
        self._counters = [k for k, a in axes.items() if a is None and k != "len"]
        lm = self.lm

        def decode_step(params, cache, counters, tokens):
            # the model's counters apart from the rest of the cache, so
            # that the rest alone is donated; returns the whole new cache
            return lm.decode_step(params, {**cache, **counters}, tokens)
        self._decode_jit = jax.jit(decode_step, donate_argnums=1)
        self._splice_jit = jax.jit(partial(_splice_leaves, axes=self._per_stream),
                                   donate_argnums=0)
        # cumulative, all numeric.  prefill_s / decode_s: host wall
        # seconds in admission rounds and decode steps; both end in the
        # host's read of the next tokens, so they include the device work.
        # queue_wait_s: admission minus submission, summed over admitted
        # requests.  slots_busy / slots_idle: active and free slots summed
        # over decode steps.  decode_{dispatch,wait,sample}_s: the parts
        # of decode_s in the spans of those names.  compiles / compile_s:
        # lowerings and compile-event seconds inside admission and decode.
        # splice_s: host seconds placing prefilled state into slots
        self._stats = {"prefills": 0, "decode_steps": 0, "completed": 0,
                       "prefill_s": 0.0, "decode_s": 0.0,
                       "queue_wait_s": 0.0, "slots_busy": 0, "slots_idle": 0,
                       "decode_dispatch_s": 0.0, "decode_wait_s": 0.0,
                       "decode_sample_s": 0.0, "compiles": 0,
                       "compile_s": 0.0, "splice_s": 0.0}

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
        req.submitted_at = time.perf_counter()
        self.queue.append(req)

    def _admit(self) -> None:
        """Admit a new batch round when all slots are free (rolling
        batches: every active slot shares one decode position, so the
        scalar cache length stays exact)."""
        if any(s is not None for s in self.slots) or not self.queue:
            return
        n = min(len(self.queue), self.max_batch)
        with TraceAnnotation("engine.admit", n=n):
            # a fresh cache for the round; the step counters run on
            self.cache = {**self.lm.init_cache(self.max_batch, self.max_len),
                          **{k: self.cache[k] for k in self._counters}}
            self.slot_len[:] = 0
            rng = np.random.default_rng(0)
            for slot in range(n):
                req = self.queue.popleft()
                self._stats["queue_wait_s"] += (time.perf_counter()
                                                - req.submitted_at)
                toks = jnp.asarray([req.prompt], jnp.int32)
                batch = {"tokens": toks, **self._aux_batch(1, rng)}
                with TraceAnnotation("engine.prefill", rid=req.rid):
                    cache1, logits = self._prefill(self.params, batch,
                                                   max_len=self.max_len)
                    # splice the single-stream cache into the batch cache;
                    # enqueued before the read, so that the read waits for
                    # it and no splice is in flight when decode is enqueued
                    with TraceAnnotation("engine.splice", rid=req.rid):
                        t0 = time.perf_counter()
                        self._splice(cache1, slot)
                        self._stats["splice_s"] += time.perf_counter() - t0
                    # logits span the padded vocab; its padding rows are
                    # no tokens
                    nxt = int(jnp.argmax(logits[0, :self.cfg.vocab]))
                self._stats["prefills"] += 1
                self.slot_len[slot] = len(req.prompt)
                req.out_tokens.append(nxt)
                self.slots[slot] = req

    def _split(self, cache: dict) -> tuple[dict, dict]:
        counters = {k: cache[k] for k in self._counters}
        return {k: v for k, v in cache.items() if k not in counters}, counters

    def _decode(self, params, cache: dict, tokens) -> tuple[dict, jax.Array]:
        """One decode step.  Deletes every leaf of ``cache`` but the
        counters: the new cache is written into their buffers."""
        return self._decode_jit(params, *self._split(cache), tokens)

    def decode_alias_bytes(self) -> int:
        """Bytes of the decode program's outputs that alias its donated
        inputs, from XLA's memory analysis of the program compiled for
        the engine's shapes (compiled on demand).  Zero means the cache
        is copied into a new output on every step."""
        shape = partial(jax.tree.map,
                        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype))
        tokens = jax.ShapeDtypeStruct((self.max_batch,), jnp.int32)
        compiled = self._decode_jit.lower(
            shape(self.params), *map(shape, self._split(self.cache)),
            tokens).compile()
        return int(compiled.memory_analysis().alias_size_in_bytes)

    def _splice(self, cache1: dict, slot: int) -> None:
        """Place a single-stream cache into ``slot``, leaf by leaf by name
        along the batch axis the model declares (``cache_batch_axes``)."""
        names = self._per_stream
        self.cache = {**self.cache, **self._splice_jit(
            {k: self.cache[k] for k in names}, {k: cache1[k] for k in names},
            slot)}

    def _step_decode(self) -> None:
        active = [i for i, r in enumerate(self.slots) if r is not None]
        if not active:
            return
        with TraceAnnotation("engine.decode", slots=len(active)):
            t0 = time.perf_counter()
            with TraceAnnotation("engine.decode.dispatch"):
                tokens = np.zeros(self.max_batch, np.int32)
                for i in active:
                    tokens[i] = self.slots[i].out_tokens[-1]
                # per-slot lengths differ; the shared cache["len"] is
                # scalar, so decode at the max and mask per-slot via stored
                # lengths: we use the max length — correctness holds
                # because each slot's cache beyond its own length is zero-KV
                # and masked by value
                self.cache["len"] = jnp.asarray(
                    int(self.slot_len[active].max()), jnp.int32)
                self.cache, logits = self._decode(self.params, self.cache,
                                                  jnp.asarray(tokens))
            t1 = time.perf_counter()
            # logits span the padded vocab; its padding rows are no tokens
            with TraceAnnotation("engine.decode.wait"):
                # every slot's token in one read, which waits for the step
                # (a separate block before it would add a host sync; a read
                # per slot would cost a device-to-host round trip each)
                nxt_all = np.asarray(jnp.argmax(logits[:, :self.cfg.vocab],
                                                axis=-1))
            t2 = time.perf_counter()
            with TraceAnnotation("engine.decode.sample"):
                for i in active:
                    self.slot_len[i] += 1
                    req = self.slots[i]
                    req.out_tokens.append(int(nxt_all[i]))
                    if (len(req.out_tokens) >= req.max_new_tokens
                            or self.slot_len[i] + 1 >= self.max_len):
                        req.done = True
                        self._stats["completed"] += 1
                        self.slots[i] = None
            t3 = time.perf_counter()
        st = self._stats
        st["decode_steps"] += 1
        st["slots_busy"] += len(active)
        st["slots_idle"] += self.max_batch - len(active)
        st["decode_dispatch_s"] += t1 - t0
        st["decode_wait_s"] += t2 - t1
        st["decode_sample_s"] += t3 - t2

    def run(self, max_steps: int = 1000) -> dict:
        steps = 0
        while (self.queue or any(s is not None for s in self.slots)) \
                and steps < max_steps:
            n0, c0 = _COMPILE_TALLY.read()
            t0 = time.perf_counter()
            self._admit()
            t1 = time.perf_counter()
            self._step_decode()
            t2 = time.perf_counter()
            n1, c1 = _COMPILE_TALLY.read()
            self._stats["prefill_s"] += t1 - t0
            self._stats["decode_s"] += t2 - t1
            self._stats["compiles"] += n1 - n0
            self._stats["compile_s"] += c1 - c0
            steps += 1
        return {**self._stats, **{k: self.cache[k] for k in self._counters}}
