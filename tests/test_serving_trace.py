"""The serving engine's own spans and counters, on a tiny engine on the
CPU: the counters' invariants, the compile tally against an independent
listener, and the ``engine.*`` spans in a profiler trace read with the
benchmark's reduction (bench/trace.py)."""

import time

import jax
import pytest

from bench import trace as bt
from repro.configs import get_config
from repro.serving import Request, ServingEngine

MAX_BATCH = 2
SPANS = ("engine.admit", "engine.prefill", "engine.splice", "engine.decode",
         "engine.decode.dispatch", "engine.decode.wait",
         "engine.decode.sample")


def _engine() -> ServingEngine:
    return ServingEngine(get_config("qwen2_0_5b").smoke(), max_batch=MAX_BATCH,
                         max_len=32, prompt_len=6, seed=1)


def _requests(n: int, first: int = 0) -> list[Request]:
    return [Request(rid=first + i, prompt=[1 + i, 2, 3],
                    max_new_tokens=3 + i % 3) for i in range(n)]


def _warm(eng: ServingEngine) -> None:
    for r in _requests(1, first=-1):
        eng.submit(r)
    eng.run()


@pytest.fixture(scope="module")
def served():
    """Five requests behind a warm engine, one iteration at a time: the
    counters before and after, the requests, and the counters just before
    the iteration that admitted the last request (queued behind two full
    rounds)."""
    eng = _engine()
    _warm(eng)
    s0 = eng.run(max_steps=0)
    reqs = _requests(5)
    for r in reqs:
        eng.submit(r)
    before_last = None
    while eng.queue or any(s is not None for s in eng.slots):
        prev = eng.run(max_steps=0)
        eng.run(max_steps=1)
        if before_last is None and reqs[-1].out_tokens:
            before_last = prev
    return s0, eng.run(max_steps=0), reqs, before_last


def test_counters_are_numeric(served):
    _, s1, _, _ = served
    assert all(isinstance(v, (int, float)) for v in s1.values())


def test_splice_time_lies_within_admission(served):
    s0, s1, _, _ = served
    d = {k: s1[k] - s0[k] for k in s1}
    assert 0 < d["splice_s"] < d["prefill_s"]


def test_slots_add_up_to_steps_times_batch(served):
    s0, s1, reqs, _ = served
    d = {k: s1[k] - s0[k] for k in s1}
    assert d["completed"] == len(reqs)
    assert d["slots_busy"] + d["slots_idle"] == d["decode_steps"] * MAX_BATCH
    # every token after a request's first comes from one busy slot-step
    assert d["slots_busy"] == sum(len(r.out_tokens) - 1 for r in reqs)
    assert 0 < d["slots_idle"]


def test_decode_parts_lie_within_decode_time(served):
    s0, s1, _, _ = served
    d = {k: s1[k] - s0[k] for k in s1}
    parts = d["decode_dispatch_s"] + d["decode_wait_s"] + d["decode_sample_s"]
    assert parts <= d["decode_s"]
    assert parts >= 0.8 * d["decode_s"]


def test_queue_wait_covers_the_rounds_ahead(served):
    s0, s1, reqs, before_last = served
    assert before_last is not None
    ahead = (before_last["prefill_s"] - s0["prefill_s"]
             + before_last["decode_s"] - s0["decode_s"])
    # only the last request is admitted in the iteration after before_last
    waited_last = s1["queue_wait_s"] - before_last["queue_wait_s"]
    assert waited_last >= ahead > 0
    assert s1["queue_wait_s"] - s0["queue_wait_s"] >= waited_last


def test_compiles_match_an_independent_listener():
    eng = _engine()
    _warm(eng)
    seen = []

    def listen(event, duration, **_):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            seen.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        s0 = eng.run(max_steps=0)
        for r in _requests(2):
            eng.submit(r)
        eng.run(max_steps=1)            # admission and a first decode step
        s1 = eng.run(max_steps=0)
        n_admit = len(seen)
        eng.run(max_steps=1)            # a decode step alone
        s2 = eng.run(max_steps=0)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert s1["compiles"] - s0["compiles"] == n_admit
    assert s1["compile_s"] - s0["compile_s"] >= sum(seen[:n_admit])
    assert s2["decode_steps"] == s1["decode_steps"] + 1
    assert s2["compiles"] == s1["compiles"] == s0["compiles"] + len(seen)


def test_spans_in_a_profiler_trace(tmp_path):
    eng = _engine()
    _warm(eng)
    for r in _requests(3):
        eng.submit(r)
    jax.profiler.start_trace(str(tmp_path))
    try:
        while eng.queue or any(s is not None for s in eng.slots):
            with jax.profiler.TraceAnnotation("bench.serve.iter"):
                eng.run(max_steps=1)
            time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    host = bt.read(bt.find_xplane(str(tmp_path))).host
    ours = [e for e in host if e.name.startswith("engine.")]
    assert {e.name for e in ours} == set(SPANS)
    assert not any(e.name.startswith(bt.HOST_PREFIX) for e in ours)
    # the engine's spans leave the window where the bench spans put it
    rest = [e for e in host if not e.name.startswith("engine.")]
    assert bt.window_of(host) == bt.window_of(rest)

    def inside(name, outer):
        outs = [e for e in ours if e.name == outer]
        inner = [e for e in ours if e.name == name]
        assert inner
        return all(any(o.start <= e.start and e.end <= o.end for o in outs)
                   for e in inner)

    assert inside("engine.prefill", "engine.admit")
    assert inside("engine.splice", "engine.prefill")
    for part in ("dispatch", "wait", "sample"):
        assert inside(f"engine.decode.{part}", "engine.decode")
    assert sum(e.name == "engine.prefill" for e in ours) == 3
    assert sum(e.name == "engine.splice" for e in ours) == 3
