"""A run of the harness with the timed path broken underneath comes out
not correct: once for each fault the serving cell can have.  Tiny sizes
on the CPU, without the harness's look for a chip, under limits set
like the cell's own at these sizes (``tiny.TINY_LIMITS``).

The serving cell can have a token altered where it is produced.  It
runs on one chip, so it has no exchange between chips to leave out, and
it has no training state or batch mean to break."""

import pytest

from bench.tests.tiny import run_tiny, tiny_cell


@pytest.fixture
def serve_cell():
    return tiny_cell("serve.chatglm3-6b.chat")


def test_serving_sound_run_is_correct(serve_cell):
    out = run_tiny(serve_cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0


def test_serving_altered_token_is_not_correct(serve_cell, monkeypatch):
    from repro.serving import engine

    step = engine.ServingEngine._step_decode

    def altered(self):
        active = [r for r in self.slots if r is not None]
        step(self)
        for r in active:       # the token just produced, one id further on
            r.out_tokens[-1] = (r.out_tokens[-1] + 1) % self.cfg.vocab

    monkeypatch.setattr(engine.ServingEngine, "_step_decode", altered)
    out = run_tiny(serve_cell)
    assert not out["correct"], out["checks"]
