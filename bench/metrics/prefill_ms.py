"""Serving engine (repro.serving.engine): host wall time of admission per
prefill over the window, from the engine's own ``prefill_s`` and
``prefills`` counters (each admission round ends in the host's read of
the first tokens).  Moves ttft_p95_ms.
"""


def read(rec):
    eng = rec.get("engine") or {}
    if not eng.get("prefills"):
        return None
    return eng["prefill_s"] / eng["prefills"] * 1e3
