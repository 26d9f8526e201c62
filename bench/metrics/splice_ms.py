"""Serving engine (repro.serving.engine): host wall time of placing one
prefilled request's state into its slot of the batch cache (the span
``engine.splice``), from the engine's own ``splice_s`` and ``prefills``
counters over the window.  Moves ttft_p95_ms.
"""


def read(rec):
    eng = rec.get("engine") or {}
    if "splice_s" not in eng or not eng.get("prefills"):
        return None
    return eng["splice_s"] / eng["prefills"] * 1e3
