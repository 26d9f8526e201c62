"""Serving engine, admission: the mean wait of an admitted request in the
engine's queue, from its submission to the start of its prefill, over the
window, from the engine's own ``queue_wait_s`` and ``prefills`` counters.
Moves ttft_p95_ms.
"""


def read(rec):
    eng = rec.get("engine") or {}
    if "queue_wait_s" not in eng or not eng.get("prefills"):
        return None
    return eng["queue_wait_s"] / eng["prefills"] * 1e3
