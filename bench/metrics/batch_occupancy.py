"""Serving engine, batching: the share of the engine's batch slots that
decoded a token, summed over the window's decode steps, from the engine's
own ``slots_busy`` and ``slots_idle`` counters, in percent.  Moves
serve_tokens_per_s.
"""


def read(rec):
    eng = rec.get("engine") or {}
    slots = eng.get("slots_busy", 0) + eng.get("slots_idle", 0)
    if "slots_busy" not in eng or not slots:
        return None
    return 100.0 * eng["slots_busy"] / slots
