"""Serving engine, decode step on the host: host wall time per decode
step outside the wait for the device, that is enqueueing the step
(``engine.decode.dispatch``) and reading and booking its tokens after
the first slot's, whose read is the wait (``engine.decode.sample``),
from the engine's own counters over the window.  Moves itl_p95_ms.
"""


def read(rec):
    eng = rec.get("engine") or {}
    if ("decode_dispatch_s" not in eng or "decode_sample_s" not in eng
            or not eng.get("decode_steps")):
        return None
    return ((eng["decode_dispatch_s"] + eng["decode_sample_s"])
            / eng["decode_steps"] * 1e3)
