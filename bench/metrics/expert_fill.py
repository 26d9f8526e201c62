"""Models, expert layer (repro.models.layers): the share of the rows that
the held experts' matmuls compute in the window's decode steps that carry
a token routed to that expert, in percent, from the decode step's own
counters ``expert_routed`` ((token, expert) pairs that landed on a held
expert) and ``expert_rows`` (rows computed), both summed on the device.
Moves itl_p95_ms.
"""


def read(rec):
    eng = rec.get("engine") or {}
    if not eng.get("expert_rows"):
        return None
    return 100.0 * float(eng["expert_routed"]) / float(eng["expert_rows"])
