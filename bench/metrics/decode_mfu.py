"""Models, decode step: model operations of the tokens decoded in the
window (the reference's ``decode_token_flops``, each at its context)
over the engine's own ``decode_s`` in the window times the chip's peak,
in percent.  Moves itl_p95_ms.
"""


def read(rec):
    eng = rec.get("engine") or {}
    if not eng.get("decode_s") or not rec.get("decode_flops"):
        return None
    return 100.0 * rec["decode_flops"] / (eng["decode_s"]
                                          * rec["peak"]["flops_per_s"])
