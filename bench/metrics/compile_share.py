"""JAX compilation inside the engine: the share of the window spent in
JAX's compile events (tracing, lowering to MLIR, backend compile or a hit
in the persistent cache) during the engine's admission and decode calls,
from the engine's own ``compile_s`` counter, in percent.  Moves
serve_tokens_per_s.
"""


def read(rec):
    eng = rec.get("engine") or {}
    if "compile_s" not in eng or not rec.get("window_s"):
        return None
    return 100.0 * eng["compile_s"] / rec["window_s"]
