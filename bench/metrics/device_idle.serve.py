"""Device, serving cell: the share of the traced window in which no
operation ran on the device, 100 * (1 - busy / window), from the profiler
trace (bench/trace.py).  Moves serve_tokens_per_s.
"""


def read(rec):
    tr = rec.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
