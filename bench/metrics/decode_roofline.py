"""Models / kernels: the decode program's share of its roofline, in
percent.  The least time of a decode step is the larger of its model
operations over peak FLOP/s and its least bytes (every matmul weight
once, plus the active slots' keys and values at their contexts) over
HBM bandwidth (the reference's ``decode_token_flops`` and
``decode_step_bytes``, bench/flops.py, bench/peaks.json); it is averaged
over the window's decode steps and divided by the decode program's
device time per call in the profiler trace, found by its name.  Moves
itl_p95_ms.
"""

import sys


def read(rec):
    from bench.trace import program_seconds

    tr = rec.get("trace")
    least = rec.get("decode_least_s")
    if not tr or not least:
        return None
    found = program_seconds(tr, rec["decode_program"])
    if not found or not found[1]:
        return None
    secs, count = found
    print(f"decode_roofline: bound by {rec['decode_bound']}, "
          f"{count} decode programs in the trace, "
          f"{secs / count * 1e3!r} ms each", file=sys.stderr)
    return 100.0 * (sum(least) / len(least)) / (secs / count)
