"""Readings that set the limits of ``correct``: the program's and the
control's, on several seeds in one process.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds <s>

Each seed runs the cell's timed path for a short window at the cell's
own load and reads the program's numbers on its sample.  Then the
control takes the program's place on the same prompts and tokens: at
each position the token that the reference computed in fp8 (``CONTROL``)
puts first, and that token's gap under the float32 reference.
Both sets of numbers go through the harness's own verdict under the
cell's limits (``bench/limits/<cell>.json``): a sound control comes out
``"correct": false``.

One JSON line per seed on standard output.  The benchmark's own runs
never run this.
"""

import json
import sys
import time
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[1]
if sys.path and Path(sys.path[0]).resolve() == _ROOT / "bench":
    sys.path.pop(0)
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

#: the precision below the configuration's bfloat16
CONTROL = "fp8"


def readings(cell, seed: int, seconds: float, prec: str, peak: dict) -> dict:
    """The program's and the control's numbers on one seed, each set
    with the verdict ``harness.judge`` gives it under the cell's limits."""
    from bench import harness

    entry = harness.entry_module(cell)
    out = entry.run(cell, seed=seed, seconds=seconds, trace=False,
                    t_process=time.perf_counter(), peak=peak)
    control = [{"name": "logit_gap", "limit": cell.limits.get("logit_gap"),
                "value": entry.check_served(cell, seed, out["sample"],
                                            prec=prec, pick="control")}]
    return {
        "seed": seed,
        "served_tokens_compared": sum(len(o) for _, o in out["sample"]),
        "program": {c["name"]: c["value"] for c in out["checks"]},
        "program_correct": harness.judge(out["checks"]),
        "control": {c["name"]: c["value"] for c in control},
        "control_correct": harness.judge(control),
    }


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)

    from bench import harness
    from bench.run import device_info
    from repro.launch.compile_cache import enable_compile_cache

    cell = harness.load_cell(args.workload)
    device = device_info(cell.chips)
    enable_compile_cache()
    peak = harness.peaks(device["kind"])
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(cell, seed, args.seconds, CONTROL, peak)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
