"""Run one cell of the on-chip benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, in this order: refuse anything but a TPU with enough chips
(a non-zero exit, no result line); turn on JAX's compile cache at its fixed path;
make the cell's inputs and weights from ``--seed``; warm up the cell's
own shapes; measure for ``--seconds``; check the timed path's output
against the plain reference; print one JSON object as the last line of
standard output.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics and ``breakdown`` from a profiler
trace of the window.  See bench/README.md.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parents[1]
# run as a script, the first path entry is bench/ itself: take it out so
# that bench/trace.py cannot shadow the standard library's ``trace``
if sys.path and Path(sys.path[0]).resolve() == _ROOT / "bench":
    sys.path.pop(0)
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_info(chips: int) -> dict:
    """The accelerator as JAX reports it; refuses the CPU."""
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        raise SystemExit(f"bench: JAX found no TPU (platform {platform!r}); "
                         f"no result")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found "
                         f"{len(devs)}; no result")
    return {"platform": platform, "kind": devs[0].device_kind,
            "count": chips}


def run_cell(cell, seed: int, seconds: float, trace: bool, device: dict,
             t_process: float, peak: dict) -> dict:
    """Drive the cell and return its result object (the printed line)."""
    from bench import harness

    entry = harness.entry_module(cell)
    out = entry.run(cell, seed=seed, seconds=seconds, trace=trace,
                    t_process=t_process, peak=peak)
    checks = out["checks"]
    result = {
        "correct": harness.judge(checks),
        "attempted": out["attempted"],
        "failed": out["failed"],
    }
    if trace:
        metrics = harness.read_per_layer(cell, out["rec"])
    else:
        metrics = {}
        for m in cell.end_to_end:
            if m["name"] not in out["e2e"]:
                raise harness.BenchError(f"{m['name']} was not measured")
            metrics[m["name"]] = {"value": out["e2e"][m["name"]],
                                  "unit": m["unit"]}
    result["metrics"] = metrics
    dev = dict(device, memory_peak_bytes=out["memory_peak_bytes"])
    if trace:
        tr = out["rec"]["trace"]
        dev.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    result["device"] = dev
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    return result


def main(argv=None) -> int:
    args = parse(argv)
    from bench import harness

    cell = harness.load_cell(args.workload)
    device = device_info(cell.chips)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), device,
                      T_PROCESS, harness.peaks(device["kind"]))
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
