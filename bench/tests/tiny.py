"""Cells of the benchmark cut to a size the CPU runs in seconds, for the
tests: the same files, entries and checks, with every size made small."""

from __future__ import annotations

import copy

from bench import harness

TINY_MODEL = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                  head_dim=16, d_ff=128, vocab=256, pad_to=128)

#: limits of the tiny cells, set like the cells' own from readings at
#: these sizes on the CPU (seeds 2**31 + 11, 5, 77): sound runs 0.0 and
#: the fp8 control 0.0030 to 0.0079
TINY_LIMITS = {"logit_gap": 1e-3}


def tiny_cell(name: str, **model) -> harness.Cell:
    return shrink(harness.load_cell(name), **{**TINY_MODEL, **model})


def shrink(cell: harness.Cell, **model) -> harness.Cell:
    """A copy of ``cell`` with the given model sizes and tiny traffic."""
    cell = copy.deepcopy(cell)
    cell.config["model"].update(model)
    cell.limits = dict(TINY_LIMITS)
    tr = cell.traffic
    tr["engine"] = {"max_batch": 2, "prompt_len": 16, "max_len": 40}
    tr["prompt"].update(min=4, max=16, median=8)
    tr["output"].update(min=3, max=6, median=4)
    tr["arrivals"] = {"kind": "closed", "clients": 4}
    tr["strata"] = 2
    tr["check"] = {"sample": 2}
    return cell


#: long enough for a few tiny requests to finish on a loaded CPU
SERVE_SECONDS = 6.0


def run_tiny(cell: harness.Cell, seed: int = 2**31 + 11,
             seconds: float = SERVE_SECONDS) -> dict:
    """The harness's run of ``cell`` on the CPU, without its look for a
    chip; returns the result line's object and the entry's output."""
    import time

    from bench import run

    dev = {"platform": "cpu", "kind": "cpu", "count": 1}
    return run.run_cell(cell, seed, seconds, False, dev, time.perf_counter(),
                        harness.peaks("TPU v5 lite"))
