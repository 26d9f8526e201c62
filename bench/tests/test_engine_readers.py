"""The readers of the serving engine's own counters (bench/metrics/) on
synthetic records: a value where the counters are there, None where they
are missing, as at a program without them, or count nothing."""

import pytest

from bench import harness

ENGINE = {"prefills": 16, "decode_steps": 500, "queue_wait_s": 304.0,
          "slots_busy": 2040, "slots_idle": 1960, "decode_dispatch_s": 1.5,
          "decode_wait_s": 37.0, "decode_sample_s": 2.5, "compiles": 14,
          "compile_s": 2.55}
#: the counters of the engine before it had these
OLD_ENGINE = {"prefills": 16, "decode_steps": 500, "completed": 8,
              "prefill_s": 5.9, "decode_s": 41.0}


def _rec(**engine):
    return {"window_s": 51.0, "engine": {**ENGINE, **engine}}


CASES = [
    ("queue_wait_ms", _rec(), 19_000.0),
    ("queue_wait_ms", _rec(prefills=0), None),
    ("queue_wait_ms", {"window_s": 51.0, "engine": OLD_ENGINE}, None),
    ("batch_occupancy", _rec(), 51.0),
    ("batch_occupancy", _rec(slots_busy=0, slots_idle=0), None),
    ("batch_occupancy", {"window_s": 51.0, "engine": OLD_ENGINE}, None),
    ("decode_host_ms", _rec(), 8.0),
    ("decode_host_ms", _rec(decode_steps=0), None),
    ("decode_host_ms", {"window_s": 51.0, "engine": OLD_ENGINE}, None),
    ("compile_share", _rec(), 5.0),
    ("compile_share", _rec(compile_s=0.0), 0.0),
    ("compile_share", {"window_s": 0.0, "engine": ENGINE}, None),
    ("compile_share", {"window_s": 51.0, "engine": OLD_ENGINE}, None),
    ("compile_share", {"window_s": 51.0}, None),
]


@pytest.mark.parametrize("metric,rec,want", CASES,
                         ids=[f"{m}-{i}" for i, (m, _, _) in enumerate(CASES)])
def test_engine_counter_readers(metric, rec, want):
    mod = harness.load_module(harness.BENCH_DIR / "metrics" / f"{metric}.py",
                              f"bench_metric_{metric}")
    got = mod.read(rec)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)
