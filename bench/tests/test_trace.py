"""bench/trace.py on synthetic intervals and on a small trace recorded on
a TPU v5e (bench/tests/data/tiny_v5e.xplane.pb: three calls of a jitted
``tiny_matmul``, a 20 ms sleep, one more call, inside ``bench.probe.*``
host spans)."""

from pathlib import Path

import pytest

from bench import trace as bt

CHIP_TRACE = Path(__file__).parent / "data" / "tiny_v5e.xplane.pb"


def _trace(ops, modules=(), host=()):
    return bt.Trace(
        devices={"/device:TPU:0": {
            "ops": [bt.Ev(n, s, e) for n, s, e in ops],
            "modules": [bt.Ev(n, s, e) for n, s, e in modules]}},
        host=[bt.Ev(n, s, e) for n, s, e in host])


def test_busy_time_is_a_union_not_a_sum():
    ivs = [(0, 10), (5, 15), (20, 30), (22, 25)]
    assert bt.busy_ns(ivs, 0, 100) == 25
    assert sum(e - s for s, e in ivs) == 33
    assert bt.merge(ivs) == [(0, 15), (20, 30)]


def test_busy_time_is_clipped_to_the_window():
    assert bt.busy_ns([(0, 10), (20, 30)], 5, 25) == 10


def test_idle_share_of_a_synthetic_gap_equals_the_gap():
    tr = _trace(ops=[("a", 0, 100_000), ("b", 400_000, 1_000_000)],
                host=[("bench.x.busy", 0, 1_000_000),
                      ("bench.x.wait", 100_000, 400_000)])
    s = bt.summarize(tr)
    assert s["window_s"] == pytest.approx(1e-3)
    assert s["busy_s"] == pytest.approx(0.7e-3)
    assert 1 - s["busy_s"] / s["window_s"] == pytest.approx(0.3)
    assert s["idle_gaps"] == [["x.wait (x1)", pytest.approx(0.3e-3)]]


def test_program_device_time_is_found_by_name():
    tr = _trace(ops=[("f", 0, 10), ("g", 20, 60)],
                modules=[("jit_decode_step(12)", 0, 10),
                         ("jit_decode_step(12)", 20, 40),
                         ("jit_other(3)", 40, 60)],
                host=[("bench.x", 0, 60)])
    s = bt.summarize(tr)
    assert bt.program_seconds(s, "decode_step") == (pytest.approx(30e-9), 2)
    assert bt.program_seconds(s, "missing") is None


def test_a_trace_without_a_device_is_refused():
    with pytest.raises(ValueError):
        bt.summarize(bt.Trace(devices={}, host=[bt.Ev("bench.x", 0, 1)]))


@pytest.fixture(scope="module")
def chip_summary():
    tr = bt.read(str(CHIP_TRACE))
    # the device clock of this trace runs about 1.3 ms ahead of the host
    # spans: take the window from the first device event to the last span
    dev = tr.devices["/device:TPU:0"]["modules"]
    _, hi = bt.window_of(tr.host)
    return bt.summarize(tr, window=(min(e.start for e in dev), hi))


def test_chip_trace_finds_the_device_and_the_program(chip_summary):
    s = chip_summary
    assert s["busy_s"] > 0
    found = bt.program_seconds(s, "tiny_matmul")
    assert found is not None and found[1] == 4
    # the program's spans hold its ops, and a few us of launch besides
    assert found[0] == pytest.approx(s["busy_s"], rel=0.05)


def test_chip_trace_idle_time_holds_the_sleep(chip_summary):
    s = chip_summary
    idle = s["window_s"] - s["busy_s"]
    assert idle >= 0.02
    labels = [lab for lab, _ in s["idle_gaps"]]
    assert any("probe.sleep" in lab for lab in labels)
    top_label, top_s = s["idle_gaps"][0]
    assert "probe.sleep" in top_label and top_s >= 0.019


def test_chip_trace_names_ops_by_program(chip_summary):
    names = [n for n, _ in chip_summary["device_ops"]]
    assert names and all(n.startswith("jit_tiny_matmul/%") for n in names)
