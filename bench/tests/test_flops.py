"""bench/flops.py: what holds for every architecture's counts."""

from bench import flops


def test_least_time_names_its_bound():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.least_time_s(1000, 50, peak) == (10.0, "compute")
    assert flops.least_time_s(100, 50, peak) == (5.0, "memory")
