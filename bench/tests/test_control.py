"""The control of ``correct``, at a size the CPU holds: the reference
computed in fp8 (the precision below the configuration's bfloat16), put
in the program's place, reads far above what the program reads, and the
harness's own verdict under the cell's limits finds it not correct.  On
the chip, at the cell's own size, ``bench/control.py`` takes these
readings (PERF.md gives them)."""

from bench import control, harness
from bench.tests.tiny import SERVE_SECONDS, tiny_cell


def test_serving_fp8_control_reads_far_above_the_program():
    cell = tiny_cell("serve.chatglm3-6b.chat")
    row = control.readings(cell, 2**31 + 21, SERVE_SECONDS, "fp8",
                           harness.peaks("TPU v5 lite"))
    assert row["control"]["logit_gap"] > 3 * row["program"]["logit_gap"]
    assert row["served_tokens_compared"] > 0
    assert row["program_correct"] is True
    assert row["control_correct"] is False
