"""The dense decoder's operation and byte counts
(bench/references/dense_decoder.py) against counts made by hand."""

import json

import pytest

from bench.harness import BENCH_DIR
from bench.references import dense_decoder as ref

SMALL = dict(n_layers=2, d_model=8, n_heads=2, n_kv_heads=1, head_dim=4,
             d_ff=16, vocab=10)


#: Qwen2-0.5B's sizes (hf Qwen/Qwen2-0.5B config.json)
QWEN2 = dict(n_layers=24, d_model=896, n_heads=14, n_kv_heads=2, head_dim=64,
             d_ff=4864, vocab=151936)


def _model(name):
    if name == "qwen2-0.5b":
        return QWEN2
    return json.loads((BENCH_DIR / "configs" / f"{name}.json").read_text())["model"]


def test_matmul_params_small_by_hand():
    # per layer: q 8x8, k 8x4, v 8x4, o 8x8, gate/up/down 3 x 8x16
    per_layer = 64 + 32 + 32 + 64 + 384
    assert ref.matmul_params(SMALL) == 2 * per_layer + 8 * 10


@pytest.mark.parametrize("name, expected", [
    # 24 x (896*896 + 2*896*128 + 896*896 + 3*896*4864) + 896*151936
    ("qwen2-0.5b", 24 * (802816 + 229376 + 802816 + 13074432) + 136134656),
    # 28 x (4096*4096 + 2*4096*256 + 4096*4096 + 3*4096*13696) + 4096*65024
    ("chatglm3-6b", 28 * (16777216 + 2097152 + 16777216 + 168296448)
     + 266338304),
])
def test_matmul_params_of_the_configs(name, expected):
    assert ref.matmul_params(_model(name)) == expected


def test_decode_token_and_step_bytes():
    m = SMALL
    assert ref.decode_token_flops(m, 7) == 2 * 1232 + 2 * 2 * 4 * 4 * 7
    # kv: 2 layers x (k, v) x 1 head x 4 dims x 2 bytes per token
    assert ref.kv_bytes_per_token(m, 2) == 32
    assert ref.decode_step_bytes(m, [3, 5], 2, 2) == 1232 * 2 + 2 * 8 * 2 + 32 * 8
