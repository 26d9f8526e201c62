"""granite-4.0-h-small: the configuration's sizes against the published
config, its parameter counts, the program's tree against the reference's,
the counts of a decode step, and the cell served through the harness at
a tiny size."""

import json

import jax
import numpy as np
import pytest

from bench import harness, weights
from bench.references import granite_hybrid as ref
from bench.tests.granite_tiny import CELL, TINY


def test_parameter_counts_published_and_cut():
    from dataclasses import replace

    from repro.configs import get_config

    full = get_config("granite-4.0-h-small")
    assert full.param_count() == pytest.approx(32.21e9, rel=1e-3)
    assert full.active_param_count() == pytest.approx(8.8e9, rel=0.01)
    cfg = json.loads((harness.BENCH_DIR / "configs"
                      / "granite-4.0-h-small.json").read_text())
    m = cfg["model"]
    mc = harness.model_config(cfg)
    tree = ref.param_tree(m, mc.padded_vocab)
    n_cut = sum(int(np.prod(s)) for s in jax.tree.leaves(
        weights.shapes_of(tree), is_leaf=lambda x: isinstance(x, tuple)))
    assert n_cut == mc.param_count() == pytest.approx(3.264e9, rel=1e-3)
    whole = dict(m, n_layers=40, layer_types=list(full.layer_types),
                 moe=dict(m["moe"], n_held=72))
    n_full = sum(int(np.prod(s)) for s in jax.tree.leaves(
        weights.shapes_of(ref.param_tree(whole, mc.padded_vocab)),
        is_leaf=lambda x: isinstance(x, tuple)))
    assert n_full == full.param_count()
    assert replace(mc, moe=full.moe, n_layers=40,
                   layer_types=full.layer_types).param_count() == n_full


def test_program_tree_is_the_reference_tree():
    from repro.models.transformer import LM

    cell = harness.load_cell(CELL)
    mc = harness.model_config(cell.config)
    entry, r = harness.entry_module(cell), harness.reference_module(cell)
    entry.check_tree(cell, r, mc, LM(mc).abstract_params())


def test_configuration_keeps_the_published_widths():
    cfg = json.loads((harness.BENCH_DIR / "configs"
                      / "granite-4.0-h-small.json").read_text())
    m, s = cfg["model"], cfg["model"]["ssm"]
    assert (m["d_model"], m["n_heads"], m["n_kv_heads"], m["vocab"]) == (
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["vocab_size"])
    assert m["d_model"] // m["n_heads"] == m["head_dim"] == 128
    assert (s["state_dim"], s["headdim"], s["n_groups"], s["conv_dim"],
            s["chunk"], s["conv_bias"], s["expand"]) == (
        cfg["mamba_d_state"], cfg["mamba_d_head"], cfg["mamba_n_groups"],
        cfg["mamba_d_conv"], cfg["mamba_chunk_size"], cfg["mamba_conv_bias"],
        cfg["mamba_expand"])
    assert s["expand"] * m["d_model"] // s["headdim"] == cfg["mamba_n_heads"]
    assert (m["moe"]["n_experts"], m["moe"]["top_k"], m["moe"]["expert_d_ff"],
            m["shared_d_ff"]) == (cfg["published"]["num_local_experts"],
                                  cfg["num_experts_per_tok"],
                                  cfg["intermediate_size"],
                                  cfg["shared_intermediate_size"])
    assert m["moe"]["n_held"] == cfg["num_local_experts"] == 18
    assert m["layer_types"] == cfg["layer_types"] == \
        cfg["published"]["layer_types"][:m["n_layers"]]
    assert (m["embedding_multiplier"], m["residual_multiplier"],
            m["attention_multiplier"], m["logits_scaling"], m["norm_eps"]) == (
        cfg["embedding_multiplier"], cfg["residual_multiplier"],
        cfg["attention_multiplier"], cfg["logits_scaling"], cfg["rms_norm_eps"])
    assert cfg["position_embedding_type"] == "nope" and m["rope_style"] == "none"


def test_expected_experts_and_the_step_bytes_at_32_slots():
    cfg = json.loads((harness.BENCH_DIR / "configs"
                      / "granite-4.0-h-small.json").read_text())
    m = cfg["model"]
    assert ref.expected_experts(m, 32) == pytest.approx(17.85, abs=0.005)
    assert ref.expected_experts(m, 1) == pytest.approx(2.5)
    nbytes = ref.decode_step_bytes(m, [0] * 32, 2, 2)
    # non-expert weights 3.13 GB, held experts touched 3.37 GB, SSM
    # state read and written 2.42 GB
    assert nbytes == pytest.approx(3.13e9 + 3.37e9 + 2.42e9, rel=0.005)
    per_pos = ref.decode_step_bytes(m, [1] * 32, 2, 2) - nbytes
    assert per_pos == 32 * 8 * 128 * 2 * 2


def test_the_cell_serves_at_a_tiny_size_through_the_harness():
    from bench.tests.tiny import run_tiny, shrink

    cell = shrink(harness.load_cell(CELL), **TINY)
    out = run_tiny(cell)
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert out["correct"], out["checks"]
    assert [m["name"] for m in cell.per_layer][-2:] == ["splice_ms", "expert_fill"]
