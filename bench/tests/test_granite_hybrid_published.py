"""granite-4.0-h-small: the plain reference against the published
modeling code (``transformers``' GraniteMoeHybridForCausalLM) on the same
weights, at a tiny size in float32 on the CPU.  Nothing is downloaded:
the model is built from a tiny ``GraniteMoeHybridConfig``."""

import jax
import numpy as np
import pytest

from bench.references import granite_hybrid as ref
from bench.tests.granite_tiny import LOGIT_TOL, TINY, config, served


def test_reference_matches_transformers_granite_moe_hybrid():
    torch = pytest.importorskip("torch")
    tf = pytest.importorskip("transformers")
    cfg = config(moe={**TINY["moe"], "first_held": 0, "n_held": 8})
    m = cfg["model"]
    s = m["ssm"]
    params = served(m, 256, seed=13)
    hf_cfg = tf.GraniteMoeHybridConfig(
        vocab_size=m["vocab"], hidden_size=m["d_model"],
        intermediate_size=m["moe"]["expert_d_ff"],
        num_hidden_layers=m["n_layers"], num_attention_heads=m["n_heads"],
        num_key_value_heads=m["n_kv_heads"], rms_norm_eps=m["norm_eps"],
        tie_word_embeddings=True, attention_bias=False,
        embedding_multiplier=m["embedding_multiplier"],
        residual_multiplier=m["residual_multiplier"],
        attention_multiplier=m["attention_multiplier"],
        logits_scaling=m["logits_scaling"],
        num_local_experts=m["moe"]["n_experts"],
        num_experts_per_tok=m["moe"]["top_k"],
        shared_intermediate_size=m["shared_d_ff"],
        position_embedding_type="nope", layer_types=m["layer_types"],
        mamba_n_heads=s["expand"] * m["d_model"] // s["headdim"],
        mamba_n_groups=s["n_groups"], mamba_d_state=s["state_dim"],
        mamba_d_head=s["headdim"], mamba_d_conv=s["conv_dim"],
        mamba_expand=s["expand"], mamba_chunk_size=s["chunk"],
        mamba_conv_bias=True, mamba_proj_bias=False, use_cache=False)
    hf_cfg._attn_implementation = "eager"
    torch.manual_seed(0)
    model = tf.GraniteMoeHybridForCausalLM(hf_cfg).eval()

    def t(a):
        return torch.tensor(np.asarray(a, np.float32))

    sd = {"model.embed_tokens.weight": t(params["emb"][:m["vocab"]]),
          "model.norm.weight": t(params["out_norm"])}
    seen = {"mamba": 0, "attention": 0}
    for li, kind in enumerate(m["layer_types"]):
        w = {k: np.asarray(v[seen[kind]]) for k, v in params[kind].items()}
        seen[kind] += 1
        pre = f"model.layers.{li}."
        sd[pre + "input_layernorm.weight"] = t(w["ln1"])
        sd[pre + "post_attention_layernorm.weight"] = t(w["ln2"])
        if kind == "mamba":
            sd.update({pre + "mamba.in_proj.weight": t(w["in_proj"].T),
                       pre + "mamba.conv1d.weight": t(w["conv_w"].T[:, None, :]),
                       pre + "mamba.conv1d.bias": t(w["conv_b"]),
                       pre + "mamba.dt_bias": t(w["dt_bias"]),
                       pre + "mamba.A_log": t(w["A_log"]),
                       pre + "mamba.D": t(w["D"]),
                       pre + "mamba.norm.weight": t(w["norm"]),
                       pre + "mamba.out_proj.weight": t(w["out_proj"].T)})
        else:
            for a, b in (("q", "wq"), ("k", "wk"), ("v", "wv"), ("o", "wo")):
                sd[pre + f"self_attn.{a}_proj.weight"] = t(w[b].T)
        moe = pre + "block_sparse_moe."
        sd[moe + "router.layer.weight"] = t(w["router"].T)
        sd[moe + "input_linear.weight"] = t(np.concatenate(
            [w["wg"].transpose(0, 2, 1), w["wu"].transpose(0, 2, 1)], axis=1))
        sd[moe + "output_linear.weight"] = t(w["wd"].transpose(0, 2, 1))
        sd[pre + "shared_mlp.input_linear.weight"] = t(np.concatenate(
            [w["shared_wg"].T, w["shared_wu"].T], axis=0))
        sd[pre + "shared_mlp.output_linear.weight"] = t(w["shared_wd"].T)
    missing, unexpected = model.load_state_dict(sd, strict=False)
    assert not unexpected and set(missing) <= {"lm_head.weight"}
    seq = np.random.default_rng(4).integers(1, m["vocab"], (2, 13))
    with torch.no_grad():
        want = model(torch.tensor(seq)).logits.numpy()
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.head_logits(
            params, ref.served_hidden(params, seq, m), m, "f32", chunk=100))
    # float32 on both sides; torch's chunked SSD and the reference's
    # sequential recurrence sum in other orders
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=LOGIT_TOL * np.abs(want).max())
