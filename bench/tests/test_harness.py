"""The harness: BENCHMARK.json's shape, the traffic generator, refusal of
the CPU, and cells, configurations, traffic and metrics found by name."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import harness, loadgen

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def spec():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_benchmark_json_keeps_to_the_contract(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["command"][:2] == ["python3", "bench/run.py"]
    assert spec["paths"] == ["bench"] and 1 <= spec["run_seconds"] <= 51
    configs = {c["name"]: c for c in spec["configs"]}
    cells = {w["name"]: w for w in spec["workloads"]}
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    names = list(configs) + list(cells) + list(e2e) + [m["name"] for m in spec["per_layer"]]
    assert len(names) == len(set(names))
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file() and c["file"].startswith("bench/")
        assert all(NAME.match(k) for k in c["reduced"])
    used = set()
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200 and NAME.match(w["name"])
        used.add(w["config"])
        harness.load_cell(w["name"])   # every file it needs is found
    assert used == set(configs)
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25 and UNIT.match(m["unit"])
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["moves"] in e2e and set(m["workloads"]) <= set(cells)
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        for w in m["workloads"]:    # each of its cells reports what it moves
            assert w in e2e[m["moves"]].get("workloads", [w])
    for w in cells:                 # every cell: setup_s, one more, a per-layer
        cell = harness.load_cell(w)
        assert len(cell.end_to_end) >= 2 and cell.per_layer


def _traffic():
    return json.loads((ROOT / "bench" / "traffic" / "chat-closed-16.json").read_text())


def test_traffic_is_a_function_of_the_seed():
    t = _traffic()
    a = [loadgen.RequestStream(t, 65024, 2**31 + 3).request(k) for k in range(24)]
    b = [loadgen.RequestStream(t, 65024, 2**31 + 3).request(k) for k in range(24)]
    c = [loadgen.RequestStream(t, 65024, 2**31 + 4).request(k) for k in range(24)]
    assert a == b and a != c


def test_traffic_lengths_keep_to_their_clips_and_strata():
    t = _traffic()
    for seed in (0, 1, 2**31 + 9):
        s = loadgen.RequestStream(t, 65024, seed)
        blocks = {}
        for k in range(48):
            prompt, olen = s.request(k)
            assert t["prompt"]["min"] <= len(prompt) <= t["prompt"]["max"]
            assert t["output"]["min"] <= olen <= t["output"]["max"]
            assert all(1 <= x < 65024 for x in prompt)
            blocks.setdefault(k // t["strata"], []).append((len(prompt), olen))
        # every block of ``strata`` requests holds the same sizes
        first = sorted(x for x, _ in blocks[0]), sorted(y for _, y in blocks[0])
        for blk in blocks.values():
            assert (sorted(x for x, _ in blk), sorted(y for _, y in blk)) == first


def test_run_refuses_the_cpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "serve.chatglm3-6b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_a_new_cell_is_found_by_name_from_added_files_only(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench = root / "bench"
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    cfg = json.loads((bench / "configs" / "chatglm3-6b.json").read_text())
    cfg["name"] = "other-model"
    (bench / "configs" / "other-model.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "other-mix.json").write_text(
        json.dumps(dict(_traffic(), arrivals={"kind": "closed", "clients": 32})))
    (bench / "limits" / "serve.other-model.open.json").write_text('{"logit_gap": 1}')
    (bench / "metrics" / "other_metric.serve.py").write_text(
        "def read(rec):\n    return rec['engine']['prefills'] * 2.0\n")
    spec["configs"].append({"name": "other-model", "source": "x",
                            "file": "bench/configs/other-model.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "serve.other-model.open", "config": "other-model",
                              "traffic": "other-mix", "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "other_metric.serve", "unit": "1",
                              "better": "lower", "source": "program_counter",
                              "layer": "serving engine (repro.serving)",
                              "moves": "ttft_p95_ms",
                              "workloads": ["serve.other-model.open"]})
    spec["end_to_end"] = [
        dict(m, workloads=m["workloads"] + ["serve.other-model.open"])
        if "ttft_p95_ms" == m["name"] else m for m in spec["end_to_end"]]
    cell = harness.load_cell("serve.other-model.open", bench_dir=bench,
                             benchmark=spec)
    assert cell.config["name"] == "other-model"
    assert cell.traffic["arrivals"]["clients"] == 32
    assert [m["name"] for m in cell.per_layer] == ["other_metric.serve"]
    got = harness.read_per_layer(cell, {"engine": {"prefills": 3}})
    assert got == {"other_metric.serve": {"value": 6.0, "unit": "1"}}
    assert harness.entry_module(cell).run is not None
    # nothing that was there has changed
    assert all(p.read_bytes() == b for p, b in before.items())


#: a reference that declares the program's mixture-of-experts tree and
#: counts the experts a token passes through; its equations are a stub
#: (embedding in, tied embedding out), since what is judged is the harness
MOE_REFERENCE = """
from bench.weights import Leaf


def param_tree(m, padded_vocab):
    d, L, e = m["d_model"], m["n_layers"], m["moe"]["n_experts"]
    hd, f = m["d_model"] // m["n_heads"], m["moe"]["expert_d_ff"]
    hq, hkv = m["n_heads"] * hd, m["n_kv_heads"] * hd
    gain = {"scale": 0.05, "shift": 1.0}
    return {"emb": Leaf((padded_vocab, d)), "out_norm": Leaf((d,), **gain),
            "blocks": {"ln1": Leaf((L, d), **gain), "wq": Leaf((L, d, hq)),
                       "wk": Leaf((L, d, hkv)), "wv": Leaf((L, d, hkv)),
                       "wo": Leaf((L, hq, d)), "ln2": Leaf((L, d), **gain),
                       "router": Leaf((L, d, e)), "wg": Leaf((L, e, d, f)),
                       "wu": Leaf((L, e, d, f)), "wd": Leaf((L, e, f, d))}}


def stacked_groups(m):
    return {"blocks": m["n_layers"]}


def _active(m):
    d, moe = m["d_model"], m["moe"]
    hd = d // m["n_heads"]
    attn = 2 * d * m["n_heads"] * hd + 2 * d * m["n_kv_heads"] * hd
    experts = moe["top_k"] * 3 * d * moe["expert_d_ff"]
    return m["n_layers"] * (attn + d * moe["n_experts"] + experts) + d * m["vocab"]


def decode_token_flops(m, context):
    hd = m["d_model"] // m["n_heads"]
    return 2 * _active(m) + m["n_layers"] * m["n_heads"] * 4 * hd * context


def decode_step_bytes(m, contexts, w_itemsize, kv_itemsize):
    hd = m["d_model"] // m["n_heads"]
    kv = m["n_layers"] * 2 * m["n_kv_heads"] * hd * kv_itemsize
    return _active(m) * w_itemsize + kv * sum(contexts)


def served_hidden(params, seqs, m, prec="f32"):
    import jax.numpy as jnp
    return params["emb"][jnp.asarray(seqs)].astype(jnp.float32)


def head_logits(params, x, m, prec="f32"):
    import jax.numpy as jnp
    return x @ params["emb"][:m["vocab"]].astype(jnp.float32).T
"""

#: granite-moe-3b's family at the program's smoke sizes
#: (``get_config("granite_moe_3b").smoke()``)
MOE_MODEL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
             "d_ff": 128, "vocab": 128, "pad_to": 16, "tie_embeddings": True,
             "moe": {"n_experts": 4, "top_k": 2, "expert_d_ff": 64}}


def _shapes(tree):
    import jax

    return jax.tree.map(lambda a: tuple(a.shape), tree)


def _moe_config(**moe):
    return {"name": "granite-moe-smoke", "arch_id": "granite_moe_3b",
            "reference": "moe_stub",
            "model": dict(MOE_MODEL, moe=dict(MOE_MODEL["moe"], **moe))}


def test_a_moe_configuration_is_added_as_files_only(tmp_path):
    from repro.models.config import MoEConfig
    from repro.models.transformer import LM

    from bench.tests.tiny import run_tiny, shrink

    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = root / "bench"
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    (bench / "configs" / "granite-moe-smoke.json").write_text(
        json.dumps(_moe_config()))
    (bench / "references" / "moe_stub.py").write_text(MOE_REFERENCE)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    name = "serve.granite-moe-smoke.chat"
    spec["configs"].append({"name": "granite-moe-smoke", "source": "x",
                            "file": "bench/configs/granite-moe-smoke.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": name, "config": "granite-moe-smoke",
                              "traffic": "chat-closed-16", "chips": 1, "why": "x"})
    spec["end_to_end"] = [dict(m, workloads=m["workloads"] + [name])
                          if "workloads" in m else m for m in spec["end_to_end"]]
    cell = harness.load_cell(name, bench_dir=bench, benchmark=spec)

    mc = harness.model_config(cell.config)
    assert isinstance(mc.moe, MoEConfig) and mc.moe.n_experts == 4
    entry, ref = harness.entry_module(cell), harness.reference_module(cell)
    program = LM(mc).abstract_params()
    entry.check_tree(cell, ref, mc, program)      # raises where they differ
    params = entry.served_params(ref, cell.config["model"], mc, 3)
    assert params["blocks"]["wg"].shape == (2, 4, 64, 64)
    assert _shapes(params) == _shapes(program)

    out = run_tiny(shrink(cell))
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert out["attempted"] > 0 and "logit_gap" in out["checks"]
    # nothing that was there has changed
    assert all(p.read_bytes() == b for p, b in before.items())


def test_an_unknown_key_inside_a_group_of_sizes_is_refused():
    with pytest.raises(harness.BenchError, match="model.moe.*n_shared"):
        harness.model_config(_moe_config(n_shared=1))


def test_the_tree_check_names_the_reference():
    from dataclasses import replace

    from repro.models.transformer import LM

    from bench.tests.tiny import tiny_cell

    cell = tiny_cell("serve.chatglm3-6b.chat")
    entry, ref = harness.entry_module(cell), harness.reference_module(cell)
    mc = harness.model_config(cell.config)
    entry.check_tree(cell, ref, mc, LM(mc).abstract_params())
    no_bias = LM(replace(mc, qkv_bias=False)).abstract_params()
    with pytest.raises(harness.BenchError, match="bench/references/dense_decoder.py"):
        entry.check_tree(cell, ref, mc, no_bias)


def test_a_reader_that_finds_nothing_leaves_its_metric_out():
    cell = harness.load_cell("serve.chatglm3-6b.chat")
    got = harness.read_per_layer(cell, {"engine": {}, "trace": None})
    assert got == {}


def test_a_share_of_a_peak_over_100_percent_fails_the_run(tmp_path):
    cell = harness.load_cell("serve.chatglm3-6b.chat")
    rec = {"engine": {"decode_s": 1.0, "prefills": 0},
           "decode_flops": 2 * 197e12, "peak": {"flops_per_s": 197e12}}
    with pytest.raises(harness.BenchError, match="decode_mfu"):
        harness.read_per_layer(cell, rec)


def test_unknown_device_has_no_peaks():
    with pytest.raises(harness.BenchError):
        harness.peaks("TPU v9 imaginary")
    assert harness.peaks("TPU v5 lite")["flops_per_s"] == 197e12


def test_judge_needs_every_number_under_its_limit():
    assert harness.judge([{"value": 0.1, "limit": 0.2}])
    assert not harness.judge([{"value": 0.3, "limit": 0.2}])
    assert not harness.judge([{"value": float("nan"), "limit": 0.2}])
    assert not harness.judge([])
