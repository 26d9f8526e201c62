"""chip_smoke.py on the CPU: its phases at the smoke config, and its
refusal to run anywhere but on a TPU."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import get_config

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load()


def test_train_phase_at_smoke_config(smoke, capsys):
    cfg = get_config(smoke.ARCH).smoke()
    losses = smoke.train_phase(cfg, steps=6, global_batch=8, seq_len=64)
    assert len(losses) == 6 and losses[-1] < losses[0]
    out = capsys.readouterr().out
    assert out.count("train step ") == 6
    assert "float32 CPU reference" in out


def test_serve_phase_at_smoke_config(smoke):
    cfg = get_config(smoke.ARCH).smoke()
    outs = smoke.serve_phase(cfg, n_requests=5, max_batch=2, prompt_len=8,
                             max_len=32, max_new_tokens=4)
    assert len(outs) == 5
    assert all(len(t) == 4 and all(0 <= x < cfg.vocab for x in t)
               for t in outs)


def test_phase_failure_raises(smoke):
    """A wrong result stops the run with a named error: here the cache
    is too short for the tokens each request asks for."""
    cfg = get_config(smoke.ARCH).smoke()
    with pytest.raises(smoke.SmokeFailure, match="2 of 4 tokens"):
        smoke.serve_phase(cfg, n_requests=2, max_batch=2, prompt_len=8,
                          max_len=10, max_new_tokens=4)


@pytest.mark.parametrize("alone", [False, True],
                         ids=["in_repo", "script_alone"])
def test_main_refuses_without_tpu(alone, tmp_path):
    """With no TPU (and with the script away from the repo) it exits
    non-zero and prints no result line."""
    script = SCRIPT
    if alone:
        script = tmp_path / SCRIPT.name
        shutil.copy(SCRIPT, script)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, str(script)], env=env,
                       cwd=script.parent, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    for line in r.stdout.splitlines():
        try:
            doc = json.loads(line)
        except ValueError:
            continue
        assert not (isinstance(doc, dict) and "ok" in doc), line
