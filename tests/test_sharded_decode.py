"""Numeric check of the shard_map flash-decode (multi-device needed, so
it runs in a subprocess with forced host devices)."""

import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.models.layers import decode_attention_sharded
from repro.models.sharding import set_batch_axes, set_ctx_mesh
from repro.kernels.ref import decode_attention_ref

mesh = jax.make_mesh((2, 4), ("data", "model"))
set_ctx_mesh(mesh); set_batch_axes(("data",))
B, T, Hq, Hkv, D = 4, 64, 8, 2, 16
rng = jax.random.PRNGKey(0); ks = jax.random.split(rng, 5)
q = jax.random.normal(ks[0], (B, 1, Hq, D))
kc = jax.random.normal(ks[1], (B, T, Hkv, D))
vc = jax.random.normal(ks[2], (B, T, Hkv, D))
kn = jax.random.normal(ks[3], (B, 1, Hkv, D))
vn = jax.random.normal(ks[4], (B, 1, Hkv, D))
length = jnp.int32(37)

kv_sh = NamedSharding(mesh, P("data", "model", None, None))
rep_sh = NamedSharding(mesh, P("data", None, None, None))
with mesh:
    out, kc2, vc2 = jax.jit(
        lambda *a: decode_attention_sharded(*a, dp_axes=("data",)),
    )(jax.device_put(q, rep_sh), jax.device_put(kc, kv_sh),
      jax.device_put(vc, kv_sh), jax.device_put(kn, rep_sh),
      jax.device_put(vn, rep_sh), length)

# reference: update cache at position `length`, attend over length+1
kc_ref = kc.at[:, 37].set(kn[:, 0])
vc_ref = vc.at[:, 37].set(vn[:, 0])
o_ref = decode_attention_ref(q, kc_ref, vc_ref, 38)
err = float(jnp.abs(out - o_ref).max())
assert err < 2e-2, err
err_k = float(jnp.abs(jnp.asarray(kc2) - kc_ref).max())
assert err_k < 1e-5, err_k
print("SHARDED_DECODE_OK", err)
"""


@pytest.mark.slow
def test_sharded_decode_matches_reference():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "SHARDED_DECODE_OK" in r.stdout, (r.stdout, r.stderr[-2000:])


@pytest.mark.slow
def test_dryrun_smoke_cell():
    """End-to-end dry-run of the smallest cell in a subprocess."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "whisper_base", "--shape", "train_4k", "--mesh", "single",
         "--out", "/tmp/repro_dryrun_test", "--tag", "testrun"],
        env=env, capture_output=True, text=True, timeout=1200,
        cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "[ok]" in r.stdout, (r.stdout, r.stderr[-2000:])
