"""Compile rehearsals for one TPU v5e, with no chip attached.

The TPU compiler compiles for a described v5e topology, so what it
would refuse on the chip (block tiling, unsupported Pallas lowerings,
programs that do not fit HBM) fails here.  Nothing runs: these tests
say nothing about results or times.

The topology is described inside a module-scoped fixture, never while
a module is imported: one process at a time may load the TPU library,
and every test worker imports every test file.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.models.transformer import LM
from repro.optim import AdamW
from repro.train.orchestrator import _grad_fn_for, _update_fn_for

#: HBM of one TPU v5e chip (Google Cloud documentation, "TPU v5e")
V5E_HBM_BYTES = 16 * 2**30
QWEN = get_config("qwen2_0_5b")
MAMBA = get_config("falcon_mamba_7b")
BATCH, SEQ = 4, 256      # one gradient shard of chip_smoke.py's training


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        # the compiler would otherwise write its logs under /tmp
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:  # whatever fails, there is no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent compilation cache
    off: what is compiled for it here could not be read back."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_case(name, chip):
    bf16, f32 = jnp.bfloat16, jnp.float32
    hq, hkv, hd = QWEN.n_heads, QWEN.n_kv_heads, QWEN.hd
    q = _sds((BATCH, SEQ, hq, hd), bf16, chip)
    kv = _sds((BATCH, SEQ, hkv, hd), bf16, chip)
    if name == "flash_attention":
        return lambda q, k, v: ops.flash_attention(q, k, v), (q, kv, kv)
    if name == "flash_attention_bwd":
        lse = _sds((BATCH, hq, SEQ), f32, chip)
        return (lambda q, k, v, o, do, lse: ops.flash_attention_bwd(
            q, k, v, o, do, lse), (q, kv, kv, q, q, lse))
    if name == "decode_attention":
        q1 = _sds((BATCH, 1, hq, hd), bf16, chip)
        length = _sds((), jnp.int32, chip)
        return ops.decode_attention, (q1, kv, kv, length)
    din = MAMBA.ssm.expand * MAMBA.d_model
    n = MAMBA.ssm.state_dim
    x = _sds((1, SEQ, din), f32, chip)
    bc = _sds((1, SEQ, n), f32, chip)
    return ops.mamba_scan, (x, x, _sds((din, n), f32, chip), bc, bc,
                            _sds((din,), f32, chip))


@pytest.mark.parametrize("name", ["flash_attention", "flash_attention_bwd",
                                  "decode_attention", "mamba_scan"])
def test_kernel_compiles_for_v5e(name, one_chip):
    """Each Pallas kernel compiles at real widths (qwen2-0.5B heads for
    attention, falcon-mamba-7b's d_inner and state for the scan) into a
    Mosaic custom call, not an interpreted fallback."""
    fn, args = _kernel_case(name, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _abstract(tree, chip):
    return jax.tree.map(lambda s: _sds(s.shape, s.dtype, chip), tree)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("program", ["grad", "update"])
def test_full_width_training_program_fits_one_chip(program, one_chip):
    """The training DAG's two device programs at qwen2-0.5B's published
    widths: the per-shard gradient and the shard-average + AdamW
    update.  The update's arguments and outputs are the step's whole
    live set (old and new parameter/optimizer state plus the shard
    gradients), so it fitting HBM is what lets the DAG run."""
    lm = LM(QWEN)
    params = _abstract(lm.abstract_params(), one_chip)
    if program == "grad":
        tok = _sds((BATCH, SEQ), jnp.int32, one_chip)
        compiled = _grad_fn_for(lm).lower(
            params, {"tokens": tok, "labels": tok}).compile()
    else:
        opt = AdamW()
        state = _abstract(jax.eval_shape(opt.init, lm.abstract_params()),
                          one_chip)
        compiled = _update_fn_for(opt).lower(
            [params, params], state, params).compile()
    used = _device_bytes(compiled)
    assert 0 < used < V5E_HBM_BYTES, (program, used)
