import os
# a host-device dry run: pinned to the CPU so it can never take the chip
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without real hardware:
``jax.jit(step, ...).lower(**abstract inputs).compile()`` on the
production mesh (16x16 single pod / 2x16x16 multi-pod), then extracts
memory analysis, cost analysis and the collective schedule for the
roofline (EXPERIMENTS.md SDry-run / SRoofline).

One cell per invocation (compiles are heavy; the driver parallelizes
across processes):

    PYTHONPATH=src python -m repro.launch.dryrun \
        --arch yi_6b --shape train_4k --mesh single --out reports/

Hillclimb levers (recorded per run): --zero, --ep, --microbatches N,
--no-remat, --moment-dtype bfloat16, --loss-chunk N.
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_config
from repro.launch.mesh import make_production_mesh
from repro.models.sharding import (
    cache_specs,
    dp_axes,
    opt_state_specs,
    param_specs,
)
from repro.models.transformer import LM
from repro.optim import AdamW, OptState
from repro.roofline.collectives import collective_bytes
from repro.roofline.hlo_cost import analyze as hlo_analyze
from repro.roofline.model import Roofline, model_flops
from repro.train.steps import make_decode_step, make_prefill_step, make_train_step


def sds(shape_tree, spec_tree, mesh):
    """Attach NamedShardings to a ShapeDtypeStruct tree."""
    return jax.tree.map(
        lambda s, sp: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, sp)),
        shape_tree, spec_tree,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def abstract_inputs(cfg, shape, mesh, kind, opt: AdamW, zero: bool,
                    ep: bool, fsdp: bool = False):
    lm = LM(cfg)
    p_shapes = lm.abstract_params()
    p_specs = param_specs(cfg, p_shapes, mesh, expert_parallel=ep,
                          fsdp=fsdp)
    params = sds(p_shapes, p_specs, mesh)
    dp = dp_axes(mesh)
    b, s = shape.global_batch, shape.seq_len
    dp_size = int(np.prod([mesh.shape[a] for a in dp]))
    tok_spec = P(dp, None) if b % dp_size == 0 else P(None, None)
    bvec_spec = P(dp) if b % dp_size == 0 else P(None)

    if kind == "train":
        o_shapes = jax.eval_shape(opt.init, p_shapes)
        m_specs = opt_state_specs(p_specs, zero=zero, mesh=mesh,
                                  shapes=p_shapes)
        o_specs = OptState(step=P(), m=m_specs, v=m_specs)
        opt_state = sds(o_shapes, o_specs, mesh)
        batch_shapes = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
                        "labels": jax.ShapeDtypeStruct((b, s), jnp.int32)}
        bspecs = {"tokens": tok_spec, "labels": tok_spec}
        if cfg.family == "encdec":
            batch_shapes["frames"] = jax.ShapeDtypeStruct(
                (b, cfg.enc_seq, cfg.d_model), jnp.bfloat16)
            bspecs["frames"] = P(dp, None, None) if b % dp_size == 0 else P()
        if cfg.family == "vlm":
            batch_shapes["img_embeds"] = jax.ShapeDtypeStruct(
                (b, cfg.img_tokens, cfg.d_model), jnp.bfloat16)
            bspecs["img_embeds"] = P(dp, None, None) if b % dp_size == 0 else P()
        batch = sds(batch_shapes, bspecs, mesh)
        return lm, (params, opt_state, batch)

    if kind == "prefill":
        batch_shapes = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
        bspecs = {"tokens": tok_spec}
        if cfg.family == "encdec":
            batch_shapes["frames"] = jax.ShapeDtypeStruct(
                (b, cfg.enc_seq, cfg.d_model), jnp.bfloat16)
            bspecs["frames"] = P(dp, None, None) if b % dp_size == 0 else P()
        if cfg.family == "vlm":
            batch_shapes["img_embeds"] = jax.ShapeDtypeStruct(
                (b, cfg.img_tokens, cfg.d_model), jnp.bfloat16)
            bspecs["img_embeds"] = P(dp, None, None) if b % dp_size == 0 else P()
        batch = sds(batch_shapes, bspecs, mesh)
        return lm, (params, batch)

    if kind == "decode":
        c_shapes = jax.eval_shape(lambda: lm.init_cache(b, s))
        c_specs = cache_specs(cfg, c_shapes, mesh, b)
        cache = sds(c_shapes, c_specs, mesh)
        token = jax.ShapeDtypeStruct(
            (b,), jnp.int32, sharding=NamedSharding(mesh, bvec_spec))
        return lm, (params, cache, token)

    raise ValueError(kind)


def run_cell(arch: str, shape_name: str, mesh_kind: str, *,
             zero: bool = False, ep: bool = False, microbatches: int = 1,
             remat: bool = True, moment_dtype: str = "float32",
             moe_global_routing: bool = False, sharded_decode: bool = False,
             ssm_scan_dtype: str = "float32", fsdp: bool = False,
             tag: str = "baseline") -> dict:
    from dataclasses import replace as _replace
    cfg = get_config(arch)
    if moe_global_routing:
        cfg = _replace(cfg, moe_group_routing=False)
    if sharded_decode:
        cfg = _replace(cfg, sharded_decode=True)
    if ssm_scan_dtype != "float32":
        cfg = _replace(cfg, ssm_scan_dtype=ssm_scan_dtype)
    from repro.models.sharding import set_batch_axes, set_ctx_mesh
    set_batch_axes(("pod", "data") if mesh_kind == "multi" else ("data",))
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    set_ctx_mesh(mesh)
    n_chips = int(np.prod(list(mesh.shape.values())))
    opt = AdamW(moment_dtype=moment_dtype)
    t0 = time.time()

    lm, args = abstract_inputs(cfg, shape, mesh, shape.kind, opt, zero, ep,
                               fsdp=fsdp)
    if shape.kind == "train":
        step = make_train_step(lm, opt, microbatches=microbatches,
                               remat=remat)
        donate = (0, 1)
    elif shape.kind == "prefill":
        step = make_prefill_step(lm, max_len=shape.seq_len)
        donate = ()
    else:
        step = make_decode_step(lm)
        donate = (1,)

    with mesh:
        lowered = jax.jit(step, donate_argnums=donate).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    try:
        mem = compiled.memory_analysis()
        mem_info = {
            "argument_size_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_size_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_size_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_size_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # pragma: no cover - backend specific
        mem_info = {"error": str(e)}

    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
        xla_flops = float(cost.get("flops", 0.0))
        xla_bytes = float(cost.get("bytes accessed", 0.0))
    except Exception as e:  # pragma: no cover
        xla_flops, xla_bytes = 0.0, 0.0

    text = compiled.as_text()
    coll = collective_bytes(text)
    # trip-count-aware per-device FLOPs/bytes (XLA's cost_analysis counts
    # while bodies once — see roofline/hlo_cost.py)
    hlo = hlo_analyze(text)
    flops = hlo["flops"]
    bytes_accessed = hlo["bytes"]

    rf = Roofline(
        flops_per_device=flops,
        hbm_bytes_per_device=bytes_accessed,
        collective_bytes_per_device=float(coll.get("total", 0)),
        model_flops_global=model_flops(cfg, shape),
        n_chips=n_chips,
    )
    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_kind,
        "tag": tag,
        "status": "ok",
        "n_chips": n_chips,
        "opts": {"zero": zero, "ep": ep, "fsdp": fsdp,
                 "microbatches": microbatches,
                 "remat": remat, "moment_dtype": moment_dtype},
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": mem_info,
        "collectives": coll,
        "xla_cost": {"flops": xla_flops, "bytes_accessed": xla_bytes},
        "roofline": rf.as_dict(),
        "param_count": cfg.param_count(),
        "active_param_count": cfg.active_param_count(),
    }
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--out", default="reports")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--zero", action="store_true")
    ap.add_argument("--ep", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--moment-dtype", default="float32")
    ap.add_argument("--moe-global-routing", action="store_true",
                    help="pre-optimization global-capacity dispatch")
    ap.add_argument("--sharded-decode", action="store_true",
                    help="shard_map flash-decode with seq-sharded KV")
    ap.add_argument("--ssm-scan-dtype", default="float32")
    ap.add_argument("--fsdp", action="store_true",
                    help="ZeRO-3-style param sharding over the data axis")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    name = f"{args.arch}.{args.shape}.{args.mesh}.{args.tag}.json"
    try:
        result = run_cell(
            args.arch, args.shape, args.mesh, zero=args.zero, ep=args.ep,
            microbatches=args.microbatches, remat=not args.no_remat,
            moment_dtype=args.moment_dtype,
            moe_global_routing=args.moe_global_routing,
            sharded_decode=args.sharded_decode,
            ssm_scan_dtype=args.ssm_scan_dtype, fsdp=args.fsdp,
            tag=args.tag)
    except Exception as e:
        result = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
                  "tag": args.tag, "status": "error", "error": str(e),
                  "traceback": traceback.format_exc()}
    with open(os.path.join(args.out, name), "w") as f:
        json.dump(result, f, indent=2)
    status = result["status"]
    extra = ""
    if status == "ok":
        r = result["roofline"]
        extra = (f" bound={r['bound']} tc={r['t_compute_s']:.4f}s "
                 f"tm={r['t_memory_s']:.4f}s tx={r['t_collective_s']:.4f}s "
                 f"rf={r['roofline_fraction']:.3f}")
    print(f"[{status}] {args.arch} {args.shape} {args.mesh} {args.tag}{extra}")
    if status != "ok":
        print(result.get("error"))
        raise SystemExit(1)


if __name__ == "__main__":
    main()
