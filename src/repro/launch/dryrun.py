import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, record memory/cost/collective artifacts.

This is how the distribution config is proven coherent without hardware:
``.lower().compile()`` must succeed for all 40 cells on the 16x16 pod mesh
and the 2x16x16 multi-pod mesh; ``memory_analysis()`` proves per-device
fit and ``cost_analysis()`` + HLO collective parsing feed §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh pod|multipod]
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2_27b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --leap   # migration programs

Artifacts: artifacts/dryrun/<mesh>/<arch>__<shape>.json (idempotent; --force
recompiles).  The roofline report generator reads only these files.
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import shapes as shp
from repro.configs.base import ARCH_IDS, ModelConfig, canon, get_config
from repro.distributed.sharding import (
    make_ctx,
    param_shardings,
    sanitize_spec,
    use_ctx,
)
from repro.launch.mesh import make_production_mesh
from repro.models import lm
from repro.roofline import flops as fl
from repro.roofline import hlo as hlo_mod
from repro.roofline import model as roof
from repro.train.optimizer import OptimizerConfig
from repro.train.train_step import TrainConfig, init_train_state, train_step

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "artifacts", "dryrun")
ART_DIR = os.path.abspath(os.environ.get("DRYRUN_ART_DIR", ART_DIR))


# ---------------------------------------------------------------------------
# Cell construction
# ---------------------------------------------------------------------------


def _dp_total(mesh) -> int:
    n = mesh.shape["data"]
    if "pod" in mesh.axis_names:
        n *= mesh.shape["pod"]
    return n


def _with_moe_groups(
    cfg: ModelConfig, tokens_per_step: int, dp: int, mode: str = "weights"
) -> ModelConfig:
    if cfg.moe is None:
        return cfg
    groups = max(dp, tokens_per_step // 512)
    return dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, groups=groups, dispatch_mode=mode)
    )


def _batch_sharding(cfg, mesh, ctx, struct: dict) -> dict:
    out = {}
    for k, v in struct.items():
        spec = P(ctx.dp, *([None] * (v.ndim - 1)))
        out[k] = NamedSharding(mesh, sanitize_spec(spec, v.shape, mesh))
    return out


def _cache_shardings(cache_struct, cfg, mesh, ctx, *, long: bool):
    seq_axes = tuple(mesh.axis_names) if long else ctx.tp

    def rule(path, leaf):
        names = [getattr(p, "key", None) for p in path]
        name = names[-1]
        stacked = "period" in names
        base = leaf.ndim - (1 if stacked else 0)
        dp = ctx.dp
        if name in ("k", "v") and base == 4:
            spec = (dp, seq_axes, None, None)
        elif name == "conv" and base == 3:
            spec = (dp, None, ctx.tp)
        elif name == "c" and base == 4:  # mlstm matrix memory
            spec = (dp, None, ctx.tp, None)
        elif name == "n" and base == 3:
            spec = (dp, None, ctx.tp)
        elif name in ("h", "c", "n", "m") and base == 2:
            spec = (dp, ctx.tp)
        elif name == "m" and base == 2:
            spec = (dp, None)
        else:
            spec = tuple([None] * base)
        if stacked:
            spec = (None,) + spec
        return NamedSharding(mesh, sanitize_spec(P(*spec), leaf.shape, mesh))

    return jax.tree_util.tree_map_with_path(rule, cache_struct)


def build_cell(cfg: ModelConfig, shape: str, mesh, ctx):
    """Returns (jitted_fn, arg_structs, in_shardings, donate, model_flops)."""
    sp = shp.SHAPES[shape]
    dp = _dp_total(mesh)
    n_active = cfg.active_param_count()

    if sp.kind == "train":
        n_micro = max(1, sp.global_batch // (dp * cfg.microbatch_per_device))
        tokens_per_micro = (sp.global_batch // n_micro) * sp.seq_len
        cfg = _with_moe_groups(cfg, tokens_per_micro, dp)
        tcfg = TrainConfig(
            n_micro=n_micro,
            accum_dtype=cfg.grad_accum_dtype,
            optimizer=OptimizerConfig(state_dtype=cfg.opt_state_dtype),
        )
        state_struct = jax.eval_shape(
            lambda: init_train_state(jax.random.key(0), cfg, tcfg)
        )
        batch_struct = shp.input_specs(cfg, shape)
        params_sh = param_shardings(state_struct.params, mesh, ctx)
        opt_sh = {
            "m": param_shardings(state_struct.opt["m"], mesh, ctx),
            "v": param_shardings(state_struct.opt["v"], mesh, ctx),
            "step": NamedSharding(mesh, P()),
        }
        from repro.train.train_step import TrainState

        state_shardings = TrainState(params=params_sh, opt=opt_sh)
        batch_sh = _batch_sharding(cfg, mesh, ctx, batch_struct)
        fn = jax.jit(
            lambda s, b: train_step(s, b, cfg, tcfg),
            in_shardings=(state_shardings, batch_sh),
            donate_argnums=(0,),
        )
        mflops = roof.model_flops(n_active, sp.global_batch * sp.seq_len, "train")
        return fn, (state_struct, batch_struct), mflops, {"n_micro": n_micro}

    params_struct = jax.eval_shape(lambda: lm.init_params(jax.random.key(0), cfg))
    params_sh = param_shardings(params_struct, mesh, ctx)

    if sp.kind == "prefill":
        cfg = _with_moe_groups(cfg, sp.global_batch * sp.seq_len, dp)
        inp = shp.input_specs(cfg, shape)["inputs"]
        inp_sh = NamedSharding(
            mesh, sanitize_spec(P(ctx.dp, *([None] * (inp.ndim - 1))), inp.shape, mesh)
        )
        fn = jax.jit(
            lambda p, t: lm.prefill(p, t, cfg, sp.seq_len),
            in_shardings=(params_sh, inp_sh),
        )
        mflops = roof.model_flops(n_active, sp.global_batch * sp.seq_len, "prefill")
        return fn, (params_struct, inp), mflops, {}

    if sp.kind == "decode":
        cfg = _with_moe_groups(cfg, sp.global_batch, dp, mode="tokens")
        # 1D inference layout (weights data-replicated, batch data-parallel)
        # when the dense weights fit; otherwise the 2D flat-TP decode layout
        # (weights sharded over every axis, batch replicated) — a dense 340B
        # at tp=16 would otherwise put 42.5 GB of weights on every chip.
        from repro.distributed.sharding import _EXPERT_LEAVES, make_decode_2d_ctx

        dense_bytes = sum(
            leaf.size * leaf.dtype.itemsize
            for path, leaf in jax.tree_util.tree_flatten_with_path(params_struct)[0]
            if getattr(path[-1], "key", None) not in _EXPERT_LEAVES
        )
        tp = mesh.shape.get("model", 1)
        if dense_bytes / tp > 10 * 2**30:
            ctx = make_decode_2d_ctx(mesh)
        params_sh = param_shardings(params_struct, mesh, ctx, inference=True)
        specs = shp.input_specs(cfg, shape)
        cache_struct = jax.eval_shape(
            lambda: lm.init_cache(cfg, sp.global_batch, sp.seq_len)
        )
        cache_sh = _cache_shardings(
            cache_struct, cfg, mesh, ctx, long=(shape == "long_500k")
        )
        inp = specs["inputs"]
        inp_sh = NamedSharding(
            mesh, sanitize_spec(P(ctx.dp, *([None] * (inp.ndim - 1))), inp.shape, mesh)
        )
        fn = jax.jit(
            lambda p, c, t, pos: lm.decode_step(p, c, t, pos, cfg),
            in_shardings=(params_sh, cache_sh, inp_sh, NamedSharding(mesh, P())),
            donate_argnums=(1,),
        )
        mflops = roof.model_flops(n_active, sp.global_batch, "decode")
        # the (possibly 2D-flat-TP) ctx must be active while tracing so the
        # model's internal constraints resolve against it
        return fn, (params_struct, cache_struct, inp, specs["pos"]), mflops, {"ctx": ctx}

    raise ValueError(sp.kind)


# ---------------------------------------------------------------------------
# Leap migration programs on the production mesh
# ---------------------------------------------------------------------------


def build_leap_cell(mesh, ctx, backend: str):
    """Lower the migration copy program for a KV-page pool on the mesh.

    Pool: one region per data-axis row; payload sized like a gemma2 KV page
    (64 tokens x 46 layers).  The ppermute backend must emit exactly one
    collective-permute of the area bytes; the xla backend shows what GSPMD
    does with the naive indexed formulation (the paper's Fig. 4 overhead
    comparison, in collective-bytes form).
    """
    from repro.core import PoolConfig, LeapState
    from repro.core import migrator

    n_regions = mesh.shape["data"]
    payload = (46, 2, 64, 16, 128)  # layers, k/v, tokens, kv_heads, head_dim
    slots = 64
    n_blocks = n_regions * slots // 2
    pool_cfg = PoolConfig(n_regions, slots, payload, jnp.bfloat16, region_axis="data")
    pool_sd = jax.ShapeDtypeStruct(
        (n_regions, slots) + payload, jnp.bfloat16
    )
    state_struct = LeapState(
        pool=pool_sd,
        table=jax.ShapeDtypeStruct((n_blocks, 2), jnp.int32),
        dirty=jax.ShapeDtypeStruct((n_blocks,), jnp.bool_),
        in_flight=jax.ShapeDtypeStruct((n_blocks,), jnp.bool_),
    )
    rep = NamedSharding(mesh, P())
    state_sh = LeapState(
        pool=NamedSharding(mesh, P("data")),
        table=rep,
        dirty=rep,
        in_flight=rep,
    )
    # source and destination slots: per region for ppermute, flat
    # (region * slots + slot) for the xla copy
    src_sd = jax.ShapeDtypeStruct((16,), jnp.int32)
    dst_sd = jax.ShapeDtypeStruct((16,), jnp.int32)
    if backend == "ppermute":
        fn = jax.jit(
            lambda s, a, b: migrator.fused_copy_ppermute(
                s, a, b, 0, 1, "data", mesh
            ),
            in_shardings=(state_sh, rep, rep),
            donate_argnums=(0,),
        )
    else:
        fn = jax.jit(
            lambda s, a, b: migrator.fused_copy(s, a, b),
            in_shardings=(state_sh, rep, rep),
            donate_argnums=(0,),
        )
    area_bytes = 16 * int(np.prod(payload)) * 2
    return fn, (state_struct, src_sd, dst_sd), float(area_bytes), {}


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------


def run_cell(arch: str, shape: str, mesh_name: str, force: bool = False) -> dict:
    os.makedirs(os.path.join(ART_DIR, mesh_name), exist_ok=True)
    out_path = os.path.join(ART_DIR, mesh_name, f"{arch}__{shape}.json")
    if os.path.exists(out_path) and not force:
        with open(out_path) as f:
            return json.load(f)

    multi_pod = mesh_name == "multipod"
    mesh = make_production_mesh(multi_pod=multi_pod)
    ctx = make_ctx(mesh)
    art = {
        "arch": arch,
        "shape": shape,
        "mesh": mesh_name,
        "n_chips": int(np.prod(list(mesh.shape.values()))),
    }

    if arch == "leap_migration":
        builder = lambda: build_leap_cell(mesh, ctx, backend=shape)
    else:
        cfg = get_config(arch)
        status = shp.cell_status(cfg, shape)
        if status:
            art["status"] = status
            with open(out_path, "w") as f:
                json.dump(art, f, indent=2)
            return art
        builder = lambda: build_cell(cfg, shape, mesh, ctx)

    try:
        with use_ctx(ctx), jax.set_mesh(mesh):
            fn, args, mflops, extra = builder()
            cell_ctx = extra.pop("ctx", ctx)
            t0 = time.time()
            with use_ctx(cell_ctx):
                lowered = fn.lower(*args)
            art["lower_s"] = round(time.time() - t0, 2)
            t0 = time.time()
            compiled = lowered.compile()
            art["compile_s"] = round(time.time() - t0, 2)

            ma = compiled.memory_analysis()
            art["memory"] = {
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
                "alias_bytes": int(ma.alias_size_in_bytes),
                "code_bytes": int(ma.generated_code_size_in_bytes),
            }
            art["memory"]["per_device_total"] = (
                art["memory"]["argument_bytes"]
                + art["memory"]["output_bytes"]
                + art["memory"]["temp_bytes"]
                - art["memory"]["alias_bytes"]
            )
            ca = compiled.cost_analysis() or {}
            # NOTE: cost_analysis counts while bodies once (no trip scaling);
            # recorded for reference, not used for the roofline terms.
            art["cost_analysis_raw"] = {
                k: float(v)
                for k, v in ca.items()
                if isinstance(v, (int, float)) and k in ("flops", "bytes accessed")
            }
            txt = compiled.as_text()
            coll = hlo_mod.summarize(hlo_mod.parse_collectives(txt))
            art["collectives_raw"] = coll
            scaled = hlo_mod.scaled_wire_bytes(txt)
            art["collectives_scaled"] = {
                "wire_bytes": scaled["wire_bytes_scaled"],
                "by_kind": scaled["by_kind_scaled"],
                "top_ops": scaled["top_ops"],
            }
            n_chips = art["n_chips"]
            if arch == "leap_migration":
                art["flops_per_device"] = 0.0
                art["bytes_per_device"] = 2.0 * mflops / mesh.shape["data"]
                art["model_flops"] = 0.0
                art["area_bytes"] = float(mflops)
            else:
                acct = fl.step_cost(get_config(arch), shape, n_chips)
                art["flops_per_device"] = acct.total_flops / n_chips
                art["bytes_per_device"] = acct.hbm_bytes / n_chips
                art["hbm_detail"] = acct.detail
                art["model_flops"] = float(mflops)
            art["wire_bytes_per_device"] = float(scaled["wire_bytes_scaled"])
            art.update(extra)
            terms = roof.terms_from_artifact(art)
            art["roofline"] = {
                "compute_s": terms.compute_s,
                "memory_s": terms.memory_s,
                "collective_s": terms.collective_s,
                "dominant": terms.dominant,
                "useful_flops_ratio": terms.useful_flops_ratio,
                "roofline_fraction": terms.roofline_fraction,
            }
            art["status"] = "OK"
    except Exception as e:  # record failures; the suite treats them as bugs
        art["status"] = f"FAIL: {type(e).__name__}: {e}"
        art["traceback"] = traceback.format_exc()[-4000:]

    with open(out_path, "w") as f:
        json.dump(art, f, indent=2)
    return art


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None)
    ap.add_argument("--mesh", type=str, default=None, choices=["pod", "multipod"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--leap", action="store_true", help="migration-program cells")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    meshes = [args.mesh] if args.mesh else ["pod", "multipod"]
    if args.leap:
        cells = [("leap_migration", b) for b in ("xla", "ppermute")]
    elif args.all or args.arch is None:
        cells = [(a, s) for a in ARCH_IDS for s in shp.SHAPES]
    else:
        shapes = [args.shape] if args.shape else list(shp.SHAPES)
        cells = [(canon(args.arch), s) for s in shapes]

    failures = 0
    for mesh_name in meshes:
        for arch, shape in cells:
            t0 = time.time()
            art = run_cell(arch, shape, mesh_name, force=args.force)
            status = art.get("status", "?")
            dom = art.get("roofline", {}).get("dominant", "-")
            print(
                f"[{mesh_name:8s}] {arch:24s} {shape:12s} {status[:60]:60s} "
                f"dom={dom:10s} ({time.time() - t0:.1f}s)",
                flush=True,
            )
            if status.startswith("FAIL"):
                failures += 1
    print(f"done; {failures} failures")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
