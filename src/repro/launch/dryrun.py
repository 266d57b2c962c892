import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# (test override hook — must still precede any jax import)
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=" + os.environ["REPRO_DRYRUN_DEVICES"]
    )

"""Multi-pod dry-run: prove every (arch x shape x mesh) cell lowers,
compiles, fits, and report its roofline terms.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --pods both

Artifacts land in artifacts/dryrun/<arch>__<shape>__<mesh>.json and feed
benchmarks/roofline.py and EXPERIMENTS.md.
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ASSIGNED, get_arch
from repro.core.arch import ASSIGNED_SHAPES, InputShape, ModelArch
from repro.launch import roofline as rl
from repro.launch.hlo_account import account
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.launch.specs import decode_specs, prefill_specs, train_batch_specs
from repro.models.lm import ModelCfg, decode_step, forward_cached, init_params, prefill
from repro.parallel.sharding import batch_spec, cache_specs, make_plan, param_specs
from repro.train.optimizer import adamw_init
from repro.train.train_step import TrainStepCfg, make_train_step

SHAPES = {s.name: s for s in ASSIGNED_SHAPES}


def _mesh_from_arg(mesh_arg: str | None, multi_pod: bool):
    if mesh_arg:
        dims = tuple(int(x) for x in mesh_arg.split("x"))
        axes = ("pod", "data", "model")[-len(dims):]
        return make_mesh(dims, axes)
    return make_production_mesh(multi_pod=multi_pod)


def _named(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def cell_applicable(arch: ModelArch, shape: InputShape) -> tuple[bool, str]:
    if shape.name == "long_500k" and not arch.supports_long_context:
        return False, "full-attention arch: 500k dense decode skipped (DESIGN.md §4)"
    return True, ""


def lower_cell(
    arch: ModelArch,
    shape: InputShape,
    mesh,
    *,
    remat: str = "full",
    fsdp: bool = True,
    microbatch_rows: int = 1,
    donate: bool = True,
    opts: frozenset = frozenset(),
) -> dict:
    """Lower + compile one cell; return the roofline/memory report.

    ``opts`` selects §Perf hillclimb optimizations: "pre_cast" (H1),
    "dense_decode" (D1), "act_shard" (H2). Empty = paper-faithful baseline.
    """
    plan = make_plan(mesh, fsdp=fsdp)
    act_shard = None
    if "act_shard" in opts:
        act_shard = {"batch": plan.batch_axes, "model": plan.model_axis}
    kv_repeat = 1
    if "kv_repeat" in opts and not arch.is_attention_free and arch.kv_heads:
        tp = plan.axis_size(plan.model_axis)
        if arch.kv_heads % tp != 0:
            # smallest replication making the head dim tp-divisible
            r = 1
            while (arch.kv_heads * r) % tp != 0 and arch.kv_heads * r < arch.heads:
                r += 1
            kv_repeat = r if (arch.kv_heads * r) % tp == 0 else 1
    cfg = ModelCfg(dtype=jnp.bfloat16, attn_impl="xla", ssm_impl="xla",
                   remat=remat,
                   decode_dense_attn="dense_decode" in opts,
                   kv_cache_repeat=kv_repeat,
                   kv_scatter_write="kv_scatter" in opts,
                   kv_cache_quant="kv_quant" in opts,
                   act_shard=act_shard)
    report: dict = {
        "arch": arch.name, "shape": shape.name,
        "mesh": "x".join(str(mesh.shape[a]) for a in mesh.axis_names),
        "axes": list(mesh.axis_names), "remat": remat, "fsdp": fsdp,
        "opts": sorted(opts),
    }
    t0 = time.perf_counter()

    if shape.kind == "train":
        params_dtype = jnp.float32
        p_struct = jax.eval_shape(
            lambda: init_params(arch, jax.random.PRNGKey(0), dtype=params_dtype)
        )
        p_spec = param_specs(arch, plan, p_struct)
        o_struct = jax.eval_shape(adamw_init, p_struct)
        o_spec = type(o_struct)(mu=p_spec, nu=p_spec, step=P())
        b_struct = train_batch_specs(arch, shape, cfg)
        b_spec = batch_spec(plan, b_struct)

        dp = plan.batch_size_divisor()
        rows_per_replica = max(shape.global_batch // dp, 1)
        K = max(rows_per_replica // microbatch_rows, 1)
        step_cfg = TrainStepCfg(
            num_microbatches=K, batch_axes=plan.batch_axes,
            pre_cast="pre_cast" in opts,
        )
        train_step = make_train_step(arch, cfg, step_cfg)
        jitted = jax.jit(
            train_step,
            in_shardings=(_named(mesh, p_spec), _named(mesh, o_spec),
                          _named(mesh, b_spec)),
            donate_argnums=(0, 1) if donate else (),
        )
        args = (p_struct, o_struct, b_struct)
        report["num_microbatches"] = K
    else:
        params_dtype = jnp.bfloat16
        p_struct = jax.eval_shape(
            lambda: init_params(arch, jax.random.PRNGKey(0), dtype=params_dtype)
        )
        p_spec = param_specs(arch, plan, p_struct)
        if shape.kind == "prefill":
            specs = prefill_specs(arch, shape, cfg)
            c_spec = cache_specs(arch, plan, specs["caches"])
            extra = {
                k: v for k, v in specs.items() if k not in ("tokens", "caches")
            }

            def serve_fn(params, caches, tokens, extra):
                return forward_cached(
                    params, arch, cfg, caches, tokens, 0,
                    frontend=extra.get("frontend"),
                )

            b_sh = batch_spec(plan, {"tokens": specs["tokens"], **extra})
            jitted = jax.jit(
                serve_fn,
                in_shardings=(
                    _named(mesh, p_spec), _named(mesh, c_spec),
                    _named(mesh, b_sh["tokens"]),
                    _named(mesh, {k: b_sh[k] for k in extra}),
                ),
                donate_argnums=(1,) if donate else (),
            )
            args = (p_struct, specs["caches"], specs["tokens"], extra)
        else:  # decode
            specs = decode_specs(arch, shape, cfg)
            c_spec = cache_specs(arch, plan, specs["caches"])

            def serve_fn(params, caches, tokens, position):
                return decode_step(params, arch, cfg, caches, tokens, position)

            tok_sh = batch_spec(plan, {"tokens": specs["tokens"]})["tokens"]
            jitted = jax.jit(
                serve_fn,
                in_shardings=(
                    _named(mesh, p_spec), _named(mesh, c_spec),
                    _named(mesh, tok_sh), NamedSharding(mesh, P()),
                ),
                donate_argnums=(1,) if donate else (),
            )
            args = (p_struct, specs["caches"], specs["tokens"], specs["position"])

    with jax.set_mesh(mesh):
        lowered = jitted.lower(*args)
        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0
    report["lower_s"] = round(t_lower, 2)
    report["compile_s"] = round(t_compile, 2)

    # --- memory ---------------------------------------------------------
    try:
        ma = compiled.memory_analysis()
        report["memory"] = {
            "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
            "output_bytes": getattr(ma, "output_size_in_bytes", None),
            "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(ma, "generated_code_size_in_bytes", None),
        }
        args_b = report["memory"]["argument_bytes"] or 0
        temp_b = report["memory"]["temp_bytes"] or 0
        report["memory"]["per_device_total"] = args_b + temp_b
        report["memory"]["fits_v5e_16g"] = bool(args_b + temp_b <= 16e9)
    except Exception as e:  # pragma: no cover
        report["memory"] = {"error": repr(e)}

    # --- cost analysis + collectives -------------------------------------
    # cost_analysis counts scan bodies once (see hlo_account docstring), so
    # the roofline terms come from the call-graph accountant; the raw numbers
    # are kept for reference.
    ca = compiled.cost_analysis() or {}
    report["cost_analysis_raw"] = {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
    }
    t0 = time.perf_counter()
    totals = account(compiled.as_text())
    report["account_s"] = round(time.perf_counter() - t0, 2)
    chips = int(len(mesh.devices.flat))
    rep = rl.RooflineReport(
        flops=totals.flops, hbm_bytes=totals.bytes,
        wire_bytes=totals.wire_bytes, chips=chips,
        model_flops_total=rl.model_flops(arch, shape),
    )
    report["collectives"] = {
        "counts": totals.collective_counts,
        "result_bytes": totals.collective_bytes,
        "wire_bytes": totals.wire_bytes,
    }
    report["roofline"] = rep.to_dict()
    report["ok"] = True
    return report


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ASSIGNED), default=None)
    ap.add_argument("--shape", choices=list(SHAPES), default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--pods", choices=("single", "multi", "both"), default="single")
    ap.add_argument("--mesh", default=None, help="override, e.g. 4x4 or 2x2x4")
    ap.add_argument("--remat", default="full", choices=("none", "selective", "full"))
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--opt", default="",
                    help="comma list: pre_cast,dense_decode,act_shard")
    ap.add_argument("--tag", default="", help="suffix for artifact filenames")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()

    cells = []
    archs = list(ASSIGNED) if args.all or not args.arch else [args.arch]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]
    pods = {"single": [False], "multi": [True], "both": [False, True]}[args.pods]
    os.makedirs(args.out, exist_ok=True)

    for arch_name in archs:
        arch = get_arch(arch_name)
        for shape_name in shapes:
            shape = SHAPES[shape_name]
            ok, why = cell_applicable(arch, shape)
            if not ok:
                print(f"SKIP {arch_name} x {shape_name}: {why}")
                continue
            for mp in pods:
                cells.append((arch, shape, mp))

    opts = frozenset(x for x in args.opt.split(",") if x)
    n_fail = 0
    for arch, shape, mp in cells:
        mesh = _mesh_from_arg(args.mesh, mp)
        mesh_tag = "x".join(str(mesh.shape[a]) for a in mesh.axis_names)
        tag = f"{arch.name}__{shape.name}__{mesh_tag}"
        if args.tag:
            tag += f"__{args.tag}"
        print(f"=== {tag} ===", flush=True)
        try:
            report = lower_cell(arch, shape, mesh, remat=args.remat,
                                fsdp=not args.no_fsdp, opts=opts)
        except Exception:
            traceback.print_exc()
            report = {"arch": arch.name, "shape": shape.name, "mesh": mesh_tag,
                      "ok": False, "error": traceback.format_exc(limit=3)}
            n_fail += 1
        with open(os.path.join(args.out, tag + ".json"), "w") as f:
            json.dump(report, f, indent=2)
        if report.get("ok"):
            r = report["roofline"]
            m = report.get("memory", {})
            print(
                f"  ok lower={report['lower_s']}s compile={report['compile_s']}s "
                f"flops/chip={r['flops_per_chip']:.3g} "
                f"terms(c/m/coll)={r['compute_s']:.4g}/{r['memory_s']:.4g}/"
                f"{r['collective_s']:.4g}s dominant={r['dominant']} "
                f"mem/device={(m.get('per_device_total') or 0)/1e9:.2f}GB",
                flush=True,
            )
    if n_fail:
        raise SystemExit(f"{n_fail} cells failed")


if __name__ == "__main__":
    main()
