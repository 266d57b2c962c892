"""Compile each cell's programs at their real sizes for a described TPU
v5e, without a chip, and print ``memory_analysis()`` per device.

    JAX_PLATFORMS=cpu python3 chipbench/compile_only.py [workload ...]

A train cell compiles its train step exactly as ``modes/train.py`` builds
it (the searched plan's microbatches and recompute, the FSDP mesh over the
cell's chips, taken from the described ``v5e:2x2`` host); a serve cell
compiles the engine's prefill and decode step at the mix's batch and
``max_len``. Nothing runs: this says whether the programs fit, not how fast
they are.
"""
from __future__ import annotations

import functools
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core.arch import ModelArch  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.train import pick_strategy  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.parallel.sharding import batch_spec, make_plan, param_specs  # noqa: E402
from repro.train.optimizer import OptState, adamw_init  # noqa: E402
from repro.train.train_step import TrainStepCfg, make_train_step  # noqa: E402

from chipbench.modes.serve import answer_lengths  # noqa: E402
from chipbench.run import cell_files  # noqa: E402


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes", "temp_size_in_bytes",
            "alias_size_in_bytes", "generated_code_size_in_bytes")
    return {k: int(getattr(m, k)) for k in keys}


def _structs(tree, shardings):
    return jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh), tree, shardings)


def train_cell(files, devices) -> dict:
    arch, t = ModelArch(**files["config"]["arch"]), files["traffic"]
    n = len(devices)
    B, S = t["global_batch"], t["seq"]
    best = pick_strategy(arch, n, B, S).best
    micro = max(best.num_microbatches(B), 1)
    mesh = make_mesh((n, 1), ("data", "model"), devices=devices)
    plan = make_plan(mesh, fsdp=True)
    cfg = lm.ModelCfg(dtype=jnp.dtype(files["config"]["dtype"]), attn_impl="xla",
                      ssm_impl="xla", remat=best.recompute_granularity)
    step = make_train_step(arch, cfg, TrainStepCfg(
        num_microbatches=micro, base_lr=t["base_lr"], warmup_steps=t["warmup_steps"],
        total_steps=t["total_steps"], batch_axes=plan.batch_axes))
    struct = jax.eval_shape(functools.partial(lm.init_params, arch, dtype=jnp.float32),
                            jax.random.PRNGKey(0))
    p_sh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s),
                                  param_specs(arch, plan, struct),
                                  is_leaf=lambda x: isinstance(x, P))
    rep = NamedSharding(mesh, P())
    opt_sh = OptState(mu=p_sh, nu=p_sh, step=rep)
    params = _structs(struct, p_sh)
    opt = _structs(jax.eval_shape(adamw_init, struct), opt_sh)
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    b_sh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), batch_spec(plan, batch),
                                  is_leaf=lambda x: isinstance(x, P))
    batch = _structs(batch, b_sh)
    with jax.set_mesh(mesh):
        compiled = jax.jit(step, out_shardings=(p_sh, opt_sh, rep),
                           donate_argnums=(0, 1)).lower(params, opt, batch).compile()
    return {"plan": f"tp={best.tensor_parallel} dp={best.data_parallel} "
                    f"mbs={best.micro_batch_size} micro={micro} "
                    f"remat={best.recompute_granularity}",
            "train_step": _mem(compiled)}


def serve_cell(files, devices) -> dict:
    arch, t = ModelArch(**files["config"]["arch"]), files["traffic"]
    dev = jax.sharding.SingleDeviceSharding(devices[0])
    cfg = lm.ModelCfg(dtype=jnp.dtype(files["config"]["dtype"]), attn_impl="xla", ssm_impl="xla")
    B, Pl = t["batch"], t["prompt_len"]
    max_len = Pl + max(answer_lengths(t))
    struct = jax.eval_shape(functools.partial(lm.init_params, arch, dtype=cfg.dtype),
                            jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=dev), struct)
    caches = jax.eval_shape(lambda: lm.init_caches(arch, cfg, B, max_len))
    caches = jax.tree_util.tree_map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=dev), caches)
    out = {}
    pre = jax.jit(functools.partial(lm.prefill, arch=arch, cfg=cfg)).lower(
        params, caches=caches, tokens=jax.ShapeDtypeStruct((B, Pl), jnp.int32, sharding=dev)).compile()
    out["prefill"] = _mem(pre)
    dec = jax.jit(functools.partial(lm.decode_step, arch=arch, cfg=cfg)).lower(
        params, caches=caches, tokens=jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=dev),
        position=jax.ShapeDtypeStruct((), jnp.int32, sharding=dev)).compile()
    out["decode_step"] = _mem(dec)
    return out


def main(argv=None) -> int:
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    names = (argv if argv is not None else sys.argv[1:]) or [w["name"] for w in bench["workloads"]]
    for name in names:
        files = cell_files(name)
        devices = list(topo.devices)[:files["workload"]["chips"]]
        fn = train_cell if files["traffic"]["mode"] == "train" else serve_cell
        print(json.dumps({"workload": name, **fn(files, devices)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
