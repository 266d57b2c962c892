"""Multi-device tests, subprocess-isolated (XLA device-count override must
precede jax import, and the main test process keeps 1 device)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, devices: int = 8, timeout: int = 480) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = _SRC
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=timeout,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


@pytest.mark.slow
def test_dp_parity_vs_single_device():
    """dp=4 sharded training step == single-device step, bit-for-bit-ish."""
    out = _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_reduced
        from repro.models import lm
        from repro.train.optimizer import adamw_init
        from repro.train.train_step import TrainStepCfg, make_train_step
        from repro.launch.mesh import make_mesh
        from repro.parallel.sharding import make_plan, param_specs, batch_spec

        arch = get_reduced("yi-6b")
        cfg = lm.ModelCfg(dtype=jnp.float32, attn_impl="xla", ssm_impl="xla")
        step = make_train_step(arch, cfg, TrainStepCfg())
        params = lm.init_params(arch, jax.random.PRNGKey(0))
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, arch.vocab)}
        opt = adamw_init(params)

        # single device
        p1, _, m1 = jax.jit(step)(params, opt, batch)

        # dp=4 x tp=2 mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        plan = make_plan(mesh, fsdp=True)
        pspec = param_specs(arch, plan, jax.eval_shape(lambda: params))
        psh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), pspec,
                                     is_leaf=lambda x: isinstance(x, P))
        bsh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s),
                                     batch_spec(plan, batch),
                                     is_leaf=lambda x: isinstance(x, P))
        params_d = jax.tree_util.tree_map(jax.device_put, params, psh)
        batch_d = jax.tree_util.tree_map(jax.device_put, batch, bsh)
        with jax.set_mesh(mesh):
            p2, _, m2 = jax.jit(step)(params_d, adamw_init(params_d), batch_d)
        err = jax.tree_util.tree_map(
            lambda a, b: float(jnp.abs(a - b).max()), p1, jax.device_get(p2))
        print("LOSS", float(m1["loss"]), float(m2["loss"]))
        print("MAXERR", max(jax.tree_util.tree_leaves(err)))
    """)
    lines = dict(l.split(" ", 1) for l in out.strip().splitlines() if " " in l)
    l1, l2 = (float(x) for x in lines["LOSS"].split())
    assert abs(l1 - l2) < 1e-4
    assert float(lines["MAXERR"]) < 1e-3


@pytest.mark.slow
def test_gpipe_pp_matches_sequential():
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.parallel.pipeline import pipeline_apply, stack_for_stages
        from repro.launch.mesh import make_mesh
        L, d = 8, 32
        w = jax.random.normal(jax.random.PRNGKey(0), (L, d, d)) * 0.1
        def apply_stage(sw, h):
            def body(c, wl): return c + jax.nn.silu(c @ wl), None
            out, _ = jax.lax.scan(body, h, sw)
            return out
        def ref(w, x):
            def body(c, wl): return c + jax.nn.silu(c @ wl), None
            out, _ = jax.lax.scan(body, x.reshape(-1, d), w)
            return out.reshape(x.shape)
        mesh = make_mesh((4,), ("stage",))
        x = jax.random.normal(jax.random.PRNGKey(1), (6, 3, d))
        y = pipeline_apply(mesh, apply_stage, stack_for_stages(w, 4), x)
        print("FWD", float(jnp.abs(y - ref(w, x)).max()))
        gp = jax.grad(lambda w: (pipeline_apply(mesh, apply_stage, stack_for_stages(w, 4), x) ** 2).sum())(w)
        gr = jax.grad(lambda w: (ref(w, x) ** 2).sum())(w)
        print("GRAD", float(jnp.abs(gp - gr).max() / jnp.abs(gr).max()))
    """, devices=4)
    lines = dict(l.split(" ", 1) for l in out.strip().splitlines() if " " in l)
    assert float(lines["FWD"]) < 1e-5
    assert float(lines["GRAD"]) < 1e-5


@pytest.mark.slow
def test_dryrun_reduced_mesh_cell():
    """A full dry-run cell (lower+compile+roofline) on a 2x2x2 pod mesh."""
    env = dict(os.environ)
    env["REPRO_DRYRUN_DEVICES"] = "8"
    env["PYTHONPATH"] = _SRC
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "whisper-tiny", "--shape", "decode_32k",
         "--mesh", "2x2x2", "--out", "/tmp/dryrun_test"],
        capture_output=True, text=True, env=env, timeout=480,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    with open("/tmp/dryrun_test/whisper-tiny__decode_32k__2x2x2.json") as f:
        rep = json.load(f)
    assert rep["ok"]
    assert rep["roofline"]["flops_per_chip"] > 0
    assert rep["roofline"]["dominant"] in ("compute", "memory", "collective")


@pytest.mark.slow
def test_elastic_restart_across_mesh_shapes():
    """Train 2 steps on mesh A, checkpoint, restore onto mesh B, continue —
    loss trajectory must match an uninterrupted run."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_reduced
        from repro.models import lm
        from repro.checkpoint import CheckpointManager
        from repro.train.optimizer import adamw_init
        from repro.train.train_step import TrainStepCfg, make_train_step
        from repro.launch.mesh import make_mesh
        from repro.parallel.sharding import make_plan, param_specs
        import tempfile

        arch = get_reduced("yi-6b")
        cfg = lm.ModelCfg(dtype=jnp.float32, attn_impl="xla", ssm_impl="xla")
        step_fn = make_train_step(arch, cfg, TrainStepCfg(base_lr=1e-3))
        params = lm.init_params(arch, jax.random.PRNGKey(0))
        opt = adamw_init(params)
        batches = [{"tokens": jax.random.randint(jax.random.PRNGKey(i), (8, 32), 0, arch.vocab)}
                   for i in range(4)]

        # uninterrupted reference
        p, o = params, opt
        for b in batches:
            p, o, m = jax.jit(step_fn)(p, o, b)
        ref_loss = float(m["loss"])

        # interrupted: 2 steps on (8,1), save, restore onto (2,4), 2 more
        mesh_a = make_mesh((8, 1), ("data", "model"))
        with jax.set_mesh(mesh_a):
            p, o = params, opt
            for b in batches[:2]:
                p, o, m = jax.jit(step_fn)(p, o, b)
        tmp = tempfile.mkdtemp()
        mgr = CheckpointManager(tmp)
        mgr.save(2, {"params": p, "opt": o}, blocking=True)

        mesh_b = make_mesh((2, 4), ("data", "model"))
        plan = make_plan(mesh_b, fsdp=True)
        pspec = param_specs(arch, plan, jax.eval_shape(lambda: params))
        psh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh_b, s), pspec,
                                     is_leaf=lambda x: isinstance(x, P))
        state, meta = mgr.restore({"params": params, "opt": opt},
                                  shardings={"params": psh})
        p, o = state["params"], state["opt"]
        with jax.set_mesh(mesh_b):
            for b in batches[2:]:
                p, o, m = jax.jit(step_fn)(p, o, b)
        print("REF", ref_loss)
        print("ELASTIC", float(m["loss"]))
    """)
    lines = dict(l.split(" ", 1) for l in out.strip().splitlines() if " " in l)
    assert abs(float(lines["REF"]) - float(lines["ELASTIC"])) < 1e-4
