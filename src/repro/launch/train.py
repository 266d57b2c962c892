"""Training driver: Astra-searched strategy -> mesh -> jit train loop.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --reduced \\
        --steps 50 --batch 32 --seq 256 --auto-strategy

On a CPU host it runs reduced configs; on a TPU host the same entry point
runs the full configs (the FSDP data mesh spans every device). The
--auto-strategy flag runs the paper's mode-1 search for the configured
cluster and applies the winning strategy's executable knobs (microbatching,
recompute granularity, distributed optimizer) — the integration point
between the paper's contribution and this framework. The search runs
serially in this process: it starts no worker processes, which could not
share the chip this process holds.
"""
from __future__ import annotations

import argparse
import functools
import json
import statistics
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.calibration.fit import load_or_train
from repro.calibration.traces import StepTrace, append_trace
from repro.core.params import ParallelStrategy
from repro.checkpoint import CheckpointManager
from repro.configs import PAPER_MODELS, get_arch, get_reduced
from repro.core import Astra, FixedPool, Limits, SearchSpec, Workload
from repro.core.arch import ModelArch
from repro.data import MarkovCorpus, SyntheticPipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models.lm import ModelCfg, init_params
from repro.parallel.sharding import batch_spec, make_plan, param_specs
from repro.serve.search_service import SearchService
from repro.train.optimizer import OptState, adamw_init
from repro.train.train_step import TrainStepCfg, make_train_step


def pick_strategy(arch, num_devices: int, global_batch: int, seq: int):
    """Run the paper's mode-1 search for this cluster (v5e chips) and return
    its :class:`SearchReport` (``.best`` is the winner).

    Goes through the spec-keyed :class:`SearchService`, so the report
    arrives via the wire format — exactly what a shared fleet service would
    answer. (The service cache is per-process; pointing this at a remote
    service, once one is deployed, is what makes repeated launches hit a
    shared cache.) A failure to load or train the eta model fails the call:
    a search priced by another model would pick another plan.

    The search is serial (``workers=1``): callers hold the chip, and a
    worker process forked from them could not use it. In a profile it is the
    ``search`` span, the eta model's loading or training its
    ``search.eta_model`` child."""
    with jax.profiler.TraceAnnotation("search"):
        with jax.profiler.TraceAnnotation("search.eta_model"):
            eta, _ = load_or_train()
        service = SearchService(Astra(eta))
        return service.search(SearchSpec(
            arch=arch,
            pool=FixedPool("tpu-v5e", max(num_devices, 1)),
            workload=Workload(global_batch, seq),
            limits=Limits(workers=1),
        ))


def _memory_in_use(devices) -> list:
    """Per-device bytes in use (None where the backend keeps no stats)."""
    return [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]


def main(argv=None, *, arch: Optional[ModelArch] = None,
         devices: Optional[Sequence[jax.Device]] = None) -> dict:
    """Train; returns the run's numbers (losses, compile and step seconds).

    ``arch`` trains that architecture instead of ``--arch`` (a caller's
    cut-depth config); ``devices`` restricts the data mesh to those devices
    (default: every device)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-test-sized config of the family")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="none", choices=("none", "selective", "full"))
    ap.add_argument("--auto-strategy", action="store_true")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--emit-traces", default=None, metavar="PATH",
                    help="append one measured StepTrace (JSONL, wire format) "
                         "per run — feed it to a calibration-enabled search "
                         "service via 'python -m repro.serve.search_service "
                         "traces' or CalibrationLoop.ingest")
    args = ap.parse_args(argv)

    if arch is None:
        arch = get_reduced(args.arch) if args.reduced and args.arch not in PAPER_MODELS \
            else get_arch(args.arch)

    enable_compile_cache()
    devices = list(devices) if devices is not None else jax.devices()
    n_dev = len(devices)
    # data x model mesh over the devices (1x1 on one chip or a CPU host)
    model_par = 1
    mesh = make_mesh((n_dev // model_par, model_par), ("data", "model"),
                     devices=devices)
    plan = make_plan(mesh, fsdp=True)

    remat, micro = args.remat, args.microbatches
    searched = None  # the auto-strategy winner, reused for trace attribution
    predicted = None
    if args.auto_strategy:
        report = pick_strategy(arch, n_dev, args.batch, args.seq)
        s = searched = report.best
        if s is not None:
            remat = s.recompute_granularity
            # num_microbatches is already per-DP-rank (GB / (dp * mbs)); the
            # train step splits the *global* batch K ways, so K is exactly it
            micro = max(s.num_microbatches(args.batch), 1)
            predicted = report.best_sim.step_time
            print(f"[astra] strategy: tp={s.tensor_parallel} pp={s.pipeline_parallel} "
                  f"dp={s.data_parallel} mbs={s.micro_batch_size} remat={remat} "
                  f"dist_opt={s.use_distributed_optimizer} "
                  f"predicted_step={predicted:.4f}s")

    cfg = ModelCfg(dtype=getattr(jnp, args.dtype), attn_impl="xla",
                   ssm_impl="xla", remat=remat)
    step_cfg = TrainStepCfg(
        num_microbatches=micro, base_lr=args.lr, warmup_steps=10,
        total_steps=args.steps, batch_axes=plan.batch_axes,
    )
    train_step = make_train_step(arch, cfg, step_cfg)

    # params and optimizer state are created in place, each device holding
    # only its FSDP shard
    init = functools.partial(init_params, arch, dtype=jnp.float32)
    key = jax.random.PRNGKey(0)
    p_spec = param_specs(arch, plan, jax.eval_shape(init, key))
    p_sh = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), p_spec,
        is_leaf=lambda x: isinstance(x, P),
    )
    replicated = NamedSharding(mesh, P())
    opt_sh = OptState(mu=p_sh, nu=p_sh, step=replicated)
    params = jax.jit(init, out_shardings=p_sh)(key)
    opt = jax.jit(adamw_init, out_shardings=opt_sh)(params)

    corpus = MarkovCorpus(arch.vocab, seed=0)
    pipe = SyntheticPipeline(corpus=corpus, global_batch=args.batch, seq_len=args.seq)

    def next_batch(step: int) -> dict:
        batch = pipe.next_batch()
        if arch.family == "encdec":
            batch["enc_features"] = jax.random.normal(
                jax.random.PRNGKey(step), (args.batch, arch.encoder_seq, arch.hidden)
            ).astype(cfg.dtype)
        elif arch.frontend_stub and arch.frontend_seq:
            batch["frontend"] = jax.random.normal(
                jax.random.PRNGKey(step), (args.batch, arch.frontend_seq, arch.hidden)
            ).astype(cfg.dtype)
        b_sh = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), batch_spec(plan, batch),
            is_leaf=lambda x: isinstance(x, P),
        )
        return jax.tree_util.tree_map(jax.device_put, batch, b_sh)

    ckpt = CheckpointManager(args.checkpoint_dir) if args.checkpoint_dir else None
    start_step = 0
    if ckpt and args.resume and ckpt.latest_step() is not None:
        state, meta = ckpt.restore({"params": params, "opt": opt})
        params, opt = state["params"], state["opt"]
        pipe.load_state_dict({"step": meta["data_step"]})
        start_step = meta["step"]
        print(f"[ckpt] resumed from step {start_step}")

    losses = []
    step_times: list[float] = []
    t0 = time.time()
    with jax.set_mesh(mesh):
        batch = next_batch(start_step)
        t_compile = time.perf_counter()
        compiled = jax.jit(
            train_step, out_shardings=(p_sh, opt_sh, replicated),
            donate_argnums=(0, 1),
        ).lower(params, opt, batch).compile()
        compile_s = time.perf_counter() - t_compile
        print(f"[compile] train step {compile_s:.2f}s")
        for step in range(start_step, args.steps):
            # one step of TensorBoard's step view when the run is profiled
            with jax.profiler.StepTraceAnnotation("train", step_num=step):
                if step > start_step:
                    batch = next_batch(step)
                t_step = time.perf_counter()
                params, opt, metrics = compiled(params, opt, batch)
                loss = float(metrics["loss"])  # blocks on the device computation
                step_times.append(time.perf_counter() - t_step)
                losses.append(loss)
                if step % args.log_every == 0 or step == args.steps - 1:
                    print(f"step {step:5d} loss {loss:.4f} "
                          f"gnorm {float(metrics.get('grad_norm', 0)):.3f} "
                          f"({(time.time()-t0):.1f}s)")
                if ckpt and (step + 1) % args.checkpoint_every == 0:
                    ckpt.save(step + 1, {"params": params, "opt": opt},
                              metadata={"data_step": pipe.step, "arch": arch.name})
    memory_in_use = _memory_in_use(devices)  # while params and opt are live
    if ckpt:
        ckpt.wait()
    if args.emit_traces and step_times:
        # attribute the measurement to the searched strategy when there is
        # one; otherwise describe the mesh this run actually used (pure
        # data-parallel over whatever devices exist)
        strategy = searched if searched is not None else ParallelStrategy(
            device="tpu-v5e", num_devices=max(n_dev, 1),
            micro_batch_size=max(args.batch // (max(n_dev, 1) * micro), 1),
        )
        trace = StepTrace(
            arch=arch, strategy=strategy,
            global_batch=args.batch, seq=args.seq,
            step_times=tuple(step_times), source="train",
        )
        append_trace(args.emit_traces, trace)
        print(f"[trace] appended {len(step_times)}-step trace "
              f"(median {trace.measured_step_time:.4f}s) to {args.emit_traces}")
    result = {
        "first_loss": losses[0], "last_loss": losses[-1],
        "entropy_floor": corpus.entropy_rate(), "steps": len(losses),
        "losses": losses, "compile_s": compile_s,
        "median_step_s": statistics.median(step_times),
        "predicted_step_s": predicted, "remat": remat, "microbatches": micro,
        "memory_in_use": memory_in_use,
    }
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    main()
