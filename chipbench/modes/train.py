"""Train mode: the searched plan's train step, driven as ``launch/train.py
main`` drives it, for a window of seconds.

Set-up, in main's order: ``pick_strategy`` (the mode-1 search) for the
cell's chips, global batch and sequence; the plan's microbatches and
recompute; the FSDP data mesh over the cell's chips from ``make_mesh`` and
``make_plan``; float32 master weights made in place on the device (from the
seed, by ``weights.py``, in the program's layout and shardings) and AdamW
state by the program's ``adamw_init``; ``make_train_step`` compiled once,
with the parameters and state donated; batches from ``SyntheticPipeline``
over a ``MarkovCorpus`` seeded from the run's seed, placed with the batch
sharding. The first ``checked_steps`` steps go through the same compiled
step and feed and are what the check compares with the plain reference;
they also warm everything the window runs.

The window then repeats main's loop body (make and place a batch, call the
step, read the loss) until ``seconds`` have passed. With a trace, a further
``trace_steps`` steps run under the profiler.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import importlib
import math
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.data import MarkovCorpus, SyntheticPipeline
from repro.launch.mesh import make_mesh
from repro.launch.train import pick_strategy
from repro.models.lm import ModelCfg, init_params
from repro.parallel.sharding import batch_spec, make_plan, param_specs
from repro.train.optimizer import OptState, adamw_init
from repro.train.train_step import TrainStepCfg, make_train_step

from chipbench import count
from chipbench import trace as tr
from chipbench import weights as W
from chipbench.check import judge, raw_grad_norms, train_numbers
from chipbench.reference.common import Products, train_reference

SPANS = ("input", "step", "loss_read")


class Trainer:
    """One compiled step with its state, its feed and its mesh."""

    def __init__(self, cell):
        self.cell = cell
        arch, t = cell.arch, cell.traffic
        sp = cell.spans
        n = len(cell.devices)
        self.batch, self.seq = t["global_batch"], t["seq"]
        with sp.span("search"):
            report = pick_strategy(arch, n, self.batch, self.seq)
        best = report.best
        if best is None:
            raise RuntimeError(f"the search found no plan for {arch.name} on {n} chip(s)")
        self.plan_text = (f"tp={best.tensor_parallel} pp={best.pipeline_parallel} "
                          f"dp={best.data_parallel} mbs={best.micro_batch_size} "
                          f"remat={best.recompute_granularity} "
                          f"dist_opt={best.use_distributed_optimizer}")
        self.remat = best.recompute_granularity
        self.micro = max(best.num_microbatches(self.batch), 1)
        self.predicted_step_s = report.best_sim.step_time

        self.mesh = make_mesh((n, 1), ("data", "model"), devices=cell.devices)
        plan = make_plan(self.mesh, fsdp=True)
        self.plan = plan
        cfg = ModelCfg(dtype=jnp.dtype(cell.config["dtype"]), attn_impl="xla",
                       ssm_impl="xla", remat=self.remat)
        step_cfg = TrainStepCfg(num_microbatches=self.micro, base_lr=t["base_lr"],
                                warmup_steps=t["warmup_steps"],
                                total_steps=t["total_steps"], batch_axes=plan.batch_axes)
        step = make_train_step(arch, cfg, step_cfg)
        init = functools.partial(init_params, arch, dtype=jnp.float32)
        self.struct = jax.eval_shape(init, jax.random.PRNGKey(0))
        p_spec = param_specs(arch, plan, self.struct)
        self.p_sh = jax.tree_util.tree_map(lambda s: NamedSharding(self.mesh, s), p_spec,
                                           is_leaf=lambda x: isinstance(x, P))
        replicated = NamedSharding(self.mesh, P())
        self.opt_sh = OptState(mu=self.p_sh, nu=self.p_sh, step=replicated)
        self.reset(cell.seed)
        _, batch = self.next_batch()
        with jax.set_mesh(self.mesh):
            self.compiled = jax.jit(
                step, out_shardings=(self.p_sh, self.opt_sh, replicated),
                donate_argnums=(0, 1),
            ).lower(self.params, self.opt, batch).compile()
        self.memory = compiled_memory(self.compiled)
        self.pipe.step = 0

    def reset(self, seed: int) -> None:
        """Fresh weights, optimizer state and feed for ``seed``."""
        self.params = self.opt = None
        gc.collect()
        self.seed = seed
        self.params = W.make(self.struct, seed, self.cell.arch.num_layers,
                             out_shardings=self.p_sh)
        self.opt = jax.jit(adamw_init, out_shardings=self.opt_sh)(self.params)
        corpus = MarkovCorpus(self.cell.arch.vocab, seed=seed % (1 << 64))
        self.pipe = SyntheticPipeline(corpus=corpus, global_batch=self.batch,
                                      seq_len=self.seq)

    def next_batch(self):
        with self.cell.spans.span("input"):
            batch = self.pipe.next_batch()
            b_sh = jax.tree_util.tree_map(
                lambda s: NamedSharding(self.mesh, s), batch_spec(self.plan, batch),
                is_leaf=lambda x: isinstance(x, P))
            placed = jax.tree_util.tree_map(jax.device_put, batch, b_sh)
        return batch["tokens"], placed

    def step(self, batch) -> float:
        sp = self.cell.spans
        with sp.span("step"):
            self.params, self.opt, metrics = self.compiled(self.params, self.opt, batch)
        with sp.span("loss_read"):
            loss = float(metrics["loss"])
        return loss, metrics

    def checked_steps(self, n: int) -> dict:
        """The first ``n`` steps, with what the check compares read on the way:
        each loss, the first raw gradient's norms (from AdamW's first moment
        and the step's gradient norm), and the parameters' change after n."""
        tokens, losses, grad = [], [], None
        with jax.set_mesh(self.mesh):
            for i in range(n):
                tok, batch = self.next_batch()
                tokens.append(tok)
                loss, metrics = self.step(batch)
                losses.append(loss)
                if i == 0:
                    grad = raw_grad_norms(W.slice_norms(self.opt.mu),
                                          float(metrics["grad_norm"]))
            change = W.change_norms(self.params, self.seed, self.cell.arch.num_layers)
        return {"tokens": tokens, "losses": losses, "grad_norms": grad,
                "change_norms": change}

    def window(self, seconds: float = None, steps: int = None) -> dict:
        """Main's loop until ``seconds`` have passed (or ``steps`` are done)."""
        sp = self.cell.spans
        done = failed = 0
        with jax.set_mesh(self.mesh), sp.span(tr.WINDOW):
            t0 = time.perf_counter()
            while True:
                _, batch = self.next_batch()
                loss, _ = self.step(batch)
                done += 1
                failed += not math.isfinite(loss)
                now = time.perf_counter()
                if (steps is not None and done >= steps) or \
                        (steps is None and now - t0 >= seconds):
                    break
        return {"steps": done, "failed": failed, "seconds": now - t0, "t0": t0, "t1": now}

    def free(self) -> None:
        self.params = self.opt = self.compiled = None
        gc.collect()


def compiled_memory(compiled) -> dict:
    """What the compiler reserves for a program on each device, in bytes."""
    m = compiled.memory_analysis()
    return {k: int(getattr(m, k + "_size_in_bytes", 0) or 0)
            for k in ("argument", "output", "alias", "temp")}


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


def reference_numbers(cell, readings: dict, products: Products) -> dict:
    """The plain reference's readings over the same seed and batches."""
    fam = importlib.import_module("chipbench.reference." + cell.arch.family)
    t = cell.traffic
    hp = {k: t[k] for k in ("base_lr", "warmup_steps", "total_steps")}
    return train_reference(fam, dataclasses.asdict(cell.arch), readings["seed"],
                           readings["tokens"], hp, products, device=cell.devices[0])


def run(cell) -> dict:
    t = cell.traffic
    trainer = Trainer(cell)
    readings = trainer.checked_steps(t["checked_steps"])
    readings["seed"] = cell.seed
    setup_s = time.perf_counter() - cell.t0
    win = trainer.window(seconds=cell.seconds)
    tokens = win["steps"] * trainer.batch * trainer.seq
    ctx = {"window": win, "predicted_step_s": trainer.predicted_step_s,
           "flops_per_step": count.train_flops_per_step(
               dataclasses.asdict(cell.arch), trainer.batch, trainer.seq)}
    t_trace = time.perf_counter()
    if cell.trace:
        ctx["trace"] = tr.capture(lambda: trainer.window(steps=t["trace_steps"]), SPANS)
    peak = memory_peak(cell.devices)
    trainer.free()
    t_ref = time.perf_counter()
    ref = reference_numbers(cell, readings, Products())
    correct, checked = judge(train_numbers(readings, ref), cell.limits)
    timing = {"plan": trainer.plan_text, "step_memory": trainer.memory, "setup_s": setup_s, "window_s": win["seconds"],
              "trace_s": t_ref - t_trace, "reference_s": time.perf_counter() - t_ref}
    return {
        "correct": correct and win["failed"] == 0,
        "attempted": win["steps"], "failed": win["failed"],
        "e2e": {"train_tokens_per_s": tokens / win["seconds"], "setup_s": setup_s},
        "memory_peak_bytes": peak, "check": checked, "ctx": ctx, "timing": timing,
    }
