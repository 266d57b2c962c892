"""Names the program gives its work for a profiler: each layer kind's
``jax.named_scope`` in the compiled train step's op metadata, and the host
spans of the serving engine, the data pipeline, the search and the train
loop in a CPU profile (``TraceAnnotation`` records only while a profiler
runs, on the device trace's clock)."""
import collections
import functools
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.models import lm
from repro.serve import ServeEngine
from repro.train.optimizer import adamw_init
from repro.train.train_step import TrainStepCfg, make_train_step

LAYER_SCOPES = {"embed", "attn", "ffn", "moe", "ssd", "head", "optimizer"}


def _scope_names(hlo_text: str) -> set:
    """Every part of every op-name path in the metadata, with the
    ``jvp(...)``/``transpose(...)`` wrappers of the backward pass removed."""
    return {re.sub(r"^(?:[\w.]+\()+|\)+$", "", part)
            for path in re.findall(r'op_name="([^"]*)"', hlo_text)
            for part in path.split("/")}


@pytest.mark.parametrize("preset, scopes", [
    ("yi-6b", {"embed", "attn", "ffn", "head", "optimizer"}),
    ("mamba2-370m", {"embed", "ssd", "head", "optimizer"}),
])
def test_train_step_ops_carry_layer_scopes(preset, scopes):
    """Scopes survive the layer scan, the remat and the transpose into the
    compiled step; a family's step carries its own kinds and no other."""
    arch = get_reduced(preset)
    cfg = lm.ModelCfg(dtype=jnp.bfloat16, attn_impl="xla", ssm_impl="xla", remat="full")
    step = make_train_step(arch, cfg, TrainStepCfg(num_microbatches=2))
    params = jax.eval_shape(functools.partial(lm.init_params, arch), jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((4, 32), jnp.int32)}
    text = jax.jit(step).lower(params, jax.eval_shape(adamw_init, params),
                               batch).compile().as_text()
    assert _scope_names(text) & LAYER_SCOPES == scopes


def _profile(tmp_path, fn):
    """Names of the host events that ``fn`` left in a CPU profile, each with
    its (start, end), and the interval of the span that wrapped ``fn``."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("test.window"):
            fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    events = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    events[ev.name].append((ev.start_ns, ev.end_ns))
    (lo, hi), = events.pop("test.window")
    return events, lo, hi


def test_generate_spans_in_profile(tmp_path, tiny_dense):
    cfg = lm.ModelCfg(dtype=jnp.float32, attn_impl="xla", ssm_impl="xla")
    engine = ServeEngine(tiny_dense, cfg, lm.init_params(tiny_dense, jax.random.PRNGKey(0)),
                         max_len=16)
    prompts = np.zeros((2, 5), np.int32)
    engine.generate(prompts, max_new_tokens=3)  # compiles outside the profile
    events, lo, hi = _profile(tmp_path, lambda: engine.generate(prompts, max_new_tokens=3))
    spans = {k: v for k, v in events.items() if k.startswith("serve.")}
    assert {k: len(v) for k, v in spans.items()} == {
        "serve.init_caches": 1, "serve.prefill": 1,
        "serve.sample": 3, "serve.host_read": 3, "serve.decode": 3}
    assert all(lo <= s <= e <= hi for v in spans.values() for s, e in v)
    # per token: sample, then the blocking read, then the next dispatch
    order = sorted((s, k) for k in ("serve.sample", "serve.host_read", "serve.decode")
                   for s, _ in spans[k])
    assert [k for _, k in order] == ["serve.sample", "serve.host_read", "serve.decode"] * 3


def test_train_loop_and_search_spans_in_profile(tmp_path, monkeypatch):
    """``main``'s steps (TensorBoard's step view), each batch the pipeline
    makes, and the search with the eta model's loading inside it."""
    from repro.calibration.fit import train_eta_model
    from repro.launch import train as train_mod

    eta = train_eta_model(n_samples=300, n_estimators=10)
    monkeypatch.setattr(train_mod, "load_or_train", lambda: eta)
    events, lo, hi = _profile(tmp_path, lambda: train_mod.main(
        ["--arch", "yi-6b", "--reduced", "--auto-strategy",
         "--steps", "2", "--batch", "4", "--seq", "32"]))
    assert len(events["train"]) == 2
    assert len(events["data.next_batch"]) == 2
    (s0, s1), = events["search"]
    (e0, e1), = events["search.eta_model"]
    assert lo <= s0 <= e0 <= e1 <= s1 <= hi
