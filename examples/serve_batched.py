"""Batched serving demo: prefill + decode over the ServeEngine.

    PYTHONPATH=src python examples/serve_batched.py --arch qwen3-8b --tokens 24

With ``--search-spec spec.json`` the server first replays a serialized
:class:`repro.core.SearchSpec` through the spec-keyed
:class:`repro.serve.SearchService` and reports the strategy it would deploy
— both the spec and the report are wire formats (see examples/README.md for
the endpoint contract), so the replayed report is exactly what a control
plane would have served. Pass ``--search-url http://host:port`` to fetch
the report from a remote service (``python -m repro.serve.search_service
serve``) instead of searching in-process; repeated deploys of the same spec
then hit the fleet-wide cache.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm
from repro.serve import ServeEngine


def pick_strategy_from_spec(path: str, url: str = None, token: str = None,
                            timeout: float = None):
    """Replay a serialized SearchSpec through the search service.

    In-process by default; with ``url`` the spec is POSTed to a remote
    service (``token`` authenticates against an ``--auth-tokens`` service)
    through the hardened HTTP client: a dead service fails within
    ``timeout`` with a clean error instead of hanging the deploy forever,
    and transient transport faults retry with backoff."""
    from repro.core import SearchSpec

    with open(path) as f:
        spec_json = f.read()
    spec = SearchSpec.from_json(spec_json)

    if url:
        from repro.serve.search_service import post_spec

        kw = {} if timeout is None else {"timeout": timeout}
        key, report, cached = post_spec(url, spec_json, token=token, **kw)
        print(f"served by {url} (key={key} cached={cached})")
        return spec, report

    from repro.calibration.fit import load_or_train
    from repro.core import Astra
    from repro.serve import SearchService

    eta, _ = load_or_train()
    return spec, SearchService(Astra(eta)).search(spec)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=24)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--search-spec", default=None, metavar="SPEC_JSON",
                    help="replay a serialized SearchSpec and report the "
                         "strategy this deployment would use")
    ap.add_argument("--search-url", default=None, metavar="URL",
                    help="fetch the report from a running search service "
                         "instead of searching in-process")
    ap.add_argument("--search-token", default=None, metavar="TOKEN",
                    help="bearer token when --search-url points at an "
                         "auth-enabled service")
    ap.add_argument("--search-timeout", type=float, default=None,
                    metavar="SECONDS",
                    help="per-request timeout against --search-url "
                         "(default: the service client's 600s)")
    ap.add_argument("--emit-traces", default=None, metavar="PATH",
                    help="append one measured source='serve' StepTrace "
                         "(JSONL, wire format) from this generate's decode "
                         "steps — the same feedback inlet launch/train.py "
                         "feeds ('python -m repro.serve.search_service "
                         "traces' or CalibrationLoop.ingest)")
    args = ap.parse_args()

    report = None
    if args.search_spec:
        try:
            spec, report = pick_strategy_from_spec(
                args.search_spec, url=args.search_url,
                token=args.search_token, timeout=args.search_timeout,
            )
        except (RuntimeError, OSError) as e:
            print(f"search service unavailable: {e}", file=sys.stderr)
            return 2
        b = report.best
        if b is None:
            print(f"search spec {args.search_spec}: no feasible strategy")
        else:
            print(f"search spec {args.search_spec} ({report.mode}): "
                  f"{b.device} x{b.num_devices} tp={b.tensor_parallel} "
                  f"pp={b.pipeline_parallel} dp={b.data_parallel} -> "
                  f"{report.best_sim.throughput_tokens:,.0f} tok/s simulated")

    enable_compile_cache()
    arch = get_reduced(args.arch)
    cfg = lm.ModelCfg(dtype=jnp.float32, attn_impl="xla", ssm_impl="xla")
    params = lm.init_params(arch, jax.random.PRNGKey(0))
    engine = ServeEngine(arch, cfg, params,
                         max_len=args.prompt_len + args.tokens + 8)

    prompts = np.random.default_rng(0).integers(
        0, arch.vocab, size=(args.batch, args.prompt_len)
    ).astype(np.int32)

    t0 = time.time()
    result = engine.generate(prompts, max_new_tokens=args.tokens,
                             temperature=args.temperature, seed=1)
    dt = time.time() - t0
    total_new = args.batch * args.tokens
    print(f"arch={arch.name} ({arch.total_params()/1e6:.1f}M params, "
          f"family={arch.family})")
    print(f"batched generate: {args.batch} requests x {args.tokens} tokens "
          f"in {dt:.2f}s ({total_new/dt:.1f} tok/s incl. compile)")
    for i, row in enumerate(result.tokens[:2]):
        print(f"  req{i}: prompt={row[:args.prompt_len].tolist()[:8]}... "
              f"generated={row[args.prompt_len:].tolist()}")

    # drop the jit-compile warmup steps before the trace ships to the
    # calibration loop — a compile-polluted step time skews drift scoring
    # toward spurious refits; the exclusion is recorded on the trace
    clean_steps = result.step_times[result.warmup_steps:]
    if args.emit_traces and clean_steps:
        from repro.calibration.traces import StepTrace, append_trace
        from repro.core.params import ParallelStrategy

        # attribute the measurement to the searched strategy when there is
        # one; otherwise describe the device this serve actually ran on
        strategy = report.best if report is not None and report.best \
            is not None else ParallelStrategy(
                device="tpu-v5e", num_devices=max(jax.device_count(), 1),
                micro_batch_size=max(args.batch, 1),
            )
        trace = StepTrace(
            arch=arch, strategy=strategy,
            global_batch=args.batch, seq=args.prompt_len + args.tokens,
            step_times=clean_steps, source="serve",
            warmup_steps_excluded=result.warmup_steps,
        )
        append_trace(args.emit_traces, trace)
        print(f"[trace] appended {len(clean_steps)}-step serve trace "
              f"({result.warmup_steps} warmup step(s) excluded, median "
              f"{trace.measured_step_time:.4f}s) to {args.emit_traces}")
    elif args.emit_traces:
        print("[trace] nothing to append: every measured step was a "
              "compile warmup")


if __name__ == "__main__":
    sys.exit(main())
