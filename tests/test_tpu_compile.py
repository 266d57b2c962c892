"""Compile-only tests for a described TPU v5e: no chip is needed, the TPU
compiler installed with JAX compiles for a ``v5e:2x2`` topology that is
described, not attached. They catch what interpret-mode tests cannot: block
shapes the Mosaic tiling refuses, unsupported vector layouts, programs that
do not fit the chip's memory.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_smoke  # noqa: E402
from repro.kernels.flash_attention import flash_attention_fwd  # noqa: E402
from repro.kernels.rmsnorm import rmsnorm_fwd  # noqa: E402
from repro.kernels.ssd import ssd_scan_fwd  # noqa: E402
from repro.models import lm  # noqa: E402

V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: a
    program compiled for a described chip can be written to the cache but
    not read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding) for shape, dtype in specs]


def _kernel_case(name):
    bf16, f32 = jnp.bfloat16, jnp.float32
    if name == "flash_attention":
        s = chip_smoke.FLASH_SHAPE
        q = ((s["B"], s["Hq"], s["S"], s["D"]), bf16)
        kv = ((s["B"], s["Hkv"], s["S"], s["D"]), bf16)
        return (lambda q, k, v: flash_attention_fwd(q, k, v, causal=True)), (q, kv, kv)
    if name == "rmsnorm":
        shape = chip_smoke.RMSNORM_SHAPE
        return rmsnorm_fwd, ((shape, bf16), (shape[-1:], bf16))
    s = chip_smoke.SSD_SHAPE
    B, S, H, P, N = (s[k] for k in ("B", "S", "H", "P", "N"))
    return ssd_scan_fwd, (((B, S, H, P), f32), ((B, S, H), f32), ((H,), f32),
                          ((B, S, N), f32), ((B, S, N), f32), ((H,), f32))


@pytest.mark.parametrize("name", ["flash_attention", "rmsnorm", "ssd"])
def test_kernel_compiles_natively_for_v5e(one_chip, name):
    """Each Pallas kernel, at the widths chip_smoke.py runs it, compiles
    for a v5e into a Mosaic custom call (no interpreter, no XLA stand-in)
    that carries the kernel's own name, the op's name in a device trace."""
    fn, specs = _kernel_case(name)
    text = jax.jit(fn).lower(*_shapes(one_chip, *specs)).compile().as_text()
    assert re.search(rf"%{name}(\.\d+)? = [^\n]*tpu_custom_call", text)


def test_serve_decode_step_compiles_for_v5e(one_chip):
    """chip_smoke.py's serve configuration (qwen3-8b widths, 16 layers, bf16,
    4 requests, 512 + 64 positions): the decode step compiles for one v5e
    and its arguments and temporaries fit the chip's HBM."""
    arch = chip_smoke.SERVE_ARCH
    cfg = lm.ModelCfg(dtype=jnp.bfloat16, attn_impl="xla", ssm_impl="xla")
    as_shapes = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), tree)
    params = as_shapes(jax.eval_shape(
        lambda k: lm.init_params(arch, k, dtype=jnp.bfloat16), jax.random.PRNGKey(0)))
    caches = as_shapes(jax.eval_shape(lambda: lm.init_caches(
        arch, cfg, chip_smoke.SERVE_REQUESTS, chip_smoke.PROMPT_LEN + chip_smoke.NEW_TOKENS)))
    tokens, position = _shapes(
        one_chip, ((chip_smoke.SERVE_REQUESTS, 1), jnp.int32), ((), jnp.int32))
    decode = jax.jit(lambda p, c, t, pos: lm.decode_step(p, arch, cfg, c, t, pos))
    compiled = decode.lower(params, caches, tokens, position).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM_BYTES
