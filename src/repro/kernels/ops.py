"""Public, differentiable wrappers over the Pallas kernels.

Each op takes ``impl``:
  * "pallas"  — the Pallas forward, compiled natively on a TPU, with a
                recompute-based backward — the flash-attention backward IS
                recomputation, so grads are memory-frugal by construction.
                ``interpret=True`` runs the Pallas interpreter instead, which
                only the CPU backend may do (tests).
  * "xla"     — plain XLA, which SPMD-partitions (the 512-device dry-run
                lowering) and is the train path's: flash attention as a
                scan over KV blocks (``xla_flash``), the SSD in its chunked
                matmul form (``ssd_xla``), RMSNorm as the ``ref`` oracle.
The oracles the kernels and the XLA forms are tested against are in ``ref``;
``ref.ssd_scan`` is the sequential SSD recurrence.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.rmsnorm import rmsnorm_fwd
from repro.kernels.ssd import ssd_scan_fwd
from repro.kernels.ssd_xla import ssd_chunked, ssd_segments


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_attention_pallas(q, k, v, causal: bool, sm_scale: Optional[float],
                            interpret: bool):
    out, _ = flash_attention_fwd(q, k, v, causal=causal, sm_scale=sm_scale,
                                 interpret=interpret)
    return out


def _fa_fwd(q, k, v, causal, sm_scale, interpret):
    out, _ = flash_attention_fwd(q, k, v, causal=causal, sm_scale=sm_scale,
                                 interpret=interpret)
    return out, (q, k, v)


def _fa_bwd(causal, sm_scale, interpret, res, g):
    q, k, v = res
    # flash backward == blockwise recompute; the reference VJP is the oracle
    # formulation of exactly that recomputation.
    _, vjp = jax.vjp(
        lambda q_, k_, v_: ref.attention(q_, k_, v_, causal=causal, sm_scale=sm_scale),
        q, k, v,
    )
    return vjp(g)


_flash_attention_pallas.defvjp(_fa_fwd, _fa_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    impl: str = "pallas",
    interpret: bool = False,
) -> jax.Array:
    """GQA flash attention. q: (B,Hq,S,D), k/v: (B,Hkv,T,D).

    impl: "pallas" (TPU kernel), "xla" (scan-based
    online softmax — memory-sane for 32k+ and SPMD-partitionable), "naive"
    (the O(S*T)-memory oracle, tests only).
    """
    if impl == "pallas":
        return _flash_attention_pallas(q, k, v, causal, sm_scale, interpret)
    if impl == "xla":
        from repro.kernels.xla_flash import flash_xla_train

        return flash_xla_train(q, k, v, causal, sm_scale, 512)
    return ref.attention(q, k, v, causal=causal, sm_scale=sm_scale)


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rmsnorm_pallas(x, w, eps: float, interpret: bool):
    return rmsnorm_fwd(x, w, eps=eps, interpret=interpret)


def _rn_fwd(x, w, eps, interpret):
    return rmsnorm_fwd(x, w, eps=eps, interpret=interpret), (x, w)


def _rn_bwd(eps, interpret, res, g):
    x, w = res
    _, vjp = jax.vjp(lambda x_, w_: ref.rmsnorm(x_, w_, eps=eps), x, w)
    return vjp(g)


_rmsnorm_pallas.defvjp(_rn_fwd, _rn_bwd)


def fused_rmsnorm(
    x: jax.Array, weight: jax.Array, *, eps: float = 1e-6, impl: str = "pallas",
    interpret: bool = False,
) -> jax.Array:
    if impl == "pallas":
        return _rmsnorm_pallas(x, weight, eps, interpret)
    return ref.rmsnorm(x, weight, eps=eps)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _ssd_pallas(x, dt, A, Bm, C, D, interpret: bool):
    y, _ = ssd_scan_fwd(x, dt, A, Bm, C, D, interpret=interpret)
    return y


def _ssd_fwd(x, dt, A, Bm, C, D, interpret):
    y, _ = ssd_scan_fwd(x, dt, A, Bm, C, D, interpret=interpret)
    return y, (x, dt, A, Bm, C, D)


def _ssd_bwd(interpret, res, g):
    x, dt, A, Bm, C, D = res
    _, vjp = jax.vjp(lambda *a: ref.ssd_scan(*a), x, dt, A, Bm, C, D)
    return vjp(g)


_ssd_pallas.defvjp(_ssd_fwd, _ssd_bwd)


def ssd(
    x: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    Bm: jax.Array,
    C: jax.Array,
    D: Optional[jax.Array] = None,
    *,
    impl: str = "pallas",
    chunk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Mamba-2 SSD mixer. Training form (no state I/O).

    impl: "pallas" (TPU kernel; its backward differentiates the sequential
    ``ref.ssd_scan``), "xla" (the chunked matmul form of ``ssd_xla`` in
    chunks of ``min(chunk, S)`` positions, differentiated by autodiff)."""
    if D is None:
        D = jnp.zeros((x.shape[2],), jnp.float32)
    if impl == "pallas":
        return _ssd_pallas(x, dt, A, Bm, C, D, interpret)
    return ssd_chunked(x, dt, A, Bm, C, D, chunk=chunk)[0]


def ssd_with_state(
    x, dt, A, Bm, C, D=None, *, init_state=None, impl: str = "xla", chunk: int = 256
):
    """Decode/prefill form: returns (y, final_state) from ``init_state``.
    More than one position (a prefill) runs ``ssd_xla.ssd_segments``, the
    chunked form in a scan over segments of ``chunk`` positions; one
    position (a decode step) is one step of the recurrence,
    ``ref.ssd_scan``. The Pallas kernel assumes a zero initial state, so
    ``impl="pallas"`` takes it for a prefill from zero alone."""
    if D is None:
        D = jnp.zeros((x.shape[2],), jnp.float32)
    if impl == "pallas" and init_state is None:
        return ssd_scan_fwd(x, dt, A, Bm, C, D)
    if x.shape[1] > 1:
        return ssd_segments(x, dt, A, Bm, C, D, chunk=chunk, init_state=init_state)
    return ref.ssd_scan(x, dt, A, Bm, C, D, init_state=init_state, return_state=True)
