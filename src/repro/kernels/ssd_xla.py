"""Mamba-2 SSD in its chunked (state-space duality) form, in plain XLA.

This is the training path of ``ops.ssd(impl="xla")``, differentiated by
JAX's own autodiff; ``ref.ssd_scan`` is the sequential oracle it is tested
against. The sequence is split into chunks of ``L`` positions (Dao & Gu
2024, the algorithm ``core/costmodel.py`` prices):

  * intra-chunk: ``y_diag = ((C B^T) o decay o dt) x`` as masked (L x L)
    matmuls on the MXU; B and C have one group, so ``C B^T`` is shared by
    every head;
  * chunk states: ``sum_s exp(sum_{u>s} a dt_u) dt_s x_s (outer) B_s``;
  * inter-chunk: the states are carried across the S / L chunks by a
    (chunks x chunks) decay matrix;
  * output: ``y_off = exp(g_t) C_t . h_in``, then ``+ D x``.

Everything runs in float32 with ``precision=HIGHEST`` products, as the
Pallas kernel (``kernels/ssd.py``) does. Positions past ``S`` are padded
with ``dt = 0``: no decay and no input.

``ssd_segments`` is the serve prefill's form, which carries a state in and
out: a scan over segments of ``chunk`` positions, each one ``ssd_chunked``
call fed the state the segment before it left, so that no temporary grows
with the prompt (at batch 16 and 64 heads each (L x L) one is 268 MB; over
a whole 2048-token prompt at once they would be 2.1 GB each).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST


def _segsum(a: jax.Array) -> jax.Array:
    """(..., T) -> (..., T, T): [t, s] = sum_{s<u<=t} a_u for s <= t, 0 above.

    The log-decay over a span is summed over that span, never taken as the
    difference of two cumulative sums: across a chunk the cumulative
    log-decay reaches hundreds, and the difference would keep only
    eps * |sum| of absolute precision. The span sums are one masked matmul,
    ``[u <= t] a_u @ [u > s]``, as in the Pallas kernel (on a v5e it ran the
    train step's SSD faster than a masked cumulative sum)."""
    T = a.shape[-1]
    i = jnp.arange(T)
    upto = jnp.where(i[None, :] <= i[:, None], a[..., None, :], 0.0)  # [t, u]
    above = (i[:, None] > i[None, :]).astype(a.dtype)  # [u, s]
    return jnp.einsum("...tu,us->...ts", upto, above, precision=_HIGHEST)


def _causal_exp(seg: jax.Array) -> jax.Array:
    """exp of a (..., T, T) span-sum matrix on and below the diagonal, 0
    above; the exponent is masked before exp, so no inf * 0 reaches the
    gradient."""
    T = seg.shape[-1]
    causal = jnp.tril(jnp.ones((T, T), bool))
    return jnp.exp(jnp.where(causal, seg, -jnp.inf))


def ssd_chunked(
    x: jax.Array,  # (B, S, H, P)
    dt: jax.Array,  # (B, S, H), positive
    A: jax.Array,  # (H,), negative
    Bm: jax.Array,  # (B, S, N)
    C: jax.Array,  # (B, S, N)
    D: Optional[jax.Array] = None,  # (H,)
    *,
    chunk: int = 256,
    init_state: Optional[jax.Array] = None,  # (B, H, P, N)
) -> tuple[jax.Array, jax.Array]:
    """Returns (y (B, S, H, P) in x.dtype, final_state (B, H, P, N) float32),
    from ``init_state`` (zero if None); chunks of ``min(chunk, S)``
    positions."""
    Bsz, S, H, P = x.shape
    L = min(chunk, S)
    nc = -(-S // L)
    f32 = jnp.float32

    def chunks(v):  # (B, S, ...) -> (B, nc, L, ...), padded with zeros
        v = v.astype(f32)
        v = jnp.pad(v, [(0, 0), (0, nc * L - S)] + [(0, 0)] * (v.ndim - 2))
        return v.reshape((Bsz, nc, L) + v.shape[2:])

    xc = chunks(x).transpose(0, 3, 1, 2, 4)  # (B, H, nc, L, P)
    dtc = chunks(dt).transpose(0, 3, 1, 2)  # (B, H, nc, L)
    Bc, Cc = chunks(Bm), chunks(C)  # (B, nc, L, N)
    adt = A.astype(f32)[None, :, None, None] * dtc  # log-decay per position
    xdt = xc * dtc[..., None]

    # intra-chunk: the quadratic (attention-like) form
    seg = _segsum(adt)  # (B, H, nc, L, L)
    cb = jnp.einsum("bcln,bcsn->bcls", Cc, Bc, precision=_HIGHEST)
    scores = cb[:, None] * _causal_exp(seg)
    y = jnp.einsum("bhcls,bhcsp->bhclp", scores, xdt, precision=_HIGHEST)

    # each chunk's own state, decayed to the chunk's end
    after = seg[..., -1, :]  # [s] = sum_{s<u<L}
    states = jnp.einsum("bhcsp,bcsn->bhcpn", xdt * jnp.exp(after)[..., None], Bc,
                        precision=_HIGHEST)  # (B, H, nc, P, N)

    # inter-chunk: the state at each chunk's end, then its effect on the next
    upto = seg[..., :, 0] + adt[..., :1]  # [t] = sum_{u<=t}
    cdecay = _causal_exp(_segsum(upto[..., -1]))  # (B, H, nc, nc)
    h_out = jnp.einsum("bhzc,bhcpn->bhzpn", cdecay, states, precision=_HIGHEST)
    if init_state is None:
        h0 = jnp.zeros_like(h_out[:, :, :1])
    else:
        # the state carried in, decayed over every chunk up to each end
        h0 = init_state.astype(f32)[:, :, None]
        h_out = h_out + jnp.exp(jnp.cumsum(upto[..., -1], axis=-1))[..., None, None] * h0
    h_in = jnp.concatenate([h0, h_out[:, :, :-1]], axis=2)
    y = y + jnp.exp(upto)[..., None] * jnp.einsum(
        "bcln,bhcpn->bhclp", Cc, h_in, precision=_HIGHEST)

    y = y.reshape(Bsz, H, nc * L, P)[:, :, :S].transpose(0, 2, 1, 3)
    if D is not None:
        y = y + D.astype(f32)[None, None, :, None] * x.astype(f32)
    return y.astype(x.dtype), h_out[:, :, -1]


def ssd_segments(
    x: jax.Array,  # (B, S, H, P)
    dt: jax.Array,  # (B, S, H), positive
    A: jax.Array,  # (H,), negative
    Bm: jax.Array,  # (B, S, N)
    C: jax.Array,  # (B, S, N)
    D: Optional[jax.Array] = None,  # (H,)
    *,
    chunk: int = 256,
    init_state: Optional[jax.Array] = None,  # (B, H, P, N)
) -> tuple[jax.Array, jax.Array]:
    """``ssd_chunked``'s result, from a ``lax.scan`` over segments of
    ``min(chunk, S)`` positions that carries the float32 state; the last
    segment is padded with ``dt = 0``."""
    Bsz, S, H, P = x.shape
    L = min(chunk, S)
    n = -(-S // L)

    def segments(v):  # (B, S, ...) -> (n, B, L, ...), padded with zeros
        v = jnp.pad(v, [(0, 0), (0, n * L - S)] + [(0, 0)] * (v.ndim - 2))
        return jnp.moveaxis(v.reshape((Bsz, n, L) + v.shape[2:]), 1, 0)

    def body(h, seg):
        xs, dts, Bs, Cs = seg
        y, h = ssd_chunked(xs, dts, A, Bs, Cs, D, chunk=L, init_state=h)
        return h, y

    if init_state is None:
        init_state = jnp.zeros((Bsz, H, P, Bm.shape[-1]), jnp.float32)
    h, ys = jax.lax.scan(body, init_state.astype(jnp.float32),
                         tuple(map(segments, (x, dt, Bm, C))))
    return jnp.moveaxis(ys, 0, 1).reshape(Bsz, n * L, H, P)[:, :S], h
