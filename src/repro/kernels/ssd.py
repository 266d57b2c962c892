"""Mamba-2 SSD (state-space duality) chunked scan as a Pallas TPU kernel.

TPU adaptation of the SSD algorithm (Dao & Gu 2024): the sequence is split
into chunks; within a chunk the recurrence is evaluated as a (chunk x chunk)
masked matmul on the MXU (the "duality" — quadratic attention form), and the
running state (P x N per head) is carried across chunks in VMEM scratch,
with the grid's minor-most dimension iterating chunks sequentially per
(batch, head). This replaces the CUDA implementation's warp-level scan with
MXU matmuls + a VMEM-resident state — the TPU-native formulation.

Recurrence (per head, A scalar per head as in Mamba-2):
    h_t = exp(A * dt_t) * h_{t-1} + dt_t * x_t (outer) B_t
    y_t = h_t . C_t + D * x_t
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import check_interpret

DEFAULT_CHUNK = 128
# f32 operands at full precision: a one-pass bf16 product would put the
# state recurrence outside the oracle's tolerance
_HIGHEST = jax.lax.Precision.HIGHEST


def _ssd_kernel(
    x_ref, dt_col_ref, dt_row_ref, a_ref, b_ref, c_ref, d_ref,  # inputs
    y_ref, state_ref,  # outputs
    h_scr,  # (P, N) running state
    *,
    chunk: int,
    seq_len: int,
):
    # Mosaic has no layout for 1-D vectors, so every per-position quantity is
    # kept 2-D: dt arrives both as a column (L, 1) and as a row (1, L), and
    # the in-chunk sums are masked (L, L) reductions and matmuls instead of
    # a cumsum plus a transpose.
    ih = pl.program_id(1)
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0, 0].astype(jnp.float32)  # (L, P)
    dt_c = dt_col_ref[0, 0].astype(jnp.float32)  # (L, 1)
    dt_r = dt_row_ref[0, 0].astype(jnp.float32)  # (1, L)
    a = a_ref[ih]  # scalar, SMEM
    dcoef = d_ref[ih]  # scalar, SMEM
    bmat = b_ref[0].astype(jnp.float32)  # (L, N)
    cmat = c_ref[0].astype(jnp.float32)  # (L, N)

    # zero invalid tail positions (sequence padding)
    valid_c = ic * chunk + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) < seq_len
    valid_r = ic * chunk + jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1) < seq_len
    dt_c = jnp.where(valid_c, dt_c, 0.0)  # exp(a*0)=1, no state change
    dt_r = jnp.where(valid_r, dt_r, 0.0)
    x = jnp.where(valid_c, x, 0.0)
    bmat = jnp.where(valid_c, bmat, 0.0)
    cmat = jnp.where(valid_c, cmat, 0.0)

    # Log-decays over a span of positions are summed over that span alone,
    # never taken as the difference of two cumulative sums: across a chunk
    # the cumulative log-decay reaches hundreds, and the difference would
    # keep only eps * |sum| of absolute precision (1e-4 relative error in
    # every decay factor at mamba2-370m's widths).
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = t_idx >= s_idx
    adt_upto = jnp.where(causal, a * dt_r, 0.0)  # [t, u] = a*dt_u for u <= t
    # g_t = sum_{u<=t} a*dt_u
    g_c = jnp.sum(adt_upto, axis=1, keepdims=True)  # (L, 1)
    # seg_ts = sum_{s<u<=t} a*dt_u, as [u <= t] a*dt_u @ [u > s]
    seg = jax.lax.dot_general(
        adt_upto, jnp.where(t_idx > s_idx, 1.0, 0.0), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_HIGHEST,
    )  # (L, L)
    # intra-chunk "attention" scores: S_ts = C_t . B_s * exp(seg_ts) * dt_s, s<=t
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))  # (L, L)
    scores = jax.lax.dot_general(
        cmat, bmat, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_HIGHEST,
    ) * decay * dt_r
    y_intra = jax.lax.dot_general(
        scores, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_HIGHEST,
    )  # (L, P)

    # inter-chunk: contribution of carried state, y_t += exp(g_t) * C_t . h_in
    h_in = h_scr[...]  # (P, N)
    y_state = jnp.exp(g_c) * jax.lax.dot_general(
        cmat, h_in, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_HIGHEST,
    )  # (L, P)

    y = y_intra + y_state + dcoef * x
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update: h_out = exp(G) h_in + sum_s exp(G - g_s) dt_s x_s (outer) B_s,
    # with G - g_s = sum_{u>s} a*dt_u
    G = jnp.sum(a * dt_r, axis=1, keepdims=True)  # (1, 1)
    after = jnp.sum(jnp.where(s_idx > t_idx, a * dt_r, 0.0), axis=1, keepdims=True)
    w = jnp.exp(after) * dt_c  # (L, 1)
    h_new = jnp.exp(G) * h_in + jax.lax.dot_general(
        x * w, bmat, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_HIGHEST,
    )  # (P, N)
    h_scr[...] = h_new

    @pl.when(ic == nc - 1)
    def _emit_state():
        state_ref[0, 0] = h_new.astype(state_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_fwd(
    x: jax.Array,  # (B, S, H, P)
    dt: jax.Array,  # (B, S, H), positive
    A: jax.Array,  # (H,), negative
    Bm: jax.Array,  # (B, S, N)
    C: jax.Array,  # (B, S, N)
    D: Optional[jax.Array] = None,  # (H,)
    *,
    chunk: int = DEFAULT_CHUNK,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (y (B,S,H,P), final_state (B,H,P,N)).

    ``interpret=True`` runs the Pallas interpreter (CPU backend only).
    Heads move ahead of the sequence for the kernel, so that every block's
    last two dims are (positions, features) and tile the TPU's (8, 128)
    vector registers."""
    check_interpret(interpret)
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    if D is None:
        D = jnp.zeros((H,), jnp.float32)
    L = min(chunk, S)
    nc = pl.cdiv(S, L)
    grid = (B, H, nc)
    dt_h = dt.transpose(0, 2, 1)  # (B, H, S)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    y, state = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=L, seq_len=S),
        name="ssd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, L, P), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, L, 1), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, 1, L), lambda b, h, ic: (b, h, 0, ic)),
            smem,
            pl.BlockSpec((1, L, N), lambda b, h, ic: (b, ic, 0)),
            pl.BlockSpec((1, L, N), lambda b, h, ic: (b, ic, 0)),
            smem,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, L, P), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(
        x.transpose(0, 2, 1, 3), dt_h[..., None], dt_h[:, :, None, :],
        A.astype(jnp.float32), Bm, C, D.astype(jnp.float32),
    )
    return y.transpose(0, 2, 1, 3), state
