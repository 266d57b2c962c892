"""Flash attention forward as a Pallas TPU kernel.

TPU-native layout (DESIGN.md hardware-adaptation): the grid's minor-most
dimension iterates over KV blocks *sequentially* per (batch, q-head,
q-block), so the online-softmax running state (m, l, acc) lives in VMEM
scratch that persists across those grid steps — the standard TPU flash pattern
(vs. the CUDA formulation's per-SM shared-memory tiles). Block shapes are
multiples of 128 to align with the MXU systolic array.

GQA is handled in the BlockSpec index maps: the KV block for q-head h comes
from kv-head h // (Hq // Hkv) — no KV replication in HBM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import check_interpret

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
_NEG_INF = -1e30


def _flash_fwd_kernel(
    q_ref, k_ref, v_ref,  # VMEM blocks
    o_ref, lse_ref,  # outputs
    m_scr, l_scr, acc_scr,  # VMEM scratch, persists across kv-block steps
    *,
    sm_scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    kv_len: int,
    q_offset: int,
):
    # every value is kept 2-D: Mosaic has no layout for 1-D vectors, so the
    # running max / sum are (bq, 1) columns and masks come from 2-D iotas
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: query block iq covers positions [q_offset + iq*bq, ...); skip
    # kv blocks strictly in the future.
    q_start = q_offset + iq * block_q

    def body():
        q = q_ref[0, 0]  # (bq, D)
        k = k_ref[0, 0]  # (bk, D)
        v = v_ref[0, 0]  # (bk, D)
        # zero the KV rows beyond the true length: out-of-bounds block padding
        # is undefined (NaN in interpret mode) and 0 * NaN would poison p @ v
        k_row = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
        k = jnp.where(k_row < kv_len, k, 0)
        v = jnp.where(k_row < kv_len, v, 0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # (bq, bk)

        k_pos = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        # mask padding beyond the true kv length
        s = jnp.where(k_pos < kv_len, s, _NEG_INF)

        m_prev = m_scr[...]  # (bq, 1)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_cur

    if causal:
        # whole block in the future => skip
        first_q = q_start
        first_k = ik * block_k
        pl.when(first_k <= first_q + block_q - 1)(body)
    else:
        body()

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[...] + jnp.log(l_safe)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "sm_scale", "block_q", "block_k", "interpret", "q_offset"
    ),
)
def flash_attention_fwd(
    q: jax.Array,  # (B, Hq, S, D)
    k: jax.Array,  # (B, Hkv, T, D)
    v: jax.Array,  # (B, Hkv, T, D)
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
    q_offset: Optional[int] = None,
) -> tuple[jax.Array, jax.Array]:
    """Returns (out (B,Hq,S,D), lse (B,Hq,S)).

    ``interpret=True`` runs the Pallas interpreter, which only the CPU
    backend may do (tests); everywhere else the kernel compiles natively.
    """
    check_interpret(interpret)
    B, Hq, S, D = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    if sm_scale is None:
        sm_scale = float(1.0 / (D ** 0.5))
    if q_offset is None:
        q_offset = T - S  # decode/append convention

    bq = min(block_q, S)
    bk = min(block_k, T)
    nq = pl.cdiv(S, bq)
    nk = pl.cdiv(T, bk)

    grid = (B, Hq, nq, nk)
    kernel = functools.partial(
        _flash_fwd_kernel,
        sm_scale=sm_scale,
        causal=causal,
        block_q=bq,
        block_k=bk,
        kv_len=T,
        q_offset=q_offset,
    )
    out, lse = pl.pallas_call(
        kernel,
        name="flash_attention",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, iq, ik: (b, h, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, S, D), q.dtype),
            jax.ShapeDtypeStruct((B, Hq, S, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
    return out, lse[..., 0]
