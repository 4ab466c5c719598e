"""Pallas TPU kernel: blockwise causal/windowed flash attention.

Grid (BH, num_q_blocks, num_kv_blocks), kv innermost (sequential on TPU);
online-softmax running state (m, l, acc) lives in VMEM scratch across the
kv sweep; fully-masked kv blocks (future blocks under causality, blocks
left of the sliding window) are skipped with ``pl.when`` so they cost
neither MXU time nor VPU time.  Block shapes are multiples of (8, 128)
MXU/VREG tiling when S and D are (pad upstream otherwise).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_vmem, l_vmem, acc_vmem,
            *, scale, causal, window, bq, bk, nk):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_vmem[...] = jnp.full_like(m_vmem, NEG_INF)
        l_vmem[...] = jnp.zeros_like(l_vmem)
        acc_vmem[...] = jnp.zeros_like(acc_vmem)

    needed = jnp.asarray(True)
    if causal:
        needed &= kj * bk <= qi * bq + (bq - 1)
    if window is not None:
        needed &= (kj + 1) * bk - 1 > qi * bq - window

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale            # (bq, d)
        k = k_ref[0].astype(jnp.float32)                    # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        qp = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kp = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= qp >= kp
        if window is not None:
            mask &= qp - kp < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_vmem[...]                                # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_vmem[...] = l_vmem[...] * corr + p.sum(axis=1, keepdims=True)
        acc_vmem[...] = acc_vmem[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_vmem[...] = m_new

    @pl.when(kj == nk - 1)
    def _flush():
        o_ref[0] = (acc_vmem[...] /
                    jnp.maximum(l_vmem[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal=True, window=None, scale=None,
                           block_q=512, block_k=512, interpret=None):
    """q,k,v: (BH, S, D) with kv pre-expanded to H heads. Returns (BH,S,D)."""
    BH, S, D = q.shape
    Skv = k.shape[1]
    scale = scale if scale is not None else D ** -0.5
    bq, bk = min(block_q, S), min(block_k, Skv)
    assert S % bq == 0 and Skv % bk == 0, (S, bq, Skv, bk)
    nq, nk = S // bq, Skv // bk
    kern = functools.partial(_kernel, scale=float(scale), causal=causal,
                             window=window, bq=bq, bk=bk, nk=nk)
    return pl.pallas_call(
        kern,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
