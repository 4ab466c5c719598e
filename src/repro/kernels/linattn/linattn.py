"""Pallas TPU kernel: chunked RWKV6 linear attention (GLA-style).

The sequential per-token recurrence is reformulated chunkwise: within a
chunk of C tokens all pairwise decay products are evaluated from the
in-chunk cumulative log-decay (a (C, C, D) broadcast whose exponents are
all <= 0, so no clamping and no overflow is possible -- see DESIGN.md for
why this beats the factored-matmul form numerically), and the (D, D)
recurrent state advances once per chunk in VMEM.  Grid = (BH, S/C),
sequential over chunks on TPU.

o_t = r_t . (S_{t-1} + diag(u) k_t^T v_t)
S_t = diag(w_t) S_{t-1} + k_t^T v_t
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret


def _kernel(r_ref, k_ref, v_ref, logw_ref, u_ref, o_ref, state_ref,
            s_vmem, *, C, D, nc):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        s_vmem[...] = jnp.zeros_like(s_vmem)

    r = r_ref[0].astype(jnp.float32)          # (C, D)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    logw = logw_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)          # (1, D) -> broadcast
    S0 = s_vmem[...]                          # (D, D)

    logA = jnp.cumsum(logw, axis=0)           # (C, D): sum_{s<=t} log w_s
    logA_prev = logA - logw                   # sum_{s<=t-1}

    # inter-chunk: o_t += (r_t * exp(logA_prev[t])) @ S0
    r_dec = r * jnp.exp(logA_prev)
    o = jax.lax.dot_general(r_dec, S0, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # intra-chunk (i < t): per-channel decay diff, exponents all <= 0
    diff = logA_prev[:, None, :] - logA[None, :, :]          # (C, C, D)
    tri = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0) > \
        jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    att = jnp.sum(r[:, None, :] * k[None, :, :] * jnp.exp(diff), axis=-1)
    att = jnp.where(tri, att, 0.0)                           # (C, C)
    o = o + jax.lax.dot_general(att, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)

    # current-token bonus: (r_t . (u * k_t)) v_t
    coeff = jnp.sum(r * u * k, axis=1, keepdims=True)        # (C, 1)
    o = o + coeff * v
    o_ref[0] = o.astype(o_ref.dtype)

    # state update: S = diag(prod w) S0 + sum_i diag(decay_i) k_i^T v_i
    decay_all = jnp.exp(logA[-1])                            # (D,)
    k_dec = k * jnp.exp(logA[-1][None, :] - logA)            # (C, D)
    s_vmem[...] = decay_all[:, None] * S0 + jax.lax.dot_general(
        k_dec, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(c == nc - 1)
    def _flush():
        state_ref[0] = s_vmem[...]


def rwkv_linattn_pallas(r, k, v, logw, u, *, chunk=64, interpret=None):
    """r,k,v,logw: (BH, S, D); u: (D,). Returns (out, final_state)."""
    BH, S, D = r.shape
    C = min(chunk, S)
    assert S % C == 0, (S, C)
    nc = S // C
    kern = functools.partial(_kernel, C=C, D=D, nc=nc)
    out, state = pl.pallas_call(
        kern,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, C, D), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, C, D), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, C, D), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, C, D), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, D), lambda b, c: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, C, D), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, D, D), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), r.dtype),
            jax.ShapeDtypeStruct((BH, D, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(r, k, v, logw, u[None, :])
    return out, state
