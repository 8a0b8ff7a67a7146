"""Flash attention for TPU (Pallas): online-softmax over KV blocks.

Grid (BH, NQ, NK): each (batch*q-head, q-block) pair streams KV blocks
through VMEM, carrying the running (max, sum, acc) in scratch — scores
never materialize beyond [bq, bk].  GQA is handled in the k/v BlockSpec
index maps (kv head = q head // group), so kv blocks are fetched once
per group from HBM, not replicated by the caller.

MXU alignment: bq/bk default 512/512 and head_dim should be a multiple
of 128 (the assigned archs use 128/192/256).  f32 accumulation
throughout; inputs may be bf16.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_interpret

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, bq: int, bk: int, nk: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                   # [bq, hd]
    k = k_ref[0].astype(jnp.float32)                   # [bk, hd]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    if causal:
        i = pl.program_id(1)
        qi = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kj = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(kj <= qi, s, NEG_INF)

    m_prev = m_ref[...]                                # [bq]
    m_cur = jnp.maximum(m_prev, s.max(axis=-1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])                    # [bq, bk]
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
    v = v_ref[0].astype(jnp.float32)                   # [bk, hd]
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_cur

    @pl.when(j == nk - 1)
    def _done():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, bq: int = 512,
                    bk: int = 512, interpret: bool | None = None):
    """q: [BH, S, hd]; k/v: [BKV, T, hd] with BH = BKV * group.

    Returns [BH, S, hd].  S % bq == 0 and T % bk == 0 (pad upstream).
    """
    bh, s, hd = q.shape
    bkv, t, _ = k.shape
    assert bh % bkv == 0, (bh, bkv)
    g = bh // bkv
    bq = min(bq, s)
    bk = min(bk, t)
    assert s % bq == 0 and t % bk == 0, (s, bq, t, bk)
    nq, nk = s // bq, t // bk
    scale = 1.0 / math.sqrt(hd)

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j, g=g: (b // g, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j, g=g: (b // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),       # running max
            pltpu.VMEM((bq,), jnp.float32),       # running sum
            pltpu.VMEM((bq, hd), jnp.float32),    # output accumulator
        ],
        interpret=pallas_interpret(interpret),
    )(q, k, v)


def flash_gqa(q, k, v, *, causal: bool = True, interpret: bool | None = None,
              bq: int = 512, bk: int = 512):
    """Convenience wrapper for model-layout tensors.

    q: [B, S, H, hd]; k/v: [B, T, KV, hd] -> [B, S, H, hd].
    Heads are grouped kv-major (head h uses kv head h // (H // KV)),
    matching ``repro.models.attention._sdpa``.
    """
    b, s, h, hd = q.shape
    t, kv = k.shape[1], k.shape[2]
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kv, t, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kv, t, hd)
    out = flash_attention(qf, kf, vf, causal=causal, bq=bq, bk=bk,
                          interpret=interpret)
    return out.reshape(b, h, s, hd).transpose(0, 2, 1, 3)
