"""Pallas TPU flash attention (online softmax) for the LM wing.

Tiled attention with the canonical TPU grid layout: ``(batch·q_heads,
q_blocks, kv_blocks)`` with the KV dimension innermost so the running
max / denominator / accumulator live in VMEM scratch across KV steps.

Features needed by the assigned architectures:
  * causal masking                       (all decoder LMs)
  * sliding-window masking               (gemma2 local layers, recurrentgemma)
  * logit soft-capping ``t·tanh(x/t)``   (gemma2)
  * GQA/MQA — KV head = q_head // group, folded into the BlockSpec
    ``index_map`` so KV tensors are never materialized per-q-head.

VMEM budget per grid step: q (BQ·D) + k,v (2·BK·D) + acc (BQ·D) + onehot
masks — with BQ=BK=512, D=256 fp32 that is ~1.5 MiB, comfortably inside
the ~16 MiB/core VMEM of v5e with double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "DEFAULT_BLOCK_Q", "DEFAULT_BLOCK_K"]

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _kernel(
    q_ref,  # (1, BQ, D)
    k_ref,  # (1, BK, D)
    v_ref,  # (1, BK, D)
    o_ref,  # (1, BQ, D)
    m_scr,  # (BQ,) running max
    l_scr,  # (BQ,) running denominator
    acc_scr,  # (BQ, D) running numerator
    *,
    scale: float,
    causal: bool,
    window: int | None,
    softcap: float | None,
    block_q: int,
    block_k: int,
    kv_len: int,
):
    qb = pl.program_id(1)
    kb = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale
    k = k_ref[0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (BQ, BK)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    q_pos = qb * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = k_pos < kv_len  # padding guard
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= q_pos - k_pos < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    # Guard fully-masked rows: exp(NEG_INF - NEG_INF) would be 1.
    row_dead = m_cur <= NEG_INF / 2
    alpha = jnp.where(row_dead, 1.0, jnp.exp(m_prev - m_cur))
    p = jnp.exp(s - jnp.where(row_dead, 0.0, m_cur)[:, None])
    p = jnp.where(mask, p, 0.0)
    l_cur = alpha * l_scr[...] + jnp.sum(p, axis=1)
    acc = alpha[:, None] * acc_scr[...] + jnp.dot(
        p, v_ref[0].astype(jnp.float32), preferred_element_type=jnp.float32
    )
    m_scr[...] = m_cur
    l_scr[...] = l_cur
    acc_scr[...] = acc

    @pl.when(kb == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, :, :] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal",
        "window",
        "softcap",
        "scale",
        "block_q",
        "block_k",
        "interpret",
    ),
)
def flash_attention(
    q: jax.Array,  # (B, Hq, Sq, D)
    k: jax.Array,  # (B, Hkv, Sk, D)
    v: jax.Array,  # (B, Hkv, Sk, D)
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    scale: float | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool | None = None,
) -> jax.Array:
    """Tiled online-softmax attention. Returns (B, Hq, Sq, D).

    GQA: ``Hq`` must be a multiple of ``Hkv``; KV blocks are indexed at
    ``head // group`` inside the BlockSpec index_map (no KV repetition in
    HBM or VMEM).

    ``interpret=None`` auto-selects like every other kernel in this
    package: compiled on TPU, interpret-mode on CPU (see
    :func:`repro.kernels.dsss_spmv.default_interpret`). ``interpret`` is
    a static jit arg, so the resolution happens at trace time.
    """
    if interpret is None:
        from repro.kernels.dsss_spmv import default_interpret

        interpret = default_interpret()
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert hq % hkv == 0, "q heads must be a multiple of kv heads"
    group = hq // hkv
    if scale is None:
        scale = d**-0.5
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    sq_pad = -(-sq // block_q) * block_q
    sk_pad = -(-sk // block_k) * block_k
    if sq_pad != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_pad - sq), (0, 0)))
    if sk_pad != sk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, sk_pad - sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, sk_pad - sk), (0, 0)))
    qf = q.reshape(b * hq, sq_pad, d)
    kf = k.reshape(b * hkv, sk_pad, d)
    vf = v.reshape(b * hkv, sk_pad, d)
    grid = (b * hq, sq_pad // block_q, sk_pad // block_k)

    def kv_index(h, qb, kb):
        # GQA indirection: flatten (batch, q_head) -> (batch, kv_head).
        return ((h // hq) * hkv + (h % hq) // group, kb, 0)

    out = pl.pallas_call(
        functools.partial(
            _kernel,
            scale=scale,
            causal=causal,
            window=window,
            softcap=softcap,
            block_q=block_q,
            block_k=block_k,
            kv_len=sk,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda h, qb, kb: (h, qb, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda h, qb, kb: (h, qb, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq_pad, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, hq, sq_pad, d)[:, :, :sq, :]
