"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

These are the ground truth the kernels are swept against in
tests/test_kernels_*.py (shape × dtype × feature sweeps). The kernels themselves resolve ``interpret``
via :func:`repro.kernels.dsss_spmv.default_interpret` — compiled on TPU,
interpret-mode on CPU, which is how the sweeps execute them in CI.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["subshard_update_ref", "attention_ref"]


def subshard_update_ref(
    src_vals: jax.Array,  # (isize,)
    src_idx: jax.Array,  # (e,) int32
    hub_inv: jax.Array,  # (e,) int32 global hub slots
    weights: jax.Array,  # (e,)
    num_slots: int,
    *,
    gather_op: str = "mul",
    reduce: str = "sum",
) -> jax.Array:
    """Reference ToHub: gather + combine + segment-reduce by hub slot."""
    vals = src_vals[src_idx]
    contrib = vals * weights if gather_op == "mul" else vals + weights
    if reduce == "sum":
        return jax.ops.segment_sum(contrib, hub_inv, num_segments=num_slots)
    if reduce == "min":
        return jax.ops.segment_min(contrib, hub_inv, num_segments=num_slots)
    return jax.ops.segment_max(contrib, hub_inv, num_segments=num_slots)


def attention_ref(
    q: jax.Array,  # (B, Hq, Sq, D)
    k: jax.Array,  # (B, Hkv, Sk, D)
    v: jax.Array,  # (B, Hkv, Sk, D)
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    scale: float | None = None,
) -> jax.Array:
    """Naive fp32 softmax attention with the same masking semantics."""
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    group = hq // hkv
    if scale is None:
        scale = d**-0.5
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32) * scale, k.astype(jnp.float32)
    )
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    q_pos = jnp.arange(sq)[:, None]
    k_pos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= q_pos - k_pos < window
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)
