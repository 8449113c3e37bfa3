"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

These are the ground truth the kernels are swept against in
tests/test_kernels_*.py and tests/test_packed_kernel_property.py (shape ×
dtype × feature sweeps). The kernels themselves resolve ``interpret``
via :func:`repro.kernels.dsss_spmv.default_interpret` — compiled on TPU,
interpret-mode on CPU, which is how the sweeps execute them in CI.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["subshard_update_ref", "attention_ref", "packed_sweep_update_ref"]


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


def packed_sweep_update_ref(
    program,
    attrs_flat: jax.Array,  # (K, n_pad)
    acc_flat: jax.Array,  # (K, n_pad)
    aux: dict,
    tiles: dict,  # (NT, ...) PackedSweep tile leaves
    row_active: jax.Array,  # (P,) bool
    has_weights: bool,
    aux_batched: bool = False,
) -> jax.Array:
    """Reference fused sweep: per-tile gather → combine → segment-reduce
    by ``run_local`` → scatter-fold at ``run_dst``.

    Plain Python loops over tiles and queries with ``jax.ops.segment_*``
    and in-order ``.at[]`` scatters — the exact fold-order semantics
    :func:`repro.kernels.packed_sweep.packed_sweep_update` must reproduce
    *bitwise* (XLA applies duplicate scatter updates in ascending
    position order, pinning the float-sum association).
    """
    from repro.core.identities import reduce_identity

    K, n_pad = attrs_flat.shape
    NT, T = tiles["src"].shape
    P = row_active.shape[0]
    vert_active = jnp.repeat(
        row_active, n_pad // P, total_repeat_length=n_pad
    )
    acc = acc_flat
    for t in range(NT):
        src = tiles["src"][t]
        dst = tiles["dst"][t]
        run = tiles["run_local"][t]
        run_dst = tiles["run_dst"][t]
        w = tiles["weights"][t] if has_weights else None
        mask = (jnp.arange(T) < tiles["e_valid"][t]) & vert_active[src]
        rows = []
        for q in range(K):
            auxq = {
                k: (v[q] if aux_batched else v) for k, v in aux.items()
            }
            s_aux = {
                k: (v[src] if getattr(v, "ndim", 0) == 1 else v)
                for k, v in auxq.items()
            }
            d_aux = (
                {
                    k: (v[dst] if getattr(v, "ndim", 0) == 1 else v)
                    for k, v in auxq.items()
                }
                if program.needs_dst_aux
                else None
            )
            contrib = program.gather(attrs_flat[q][src], w, s_aux, d_aux)
            ident = reduce_identity(program.reduce, contrib.dtype)
            contrib = jnp.where(mask, contrib, ident)
            aq = acc[q]
            if program.reduce == "sum":
                red = jax.ops.segment_sum(contrib, run, num_segments=T)
                aq = aq.at[run_dst].add(red.astype(aq.dtype), mode="drop")
            elif program.reduce == "min":
                red = jax.ops.segment_min(contrib, run, num_segments=T)
                aq = aq.at[run_dst].min(red.astype(aq.dtype), mode="drop")
            else:
                red = jax.ops.segment_max(contrib, run, num_segments=T)
                aq = aq.at[run_dst].max(red.astype(aq.dtype), mode="drop")
            rows.append(aq)
        acc = jnp.stack(rows)
    return acc


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
