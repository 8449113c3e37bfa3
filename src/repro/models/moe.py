"""Mixture-of-Experts layer: shared + routed top-k, sort-based dispatch.

NXgraph-technique note (DESIGN.md §Arch-applicability): token→expert
dispatch is a bipartite graph update. We dispatch by *sorting the
(token, expert) assignments by expert id* — the exact analogue of the
paper's destination-sorted edges — so each expert's tokens are a
contiguous block and the per-expert matmul is a dense, conflict-free
"sub-shard update". Capacity-factor dropping bounds the block size the
way the paper's interval partitioning bounds sub-shard working sets.

Experts are padded to a multiple of 16 for EP divisibility (qwen2-moe:
60→64); dummy experts have zero weights and the router never emits them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import dense_init, mlp_apply, mlp_init
from repro.sharding.rules import maybe_constrain

__all__ = ["moe_init", "moe_apply"]


def moe_init(key, cfg: ModelConfig, dtype=jnp.float32):
    m = cfg.moe
    d = cfg.d_model
    e_pad = m.num_experts_padded
    ks = jax.random.split(key, 5)
    p: dict = {
        "router": dense_init(ks[0], (d, m.num_experts), dtype=jnp.float32),
        # routed experts: fused [gate; up] then down, stacked on expert axis
        "wi": dense_init(ks[1], (e_pad, d, 2 * m.expert_ff), fan_in=d, dtype=dtype),
        "wo": dense_init(
            ks[2], (e_pad, m.expert_ff, d), fan_in=m.expert_ff, dtype=dtype
        ),
    }
    if m.num_experts != e_pad:
        # zero the dummy experts so padding is inert even if ever hit
        mask = (jnp.arange(e_pad) < m.num_experts).astype(dtype)
        p["wi"] = p["wi"] * mask[:, None, None]
        p["wo"] = p["wo"] * mask[:, None, None]
    if m.shared_ff:
        p["shared"] = mlp_init(ks[3], d, m.shared_ff, cfg.activation, dtype)
    return p


DENSE_PATH_MAX_TOKENS = 256  # below this, run the exact dropless path


def moe_apply(params, x, cfg: ModelConfig, *, return_aux: bool = True):
    """x: (B, S, D) -> (y, aux). aux carries the load-balancing loss.

    Two compute paths:
      * T > DENSE_PATH_MAX_TOKENS — sort-based capacity dispatch (training /
        long prefill; GShard-style, may drop overflow tokens).
      * T ≤ DENSE_PATH_MAX_TOKENS — dense all-experts einsum (decode / short
        prefill): exact and dropless, so prefill↔decode are consistent.
        At decode T the all-experts overcompute is cheaper than dispatch.
    """
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    e, e_pad, k = m.num_experts, m.num_experts_padded, m.top_k
    xf = x.reshape(t, d)
    dtype = x.dtype

    # Router in fp32 (standard practice: routing decisions are precision-
    # sensitive). Softmax over real experts only.
    logits = xf.astype(jnp.float32) @ params["router"]
    if m.router_softcap:
        logits = m.router_softcap * jnp.tanh(logits / m.router_softcap)
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)  # (T, k)

    if t <= DENSE_PATH_MAX_TOKENS:
        return _moe_dense_path(
            params, xf, cfg, probs, gate_vals, expert_ids, (b, s, d), return_aux
        )

    from repro.sharding.rules import active_mesh, active_rules

    mesh = active_mesh()
    rules = active_rules()
    if mesh is not None and rules.get("experts") == ():
        # FSDP/no-EP profile: dispatch must stay LOCAL per batch shard —
        # under pjit the data-dependent dispatch scatter gets fully
        # replicated (measured: 357 GB temp + 5.8 TB collectives on
        # deepseek train). shard_map makes per-shard locality explicit:
        # gather expert weights (the normal FSDP all-gather), route only
        # local tokens, zero MoE-specific collectives. This is the paper's
        # locality argument applied to the token->expert bipartite graph.
        return _moe_fsdp_local(params, x, cfg, mesh, rules, return_aux)

    # --- destination-sorted dispatch (the DSSS idea on the token-expert
    # bipartite graph): sort assignments by expert, slot into (E, C). ---
    cap = int(max(1, min(t, t * k * m.capacity_factor / e_pad)))
    flat_e = expert_ids.reshape(-1)  # (T·k,)
    order = jnp.argsort(flat_e)  # stable: preserves token order per expert
    sorted_e = flat_e[order]
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(e_pad))
    pos_in_e = jnp.arange(t * k) - seg_start[sorted_e]
    keep = pos_in_e < cap
    slot = jnp.where(keep, sorted_e * cap + pos_in_e, e_pad * cap)  # drop -> OOB
    token_of = order // k
    x_disp = jnp.zeros((e_pad * cap, d), dtype)
    x_disp = x_disp.at[slot].set(xf[token_of], mode="drop")
    x_disp = x_disp.reshape(e_pad, cap, d)
    x_disp = maybe_constrain(x_disp, "experts", None, None)

    # per-expert fused-gated MLP ("sub-shard update": dense block matmul)
    wi = params["wi"].astype(dtype)
    wo = params["wo"].astype(dtype)
    h = jnp.einsum("ecd,edf->ecf", x_disp, wi)
    h = maybe_constrain(h, "experts", None, None)
    gate, up = jnp.split(h, 2, axis=-1)
    act = jax.nn.silu if cfg.activation == "silu" else jax.nn.gelu
    hh = act(gate) * up
    y_disp = jnp.einsum("ecf,efd->ecd", hh, wo).reshape(e_pad * cap, d)

    # combine: gather back and weight by gate values
    gathered = y_disp.at[slot].get(mode="fill", fill_value=0)  # (T·k, d)
    # gate_vals is token-major; index i here runs in SORTED order — permute
    # the gates through `order` or every token gets another token's gate
    # (regression-tested against the dense path in tests).
    w = (gate_vals.reshape(-1)[order] * keep).astype(dtype)
    contrib = gathered * w[:, None]
    y = jax.ops.segment_sum(contrib, token_of, num_segments=t).astype(dtype)

    if m.shared_ff:
        y = y + mlp_apply(params["shared"], xf, cfg.activation)
    y = y.reshape(b, s, d)

    aux = {}
    if return_aux:
        # GShard/Switch load-balance loss: E · Σ_e f_e · p_e.
        me = probs.mean(axis=0)  # (E,)
        one_hot = jax.nn.one_hot(expert_ids, e, dtype=jnp.float32)
        ce = one_hot.sum(axis=(0, 1)) / (t * k)
        aux["load_balance_loss"] = e * jnp.sum(me * ce)
        aux["dropped_fraction"] = 1.0 - keep.mean()
    return y, aux


def _sorted_dispatch_compute(xf, probs, gate_vals, expert_ids, wi, wo, cfg):
    """Core destination-sorted dispatch + expert matmuls on LOCAL arrays.

    xf: (T, d); wi/wo: full (E_pad, ...) expert weights. Returns (y (T, d),
    dropped_fraction). Pure function of local data — used by both the pjit
    path (global arrays) and the shard_map FSDP path (per-shard arrays).
    """
    m = cfg.moe
    t, d = xf.shape
    e_pad, k = m.num_experts_padded, m.top_k
    dtype = xf.dtype
    cap = int(max(1, min(t, t * k * m.capacity_factor / e_pad)))
    flat_e = expert_ids.reshape(-1)
    order = jnp.argsort(flat_e)
    sorted_e = flat_e[order]
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(e_pad))
    pos_in_e = jnp.arange(t * k) - seg_start[sorted_e]
    keep = pos_in_e < cap
    slot = jnp.where(keep, sorted_e * cap + pos_in_e, e_pad * cap)
    token_of = order // k
    x_disp = jnp.zeros((e_pad * cap, d), dtype)
    x_disp = x_disp.at[slot].set(xf[token_of], mode="drop")
    x_disp = x_disp.reshape(e_pad, cap, d)
    h = jnp.einsum("ecd,edf->ecf", x_disp, wi.astype(dtype))
    gate, up = jnp.split(h, 2, axis=-1)
    act = jax.nn.silu if cfg.activation == "silu" else jax.nn.gelu
    hh = act(gate) * up
    y_disp = jnp.einsum("ecf,efd->ecd", hh, wo.astype(dtype)).reshape(
        e_pad * cap, d
    )
    gathered = y_disp.at[slot].get(mode="fill", fill_value=0)
    # token-major gates -> sorted order (see note in moe_apply)
    w = (gate_vals.reshape(-1)[order] * keep).astype(dtype)
    contrib = gathered * w[:, None]
    y = jax.ops.segment_sum(contrib, token_of, num_segments=t).astype(dtype)
    return y, 1.0 - keep.mean()


def _moe_fsdp_local(params, x, cfg: ModelConfig, mesh, rules, return_aux):
    """shard_map MoE for the FSDP/no-EP profile: local dispatch per shard."""
    from jax.sharding import PartitionSpec as P

    from repro.sharding.rules import spec_for

    m = cfg.moe
    b, s, d = x.shape
    e = m.num_experts
    x_spec = spec_for(("batch", "seq", None), (b, s, d), mesh, rules)
    router_spec = spec_for(("embed", None), params["router"].shape, mesh, rules)
    wi_spec = spec_for(
        ("experts", "embed", "expert_mlp"), params["wi"].shape, mesh, rules
    )
    wo_spec = spec_for(
        ("experts", "expert_mlp", "embed"), params["wo"].shape, mesh, rules
    )
    has_shared = bool(m.shared_ff)
    if has_shared:
        swi_spec = spec_for(("embed", "mlp"), params["shared"]["wi"].shape, mesh, rules)
        swo_spec = spec_for(("mlp", "embed"), params["shared"]["wo"].shape, mesh, rules)
    all_axes = tuple(mesh.shape.keys())

    def _gather_full(w, spec):
        """Explicit FSDP all-gather of a weight shard (bf16 on the wire)."""
        for dim, entry in enumerate(spec):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            for ax in reversed(axes):
                w = jax.lax.all_gather(w, ax, axis=dim, tiled=True)
        return w

    def body(xb, router, wi, wo, *shared):
        from repro.sharding.rules import suppress_constraints

        with suppress_constraints():
            return _body_inner(xb, router, wi, wo, *shared)

    def _body_inner(xb, router, wi, wo, *shared):
        bl, sl, _ = xb.shape
        xf = xb.reshape(bl * sl, d)
        router_f = _gather_full(router, router_spec).astype(jnp.float32)
        wi_f = _gather_full(wi.astype(xb.dtype), wi_spec)
        wo_f = _gather_full(wo.astype(xb.dtype), wo_spec)
        logits = xf.astype(jnp.float32) @ router_f
        if m.router_softcap:
            logits = m.router_softcap * jnp.tanh(logits / m.router_softcap)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_ids = jax.lax.top_k(probs, m.top_k)
        y, dropped = _sorted_dispatch_compute(
            xf, probs, gate_vals, expert_ids, wi_f, wo_f, cfg
        )
        if has_shared:
            swi = _gather_full(shared[0].astype(xb.dtype), swi_spec)
            swo = _gather_full(shared[1].astype(xb.dtype), swo_spec)
            y = y + mlp_apply({"wi": swi, "wo": swo}, xf, cfg.activation)
        # aux scalars: psum over every axis -> replicated
        me = probs.mean(axis=0)
        oh = jax.nn.one_hot(expert_ids, e, dtype=jnp.float32)
        ce = oh.sum(axis=(0, 1)) / (xf.shape[0] * m.top_k)
        lbl = jax.lax.pmean(e * jnp.sum(me * ce), all_axes)
        dropped = jax.lax.pmean(dropped, all_axes)
        return y.reshape(bl, sl, d), lbl, dropped

    in_specs = [x_spec, router_spec, wi_spec, wo_spec]
    args = [x, params["router"], params["wi"], params["wo"]]
    if has_shared:
        in_specs += [swi_spec, swo_spec]
        args += [params["shared"]["wi"], params["shared"]["wo"]]
    y, lbl, dropped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(x_spec, P(), P()),
        check_vma=False,
    )(*args)
    aux = (
        {"load_balance_loss": lbl, "dropped_fraction": dropped}
        if return_aux
        else {}
    )
    return y, aux


def _moe_dense_path(params, xf, cfg, probs, gate_vals, expert_ids, bsd, return_aux):
    """Exact dropless path: every expert runs on every token, combined by the
    (sparse) top-k gate matrix. O(T·E·F) compute — only used for small T."""
    m = cfg.moe
    b, s, d = bsd
    t, e = probs.shape
    e_pad = m.num_experts_padded
    dtype = xf.dtype
    # (T, E_pad) combine weights: gate value where expert is in top-k, else 0.
    onehot = jax.nn.one_hot(expert_ids, e_pad, dtype=jnp.float32)  # (T,k,Ep)
    combine = jnp.einsum("tk,tke->te", gate_vals, onehot).astype(dtype)
    wi = params["wi"].astype(dtype)
    wo = params["wo"].astype(dtype)
    h = jnp.einsum("td,edf->tef", xf, wi)
    gate, up = jnp.split(h, 2, axis=-1)
    act = jax.nn.silu if cfg.activation == "silu" else jax.nn.gelu
    hh = act(gate) * up
    y_e = jnp.einsum("tef,efd->ted", hh, wo)
    y = jnp.einsum("ted,te->td", y_e, combine)
    if m.shared_ff:
        y = y + mlp_apply(params["shared"], xf, cfg.activation)
    aux = {}
    if return_aux:
        me = probs.mean(axis=0)
        oh = jax.nn.one_hot(expert_ids, e, dtype=jnp.float32)
        ce = oh.sum(axis=(0, 1)) / (t * m.top_k)
        aux["load_balance_loss"] = e * jnp.sum(me * ce)
        aux["dropped_fraction"] = jnp.zeros((), jnp.float32)
    return y.reshape(b, s, d), aux
