"""Fine-grained Mixture-of-Experts (DeepSeekMoE style).

n shared experts always active + E routed experts with top-k softmax
gating.  Dispatch is capacity-limited scatter/gather (Mesh-TF positions
via cumsum) — no (T, E, C) one-hot is ever materialised, so the layer
scales to 10^6 tokens; experts shard over the "model" mesh axis (EP) and
tokens over ("pod","data") (DP), with XLA SPMD inserting the all-to-all
at the dispatch boundary.

Aux losses: load-balance (Switch-style) + router-z, returned as metrics.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import logical_constraint
from repro.models.common import ParamSpec
from repro.models import mlp as mlp_mod


def moe_specs(cfg, stacked: int | None) -> dict:
    lead = (stacked,) if stacked else ()
    lx = ("layers",) if stacked else ()
    D, E, Fe = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    out = {
        "router": ParamSpec(lead + (D, E), lx + ("embed", None), scale=0.1),
        "w_gate": ParamSpec(lead + (E, D, Fe), lx + ("experts", "embed", "expert_mlp")),
        "w_up": ParamSpec(lead + (E, D, Fe), lx + ("experts", "embed", "expert_mlp")),
        "w_down": ParamSpec(lead + (E, Fe, D), lx + ("experts", "expert_mlp", "embed")),
    }
    if cfg.n_shared_experts:
        fs = cfg.moe_d_ff * cfg.n_shared_experts
        out["shared"] = mlp_mod.mlp_specs("swiglu", D, fs, stacked)
    return out


def _capacity(n_tokens: int, cfg) -> int:
    cap = int(n_tokens * cfg.moe_top_k * cfg.capacity_factor / cfg.n_experts)
    return max(cap, cfg.moe_top_k)


def moe_apply_shard_map(cfg, p: dict, x: jax.Array) -> Tuple[jax.Array, dict]:
    """Explicit-collective MoE: manual mesh axes for the dispatch.

    Auto-SPMD lowers the scatter-add dispatch to an all-reduce of the
    whole (E*C, D) buffer (~2x30 GB/layer on deepseek-moe; EXPERIMENTS.md
    §Perf A-series).  Here the dispatch runs inside shard_map:

      * each (pod,data) shard builds its LOCAL (E, Cl, D) capacity slice
        (positions are shard-local prefix sums — free);
      * each "model" shard all-gathers ONLY ITS E/|model| experts' slices
        over (pod,data)  -> (E_loc, G*Cl, D): ~1.9 GB/layer;
      * expert FFNs run non-replicated on the expert owner;
      * combine all-gathers each token group's OWN capacity slice over
        "model" -> (E, Cl, D): ~1.0 GB/layer.

    ~3 GB/layer of all-gather replaces ~60 GB/layer of all-reduce.
    Activated via cfg.moe_dispatch == "shard_map" when a mesh is active.
    """
    from repro.distributed.sharding import active_rules
    from jax.sharding import PartitionSpec as P

    rules = active_rules()
    mesh = rules.mesh
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.moe_top_k
    T = B * S
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    G = 1
    for a in dp_axes:
        G *= mesh.shape[a]
    n_model = mesh.shape.get("model", 1)
    if T % G or E % n_model:
        return moe_apply_scatter(cfg, p, x)  # fallback: shapes don't tile
    Cl = max(-(-_capacity(T, cfg) // G), K)
    E_loc = E // n_model

    def body(xt, router, wg, wu, wd):
        # xt: (Tl, D) local tokens (replicated over "model")
        Tl = xt.shape[0]
        logits = xt.astype(jnp.float32) @ router.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_ids = jax.lax.top_k(probs, K)
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
        flat_e = expert_ids.T.reshape(Tl * K)          # k-major
        eq = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
        pos = jnp.take_along_axis(jnp.cumsum(eq, 0) - eq,
                                  flat_e[:, None], 1)[:, 0]
        keep = pos < Cl
        slot = jnp.where(keep, flat_e * Cl + pos, E * Cl)
        token_of = jnp.tile(jnp.arange(Tl), K)
        buf = jnp.zeros((E * Cl + 1, D), x.dtype).at[slot].add(
            jnp.where(keep[:, None], xt[token_of], 0))
        buf = buf[:-1].reshape(E, Cl, D)

        # my experts' slices from every token group: (E_loc, G*Cl, D)
        me = jax.lax.axis_index("model")
        mine = jax.lax.dynamic_slice_in_dim(buf, me * E_loc, E_loc, 0)
        gathered = jax.lax.all_gather(mine, dp_axes, axis=1, tiled=True)

        h = jnp.einsum("ecd,edf->ecf", gathered, wg.astype(x.dtype))
        u = jnp.einsum("ecd,edf->ecf", gathered, wu.astype(x.dtype))
        eo = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u,
                        wd.astype(x.dtype))              # (E_loc, G*Cl, D)

        # my token group's slice from every expert owner: (E, Cl, D)
        g_lin = jnp.int32(0)
        for a in dp_axes:
            g_lin = g_lin * jax.lax.axis_size(a) + jax.lax.axis_index(a)
        my_slice = jax.lax.dynamic_slice_in_dim(
            eo.reshape(E_loc, G, Cl, D).transpose(1, 0, 2, 3),  # (G,E_loc,Cl,D)
            g_lin, 1, 0)[0]                                     # (E_loc, Cl, D)
        eo_all = jax.lax.all_gather(my_slice, "model", axis=0,
                                    tiled=True)                 # (E, Cl, D)

        picked = eo_all.reshape(E * Cl, D)[jnp.minimum(slot, E * Cl - 1)]
        contrib = jnp.where(keep[:, None],
                            picked * gate_vals.T.reshape(-1)[:, None].astype(x.dtype), 0)
        out = jnp.zeros((Tl, D), x.dtype).at[token_of].add(contrib)

        me_probs = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(expert_ids, E).sum(1), axis=0)
        stats = jnp.stack([E * jnp.sum(me_probs * ce) / K,
                           jnp.mean(jax.nn.logsumexp(logits, -1) ** 2),
                           1.0 - jnp.mean(keep.astype(jnp.float32))])
        stats = jax.lax.pmean(stats, dp_axes + ("model",))
        return out, stats

    tok_spec = P(dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None))
    out, stats = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(tok_spec[0], None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(tok_spec[0], None), P()),
        check_vma=False,
    )(x.reshape(T, D), p["router"], p["w_gate"], p["w_up"], p["w_down"])

    out = out.reshape(B, S, D)
    if cfg.n_shared_experts:
        out = out + mlp_mod.mlp_apply("swiglu", p["shared"],
                                      x.reshape(T, D)).reshape(B, S, D)
    metrics = {"moe_lb_loss": stats[0], "moe_z_loss": stats[1],
               "moe_drop_frac": stats[2]}
    return out, metrics


def moe_apply(cfg, p: dict, x: jax.Array) -> Tuple[jax.Array, dict]:
    """x: [B,S,D] -> (out [B,S,D], metrics). Dispatch-mode switch."""
    from repro.distributed.sharding import active_rules
    if (getattr(cfg, "moe_dispatch", "scatter") == "shard_map"
            and active_rules() is not None):
        return moe_apply_shard_map(cfg, p, x)
    return moe_apply_scatter(cfg, p, x)


def moe_apply_scatter(cfg, p: dict, x: jax.Array) -> Tuple[jax.Array, dict]:
    """x: [B,S,D] -> (out [B,S,D], metrics)."""
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.moe_top_k
    T = B * S
    C = _capacity(T, cfg)
    # Token groups = data shards: the position-in-expert prefix sums run
    # WITHIN a group, so they are shard-local (no cross-device scan), and
    # each group owns its own capacity slice of every expert — per-shard
    # capacity quotas, the standard SPMD dropping semantics.
    G = cfg.moe_groups if (cfg.moe_groups and T % cfg.moe_groups == 0
                           and T >= cfg.moe_groups * K) else 1
    Tg = T // G
    Cg = max(-(-C // G), K)
    xt = logical_constraint(x.reshape(T, D), ("tokens", None))

    logits = (xt.astype(jnp.float32) @ p["router"].astype(jnp.float32))  # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, K)                      # (T,K)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # --- per-group capacity-limited positions (k=0 slots first) ---
    flat_e = expert_ids.reshape(G, Tg, K).transpose(0, 2, 1).reshape(G, K * Tg)
    eq = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)           # (G, KTg, E)
    eq = logical_constraint(eq, ("tokens", None, None))
    pos_in_e = jnp.cumsum(eq, axis=1) - eq                    # local prefix
    pos = jnp.take_along_axis(pos_in_e, flat_e[..., None], axis=2)[..., 0]
    keep = pos < Cg
    g_idx = jnp.arange(G, dtype=jnp.int32)[:, None]
    # Expert-major slot layout with per-group capacity slices.  (A group-
    # major layout + transpose-reshard was tried to turn the dispatch into
    # a pure all-to-all, but XLA lowered the resharding transposes into
    # collective-permute storms 2.5x worse — see EXPERIMENTS.md §Perf A3;
    # the explicit shard_map all-to-all dispatch is the documented next
    # step.)
    slot = jnp.where(keep, flat_e * (G * Cg) + g_idx * Cg + pos, E * G * Cg)

    # token id (global) of each (group, k, t') dispatch entry
    token_of = (g_idx * Tg + jnp.tile(jnp.arange(Tg), K)[None]).reshape(-1)
    slot = slot.reshape(-1)
    keep = keep.reshape(-1)
    gathered = logical_constraint(
        jnp.where(keep[:, None], xt[token_of], 0), ("tokens", None))
    xin = jnp.zeros((E * G * Cg + 1, D), x.dtype).at[slot].add(gathered)
    expert_in = logical_constraint(xin[:-1].reshape(E, G * Cg, D),
                                   ("experts", None, None))

    h = jnp.einsum("ecd,edf->ecf", expert_in, p["w_gate"].astype(x.dtype))
    u = jnp.einsum("ecd,edf->ecf", expert_in, p["w_up"].astype(x.dtype))
    eo = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u,
                    p["w_down"].astype(x.dtype))
    eo = logical_constraint(eo, ("experts", None, None))

    flat_gate = gate_vals.reshape(G, Tg, K).transpose(0, 2, 1).reshape(-1)
    picked = eo.reshape(E * G * Cg, D)[jnp.minimum(slot, E * G * Cg - 1)]
    contrib = jnp.where(keep[:, None], picked * flat_gate[:, None].astype(x.dtype), 0)
    contrib = logical_constraint(contrib, ("tokens", None))
    out = jnp.zeros((T, D), x.dtype).at[token_of].add(contrib)

    if cfg.n_shared_experts:
        out = out + mlp_mod.mlp_apply("swiglu", p["shared"], xt)

    # --- aux metrics ---
    me = jnp.mean(probs, axis=0)                              # router mass
    ce = jnp.mean(jax.nn.one_hot(expert_ids, E).sum(1), axis=0)  # pick rate
    lb_loss = E * jnp.sum(me * ce) / K
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    dropped = 1.0 - jnp.mean(keep.astype(jnp.float32))
    metrics = {"moe_lb_loss": lb_loss, "moe_z_loss": z_loss,
               "moe_drop_frac": dropped}
    return out.reshape(B, S, D), metrics
