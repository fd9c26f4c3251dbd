"""Mixture-of-Experts FFN: top-k routing with capacity-based gather dispatch.

Dispatch is sort-based (argsort by expert id -> capacity buckets -> gather),
so expert compute is a single batched einsum of shape [E, C, *] with
C = T * top_k * capacity_factor / E -- i.e. the compiled FLOPs equal the
*active* expert compute (correct 6*N_active*D roofline accounting), unlike a
dense all-experts evaluation.  Overflowing tokens are dropped (standard
capacity semantics) and their combine weight is zero.

Telemetry: returns the (expert, token-bucket) modularity-2 key stream for
the MOD-Sketch routing monitor (streams/ngram.py; DESIGN.md S2) -- few
experts x many buckets is exactly the asymmetric-marginal regime of Thm 3.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from jax import shard_map
from repro.configs.base import ModelConfig
from repro.models.layers import dense_init
from repro.models.shard_ctx import DP, MP, constrain


def make_moe_params(cfg: ModelConfig, key) -> Dict[str, jax.Array]:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    dt = cfg.activation_dtype
    ks = jax.random.split(key, 4)
    p = {
        "router": dense_init(ks[0], d, e, jnp.float32),
        "w_in": (jax.random.normal(ks[1], (e, d, f), jnp.float32) / jnp.sqrt(d)).astype(dt),
        "w_out": (jax.random.normal(ks[2], (e, f, d), jnp.float32) / jnp.sqrt(f)).astype(dt),
    }
    if cfg.mlp_type in ("swiglu", "geglu"):
        p["w_gate"] = (jax.random.normal(ks[3], (e, d, f), jnp.float32) / jnp.sqrt(d)).astype(dt)
    return p


def _dispatch_groups(cfg: ModelConfig, t: int) -> int:
    """#independent dispatch groups for moe_dispatch='local'.

    One group per DP shard (from the active mesh context): capacity is
    computed per shard and the scatter/gather becomes a batched (vmapped)
    scatter GSPMD can partition on the group dim -- no cross-shard
    activation collectives in the dispatch (SPerf collective-term fix).
    Slightly higher drop variance than global capacity (per-group
    imbalance); measured in EXPERIMENTS.md.
    """
    if cfg.moe_dispatch != "local":
        return 1
    from repro.models.shard_ctx import current_mesh
    mesh = current_mesh()
    if mesh is None:
        return 1
    g = 1
    for name in mesh.axis_names:
        if name != "model":
            g *= mesh.shape[name]
    while g > 1 and t % g:
        g //= 2
    return max(1, g)


def apply_moe(
    cfg: ModelConfig,
    p: Dict[str, jax.Array],
    x: jax.Array,            # [B, S, D]
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.top_k

    if cfg.moe_dispatch == "ep_shardmap":
        from repro.models.shard_ctx import current_mesh
        if current_mesh() is not None:
            return _shardmap_dispatch(cfg, p, x)

    xt = x.reshape(t, d)

    gate_logits = (xt.astype(jnp.float32)) @ p["router"]               # [T, E]
    weights, experts = jax.lax.top_k(jax.nn.softmax(gate_logits, -1), k)  # [T,k]
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)

    groups = _dispatch_groups(cfg, t)
    if groups > 1:
        out, aux = _grouped_dispatch(cfg, p, xt.reshape(groups, t // groups, d),
                                     experts.reshape(groups, t // groups, k),
                                     weights.reshape(groups, t // groups, k))
        me = jnp.mean(jax.nn.softmax(gate_logits, -1), axis=0)
        ce = jnp.mean(jax.nn.one_hot(experts[:, 0], e), axis=0)
        aux["lb_loss"] = e * jnp.sum(me * ce)
        aux["expert_choice"] = experts
        return out.reshape(b, s, d), aux

    # ---- capacity-bucketed dispatch -----------------------------------
    # Small token counts (decode steps, smoke tests) run dropless: cap = T*k
    # guarantees no overflow whatever the routing; large T uses the standard
    # capacity formula (overflowing tokens dropped, weight 0).
    if t * k <= 4096:
        cap = t * k
    else:
        cap = int(max(1, round(t * k * cfg.capacity_factor / e)))
    flat_expert = experts.reshape(-1)                                   # [T*k]
    order = jnp.argsort(flat_expert)                                    # stable
    sorted_expert = flat_expert[order]
    # position of each routed slot within its expert's bucket
    slot_in_expert = jnp.arange(t * k) - jnp.searchsorted(
        sorted_expert, sorted_expert, side="left")
    keep = slot_in_expert < cap
    token_of = order // k                                               # [T*k]
    dest = jnp.where(keep, sorted_expert * cap + slot_in_expert, 0)     # [T*k]

    # gather tokens into [E*C, D]: kept slots have unique dests, so a masked
    # scatter-add == set, and the buffer stays shardable (no overflow row)
    upd = jnp.where(keep[:, None], xt[token_of], 0)
    buf = jnp.zeros((e * cap, d), x.dtype).at[dest].add(upd)
    xe = constrain(buf.reshape(e, cap, d), None, DP, None)

    # ---- expert FFN: [E, C, D] x [E, D, F] ------------------------------
    if "w_gate" in p:
        act = jax.nn.silu if cfg.mlp_type == "swiglu" else jax.nn.gelu
        h = act(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])) * \
            jnp.einsum("ecd,edf->ecf", xe, p["w_in"])
        h = constrain(h, None, DP, MP)
    else:
        h = constrain(jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xe, p["w_in"])),
                      None, DP, MP)
    ye = jnp.einsum("ecf,efd->ecd", h, p["w_out"]).reshape(e * cap, d)

    # ---- combine back ---------------------------------------------------
    gathered = jnp.where(keep[:, None], ye[dest], 0.0)
    wcomb = (weights.reshape(-1)[order] * keep).astype(x.dtype)
    out = jnp.zeros((t, d), x.dtype).at[token_of].add(gathered * wcomb[:, None])

    # ---- aux: load-balancing loss + routing telemetry -------------------
    me = jnp.mean(jax.nn.softmax(gate_logits, -1), axis=0)               # [E]
    ce = jnp.mean(jax.nn.one_hot(experts[:, 0], e), axis=0)
    aux = {
        "lb_loss": e * jnp.sum(me * ce),
        "expert_choice": experts,                                        # [T, k]
        "dropped_frac": 1.0 - jnp.mean(keep.astype(jnp.float32)),
    }
    return out.reshape(b, s, d), aux


def _grouped_dispatch(cfg: ModelConfig, p, xg, eg, wg):
    """Per-group capacity dispatch, vmapped over the group (DP-shard) dim.

    xg: [G, Tl, D], eg: [G, Tl, k], wg: [G, Tl, k].  The vmapped scatter /
    gather lower to batched scatter ops that GSPMD partitions along G, so
    dispatch traffic stays shard-local.
    """
    g_, tl, d = xg.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = int(max(1, round(tl * k * cfg.capacity_factor / e)))
    if tl * k <= 4096:
        cap = tl * k

    # NOTE (SPerf iteration 3, refuted): forcing the expert weights to
    # (None, None, MP) here to avoid dp-sharded contractions made XLA
    # replicate the expert einsums instead (t_compute x13, t_coll x3.8 at
    # mixtral train_4k).  Reverted; the partial-sum ARs are cheaper.
    w_in, w_out = p["w_in"], p["w_out"]
    w_gate = p.get("w_gate")

    def one_group(xt, experts, weights):
        flat_expert = experts.reshape(-1)
        order = jnp.argsort(flat_expert)
        sorted_expert = flat_expert[order]
        slot = jnp.arange(tl * k) - jnp.searchsorted(sorted_expert,
                                                     sorted_expert, "left")
        keep = slot < cap
        token_of = order // k
        dest = jnp.where(keep, sorted_expert * cap + slot, 0)
        upd = jnp.where(keep[:, None], xt[token_of], 0)
        buf = jnp.zeros((e * cap, d), xt.dtype).at[dest].add(upd)
        xe = buf.reshape(e, cap, d)
        if w_gate is not None:
            act = jax.nn.silu if cfg.mlp_type == "swiglu" else jax.nn.gelu
            h = act(jnp.einsum("ecd,edf->ecf", xe, w_gate)) * \
                jnp.einsum("ecd,edf->ecf", xe, w_in)
        else:
            h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xe, w_in))
        ye = jnp.einsum("ecf,efd->ecd", h, w_out).reshape(e * cap, d)
        gathered = jnp.where(keep[:, None], ye[dest], 0.0)
        wcomb = (weights.reshape(-1)[order] * keep).astype(xt.dtype)
        out = jnp.zeros((tl, d), xt.dtype).at[token_of].add(
            gathered * wcomb[:, None])
        return out, jnp.mean(keep.astype(jnp.float32))

    xg = constrain(xg, DP, None, None)
    out, kept = jax.vmap(one_group)(xg, eg, wg)
    out = constrain(out, DP, None, None)
    return out.reshape(g_ * tl, d), {"dropped_frac": 1.0 - jnp.mean(kept)}


# --------------------------------------------------------------------------
# shard_map expert compute (SPerf cell A, iteration 5)
# --------------------------------------------------------------------------

def _shardmap_dispatch(cfg: ModelConfig, p, x: jax.Array):
    """Explicit-collective MoE: the program structure GSPMD cannot find.

    Iterations 2-4 (EXPERIMENTS SPerf) showed that with token groups on the
    data axes and expert weights D-sharded on them, the partitioner always
    resolves the einsum conflict by partial-sum all-reducing the [E,C,F]
    intermediates (TBs/step).  Under shard_map WE choose the loser:

      1. all-gather the expert weights' D-shard over the data axes
         (~100s of MB per layer -- the cheap side),
      2. dispatch and contract entirely locally (tokens stay in their
         shard; each model column computes its F-slice of every expert),
      3. one psum over "model" combines the F-slices (the only big
         collective: ~|tokens_local| * D per layer).

    Capacity is per data shard (same semantics as moe_dispatch="local").
    """
    from jax.sharding import PartitionSpec as P
    from repro.models.shard_ctx import current_mesh

    mesh = current_mesh()
    dp_axes = tuple(n for n in mesh.axis_names if n != "model")
    dp = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    e, k = cfg.n_experts, cfg.top_k
    has_gate = "w_gate" in p

    def local_fn(router, wg, wi, wo, xl):
        bl, sl, d = xl.shape
        tl = bl * sl
        xt = xl.reshape(tl, d)
        gates = jax.nn.softmax(xt.astype(jnp.float32) @ router, -1)
        weights, experts = jax.lax.top_k(gates, k)
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)

        cap = tl * k if tl * k <= 4096 else int(
            max(1, round(tl * k * cfg.capacity_factor / e)))
        flat_expert = experts.reshape(-1)
        order = jnp.argsort(flat_expert)
        sorted_expert = flat_expert[order]
        slot = jnp.arange(tl * k) - jnp.searchsorted(sorted_expert,
                                                     sorted_expert, "left")
        keep = slot < cap
        token_of = order // k
        dest = jnp.where(keep, sorted_expert * cap + slot, 0)
        upd = jnp.where(keep[:, None], xt[token_of], 0)
        xe = jnp.zeros((e * cap, d), xt.dtype).at[dest].add(upd)
        xe = xe.reshape(e, cap, d)

        # weights arrive D-sharded over the data axes: gather D explicitly
        wi_f = jax.lax.all_gather(wi, dp_axes, axis=1, tiled=True)
        wo_f = jax.lax.all_gather(wo, dp_axes, axis=2, tiled=True)
        if has_gate:
            wg_f = jax.lax.all_gather(wg, dp_axes, axis=1, tiled=True)
            act = jax.nn.silu if cfg.mlp_type == "swiglu" else jax.nn.gelu
            h = act(jnp.einsum("ecd,edf->ecf", xe, wg_f)) * \
                jnp.einsum("ecd,edf->ecf", xe, wi_f)
        else:
            h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xe, wi_f))
        ye = jnp.einsum("ecf,efd->ecd", h, wo_f).reshape(e * cap, d)

        gathered = jnp.where(keep[:, None], ye[dest], 0.0)
        wcomb = (weights.reshape(-1)[order] * keep).astype(xt.dtype)
        out = jnp.zeros((tl, d), xt.dtype).at[token_of].add(
            gathered * wcomb[:, None])
        # each model column held an F-slice: combine the partial outputs
        out = jax.lax.psum(out, "model")

        me = jnp.mean(gates, axis=0)
        ce = jnp.mean(jax.nn.one_hot(experts[:, 0], e), axis=0)
        lb = jax.lax.pmean(e * jnp.sum(me * ce), dp_axes)
        drop = jax.lax.pmean(1.0 - jnp.mean(keep.astype(jnp.float32)),
                             dp_axes)
        return out.reshape(bl, sl, d), lb, drop

    w_spec = P(None, dp, "model")
    wo_spec = P(None, "model", dp)
    args = [p["router"], p.get("w_gate", p["w_in"]), p["w_in"], p["w_out"], x]
    out, lb, drop = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(None, None), w_spec, w_spec, wo_spec, P(dp, None, None)),
        out_specs=(P(dp, None, None), P(), P()),
        check_vma=False,
    )(*args)
    aux = {"lb_loss": lb, "dropped_frac": drop,
           "expert_choice": jnp.zeros((1, k), jnp.int32)}
    return out, aux
