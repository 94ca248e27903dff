"""Mixture-of-Experts with *fsparse-style* counting-sort dispatch.

Token routing is literally the paper's assembly problem: triplets
``(expert e, token t, gate g)`` with bounded integer keys, where the
combine step must sum k contributions per token ("repeated indices
imply summation").  The dispatch below is the paper's pipeline:

  Part 1  histogram of expert keys (private counters under sharding)
  Part 2  stable counting-sort placement -> expert-contiguous slots
  capacity crop == nzmax; dropped tokens are the overflow diagnostic
  Post    combine = *gather* + weighted sum (no colliding scatter:
          each (t, k) remembers its slot — the paper's ``irank``)

The einsum over ``[E, C, D] x [E, D, F]`` keeps experts sharded on the
``model`` axis (expert parallelism); activations stay sharded on
``data``.  See ``kernels/counting_sort`` for the Pallas placement.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..sparse.ops import scatter_rows
from .layers import _dense_init


def init_moe(key, cfg):
    D = cfg.d_model
    E = cfg.moe.n_experts
    F = cfg.moe.d_expert
    dtype = jnp.dtype(cfg.dtype)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    scale = (1.0 / D) ** 0.5
    return {
        "router": _dense_init(k1, D, E, jnp.float32, scale),
        "gate_ein": (jax.random.normal(k2, (E, D, F), jnp.float32) * scale).astype(dtype),
        "up_ein": (jax.random.normal(k3, (E, D, F), jnp.float32) * scale).astype(dtype),
        "down_eout": (jax.random.normal(k4, (E, F, D), jnp.float32) * (1.0 / F) ** 0.5).astype(dtype),
    }


def moe_dispatch_indices(expert_ids, *, n_experts: int, capacity: int):
    """fsparse Parts 1+2 on expert keys: slot per (token, choice).

    expert_ids: int32[L] flattened (token-major) top-k choices.
    Returns ``slot`` int32[L] in [0, E*C] — E*C marks dropped (overflow),
    plus per-expert load (the Part-1 histogram).
    """
    L = expert_ids.shape[0]
    # Part 2: stable counting-sort placement (kernel: counting_sort.ops)
    order = jnp.argsort(expert_ids, stable=True)
    e_sorted = expert_ids[order]
    # Part 1: histogram -> exclusive prefix = segment starts
    load = jnp.bincount(expert_ids, length=n_experts)
    starts = jnp.searchsorted(e_sorted, jnp.arange(n_experts, dtype=e_sorted.dtype))
    within = jnp.arange(L, dtype=jnp.int32) - starts[e_sorted].astype(jnp.int32)
    slot_sorted = jnp.where(
        within < capacity,
        e_sorted.astype(jnp.int32) * capacity + within,
        n_experts * capacity,
    )
    # un-permute: slot in original (token, choice) order == the paper's
    # irank (slot per raw triplet), recovered collision-free.
    slot = jnp.zeros((L,), jnp.int32).at[order].set(slot_sorted)
    return slot, load


def moe_ffn(params, x, cfg):
    """x: [B, S, D] -> (y, aux_loss).

    §Perf iteration 5: with ``runtime_flags.MOE_GROUPS = dp`` the
    dispatch runs *per token group* (group == data shard): the
    counting sort, capacity crop and combine stay device-local — the
    paper's thread-private-counter design — and only the expert einsum
    crosses shards.  ``MOE_GROUPS = 1`` is the global-sort baseline.
    """
    from . import runtime_flags

    B, S, D = x.shape
    E = cfg.moe.n_experts
    K = cfg.moe.top_k
    T = B * S
    mm = runtime_flags.moe_mesh()
    if mm is not None:
        mesh, dp_axes = mm
        dp = 1
        for a in dp_axes:
            dp *= mesh.shape[a]
        if B % dp == 0:
            return moe_ffn_shardmap(params, x, cfg, mesh, dp_axes)
    G = runtime_flags.moe_groups()
    if T % G or B % G:
        G = 1
    TG = T // G
    C = max(8, int(cfg.moe.capacity_factor * K * TG / E))
    C = -(-C // 8) * 8

    xt = x.reshape(G, TG, D)
    logits = jnp.einsum(
        "gtd,de->gte", xt.astype(jnp.float32), params["router"]
    )
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, experts = jax.lax.top_k(probs, K)             # [G, TG, K]
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # ---- fsparse dispatch per group (vmapped -> shard-local sorts)
    # token-major triplet order: choice k of token t sits at t*K + k
    slot, load = jax.vmap(
        lambda e: moe_dispatch_indices(e, n_experts=E, capacity=C)
    )(experts.reshape(G, TG * K).astype(jnp.int32))          # [G, TG*K]

    token_of = jnp.repeat(jnp.arange(TG, dtype=jnp.int32), K)

    def bucketize(slot_g, x_g):
        # one gather + ONE scatter, via the differentiable sparse-API
        # primitive (backward = masked gather by slot, the paper's irank
        # replay).  (§Perf iteration 6 tried K per-choice scatters to
        # skip the [TG*K, D] gathered copy — REFUTED: every functional
        # scatter costs a full buffer read-modify-write in the HLO cost
        # model, 16 buffer passes vs ~4.5.  Fewer, larger scatters win.)
        return scatter_rows(slot_g, x_g[token_of], num_slots=E * C)

    xs = jax.vmap(bucketize)(slot, xt).reshape(G, E, C, D)

    # ---- expert FFN (SwiGLU), experts sharded on `model`
    g = jnp.einsum("gecd,edf->gecf", xs, params["gate_ein"])
    u = jnp.einsum("gecd,edf->gecf", xs, params["up_ein"])
    out = jnp.einsum("gecf,efd->gecd", jax.nn.silu(g) * u,
                     params["down_eout"])

    # ---- combine: gather each (t, k)'s slot, weighted sum (no scatter)
    out_flat = out.reshape(G, E * C, D)
    dropped = slot >= E * C
    safe = jnp.where(dropped, 0, slot)
    y_tk = jax.vmap(lambda o, s: o[s])(out_flat, safe).reshape(G, TG, K, D)
    gates = jnp.where(dropped.reshape(G, TG, K), 0.0, gate_vals)
    y = jnp.einsum("gtkd,gtk->gtd", y_tk.astype(jnp.float32),
                   gates.astype(jnp.float32))

    # ---- load-balancing auxiliary loss (Switch-style)
    load_total = jnp.sum(load, axis=0)
    frac_tokens = load_total.astype(jnp.float32) / jnp.maximum(
        jnp.sum(load_total), 1
    )
    frac_probs = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(frac_tokens * frac_probs) * cfg.moe.aux_loss_weight
    return y.reshape(B, S, D).astype(x.dtype), aux


def moe_ffn_decode(params, x, cfg):
    """Decode-time MoE: T = B tokens, same path (capacity >= K guaranteed)."""
    y, _ = moe_ffn(params, x, cfg)
    return y


# ---------------------------------------------------------------------------
# §Perf iteration 7: explicit shard_map dispatch (paper §3 verbatim)
# ---------------------------------------------------------------------------
def moe_ffn_shardmap(params, x, cfg, mesh, dp_axes):
    """Dispatch/combine under shard_map: scatter and sort are
    device-local by construction; only the expert einsum (experts on
    ``model``) crosses shards.  This removes GSPMD's replicated
    dispatch buffers observed in the probe HLO.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    B, S, D = x.shape
    E, K = cfg.moe.n_experts, cfg.moe.top_k
    dp = 1
    for a in dp_axes:
        dp *= mesh.shape[a]
    T_loc = (B // dp) * S
    C = max(8, int(cfg.moe.capacity_factor * K * T_loc / E))
    C = -(-C // 8) * 8
    token_of = jnp.repeat(jnp.arange(T_loc, dtype=jnp.int32), K)

    def _dispatch(router, x_blk):
        # x_blk: [B_loc, S, D] — this device's tokens (paper Listing 9:
        # private counters; Listing 10: local placement)
        xf = x_blk.reshape(T_loc, D)
        logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, experts = jax.lax.top_k(probs, K)
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
        slot, load = moe_dispatch_indices(
            experts.reshape(-1).astype(jnp.int32), n_experts=E, capacity=C
        )
        buf = scatter_rows(slot, xf[token_of], num_slots=E * C)
        return (buf.reshape(1, E, C, D), slot[None], gate_vals[None],
                load[None], jnp.sum(probs, axis=0)[None])

    spec_x = P(dp_axes, None, None)
    dispatch = shard_map(
        _dispatch, mesh=mesh, check_vma=False,
        in_specs=(P(None, None), spec_x),
        out_specs=(P(dp_axes, None, None, None), P(dp_axes, None),
                   P(dp_axes, None, None), P(dp_axes, None),
                   P(dp_axes, None)),
    )
    xs, slot, gate_vals, load, sum_probs = dispatch(params["router"], x)

    # ---- expert FFN at global level: experts sharded on `model`
    g = jnp.einsum("gecd,edf->gecf", xs, params["gate_ein"])
    u = jnp.einsum("gecd,edf->gecf", xs, params["up_ein"])
    out = jnp.einsum("gecf,efd->gecd", jax.nn.silu(g) * u,
                     params["down_eout"])

    def _combine(out_blk, slot_blk, gates_blk):
        out_flat = out_blk.reshape(E * C, D)
        s = slot_blk.reshape(-1)
        dropped = s >= E * C
        safe = jnp.where(dropped, 0, s)
        y_tk = out_flat[safe].reshape(T_loc, K, D)
        gts = jnp.where(dropped.reshape(T_loc, K), 0.0,
                        gates_blk.reshape(T_loc, K))
        y = jnp.einsum("tkd,tk->td", y_tk.astype(jnp.float32),
                       gts.astype(jnp.float32))
        return y.reshape(1, B // dp, S, D).astype(out_blk.dtype)

    combine = shard_map(
        _combine, mesh=mesh, check_vma=False,
        in_specs=(P(dp_axes, None, None, None), P(dp_axes, None),
                  P(dp_axes, None, None)),
        out_specs=P(dp_axes, None, None, None),
    )
    y = combine(out, slot, gate_vals).reshape(B, S, D)

    load_total = jnp.sum(load, axis=0)
    frac_tokens = load_total.astype(jnp.float32) / jnp.maximum(
        jnp.sum(load_total), 1
    )
    frac_probs = jnp.sum(sum_probs, axis=0) / (dp * T_loc)
    aux = E * jnp.sum(frac_tokens * frac_probs) * cfg.moe.aux_loss_weight
    return y.astype(x.dtype), aux
