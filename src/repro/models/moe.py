"""Mixture-of-Experts FFN with capacity-based sort-free dispatch.

Design (TPU-native adaptation; see DESIGN.md):
  * tokens are organised into G groups (G = data-parallel shard count) so all
    dispatch bookkeeping (rank-within-expert via cumsum) is local to a group
    — no cross-shard prefix sums;
  * expert buffers are (G, E, C, D) with C = ceil(Tg * top_k * cf / E): the
    gather/scatter dispatch costs zero matmul FLOPs, unlike one-hot dispatch
    einsums whose (tokens, E, C) one-hot tensors are infeasible at top-8 /
    128 experts;
  * experts shard over the 'model' mesh axis, groups over 'data'; the combine
    is a scatter-add followed by the usual TP psum (inserted by SPMD).

Dropping: tokens beyond an expert's capacity C are dropped (standard
capacity-factor semantics). Decode-sized batches clamp C to the group size,
which makes dispatch provably dropless there.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["moe_param_table", "moe_ffn", "moe_ffn_sharded", "moe_capacity"]


def moe_capacity(tokens_per_group: int, num_experts: int, top_k: int,
                 capacity_factor: float) -> int:
    c = math.ceil(tokens_per_group * top_k * capacity_factor / num_experts)
    c = max(c, min(8, tokens_per_group))
    return min(c, tokens_per_group)


def moe_param_table(cfg) -> dict[str, tuple]:
    """name -> (shape, logical_axes, fan_in). Gated (swiglu) experts."""
    E, D, F = cfg.num_experts, cfg.d_model, cfg.moe_d_ff
    return {
        "router": ((D, E), ("embed", "experts_router"), D),
        "wi_0": ((E, D, F), ("experts", "embed", "mlp"), D),
        "wi_1": ((E, D, F), ("experts", "embed", "mlp"), D),
        "wo": ((E, F, D), ("experts", "mlp", "embed"), F),
    }


def moe_ffn(x: jnp.ndarray, params: dict[str, Any], cfg, num_groups: int,
            constrain=lambda t, names: t) -> jnp.ndarray:
    """x: (B, S, D) -> (B, S, D).

    ``constrain(tensor, logical_axes)`` applies a mesh sharding constraint
    (identity in single-device tests).
    """
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.moe_top_k
    T = B * S
    G = max(1, min(num_groups, T))
    while T % G:
        G -= 1
    Tg = T // G
    C = moe_capacity(Tg, E, K, cfg.capacity_factor)
    xg = x.reshape(G, Tg, D)
    xg = constrain(xg, ("moe_groups", None, "embed"))

    # --- routing -----------------------------------------------------------
    logits = jnp.einsum("gtd,de->gte", xg, params["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)            # (G, Tg, K)
    if getattr(cfg, "moe_renormalize", True):
        top_p = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)

    # --- rank of each (token, k) within its expert --------------------------
    # flat (G, Tg*K) assignment order is token-major: earlier tokens win slots.
    flat_e = top_e.reshape(G, Tg * K)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.float32)      # (G, Tg*K, E)
    onehot = constrain(onehot, ("moe_groups", None, None))
    pos_in_e = jnp.cumsum(onehot, axis=1) - onehot             # rank, 0-based
    slot = jnp.sum(pos_in_e * onehot, axis=-1).astype(jnp.int32)  # (G, Tg*K)
    slot = slot.reshape(G, Tg, K)
    keep = (slot < C)
    weight = top_p * keep.astype(top_p.dtype)                  # dropped -> 0

    # --- dispatch: scatter tokens into (G, E, C, D) buffers -----------------
    buf = jnp.zeros((G, E, C, D), x.dtype)
    gidx = jnp.arange(G)[:, None]
    for j in range(K):
        src = jnp.where(keep[:, :, j, None], xg, 0).astype(x.dtype)
        buf = buf.at[gidx, top_e[:, :, j], jnp.minimum(slot[:, :, j], C - 1)].add(
            src, mode="drop")
    buf = constrain(buf, ("moe_groups", "experts", None, "embed"))

    # --- expert computation (gated SwiGLU) ----------------------------------
    g = jnp.einsum("gecd,edf->gecf", buf, params["wi_0"])
    u = jnp.einsum("gecd,edf->gecf", buf, params["wi_1"])
    h = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)).astype(x.dtype)
    out_buf = jnp.einsum("gecf,efd->gecd", h, params["wo"])
    out_buf = constrain(out_buf, ("moe_groups", "experts", None, "embed"))

    # --- combine: gather each token's k slots, weight, and sum --------------
    out = jnp.zeros((G, Tg, D), jnp.float32)
    for j in range(K):
        gathered = out_buf[gidx, top_e[:, :, j],
                           jnp.minimum(slot[:, :, j], C - 1)]
        out = out + weight[:, :, j, None] * gathered.astype(jnp.float32)
    out = constrain(out.astype(x.dtype), ("moe_groups", None, "embed"))
    return out.reshape(B, S, D)


# --------------------------------------------------------------------------
# Expert-parallel shard_map path (§Perf hillclimb 1)
# --------------------------------------------------------------------------
def _local_moe(x_loc, router, wi0, wi1, wo, cfg, e_lo_size, axis="model"):
    """Per-shard body: all local tokens x this shard's experts, psum combine.

    x_loc: (B_loc, S, D) — this data-shard's tokens (replicated over the
    model axis). wi0/wi1/wo: (E_loc, ...) — this model-shard's experts.
    Every rank routes against the FULL router (E logits), keeps only the
    assignments that land in its local expert range, computes them at
    capacity C, and the final psum over the model axis sums partial outputs
    (dropped tokens and foreign-expert assignments contribute zeros).
    """
    B, S, D = x_loc.shape
    E, K = cfg.num_experts, cfg.moe_top_k
    e_rank = jax.lax.axis_index(axis)
    e_lo = e_rank * e_lo_size
    T = B * S
    C = moe_capacity(T, E, K, cfg.capacity_factor)
    xf = x_loc.reshape(T, D)

    logits = jnp.einsum("td,de->te", xf, router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)                 # (T, K)
    if getattr(cfg, "moe_renormalize", True):
        top_p = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)

    # Global slot ranks (shared across shards so capacity drops agree),
    # then restrict to local experts.
    flat_e = top_e.reshape(T * K)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.float32)  # (T*K, E)
    pos = jnp.cumsum(onehot, axis=0) - onehot
    slot = jnp.sum(pos * onehot, axis=-1).astype(jnp.int32).reshape(T, K)
    local = (top_e >= e_lo) & (top_e < e_lo + e_lo_size)
    keep = (slot < C) & local
    weight = (top_p * keep.astype(top_p.dtype)).astype(jnp.float32)
    e_idx = jnp.clip(top_e - e_lo, 0, e_lo_size - 1)
    s_idx = jnp.minimum(slot, C - 1)

    buf = jnp.zeros((e_lo_size, C, D), x_loc.dtype)
    for j in range(K):
        src = jnp.where(keep[:, j, None], xf, 0).astype(x_loc.dtype)
        buf = buf.at[e_idx[:, j], s_idx[:, j]].add(src, mode="drop")

    g = jnp.einsum("ecd,edf->ecf", buf, wi0)
    u = jnp.einsum("ecd,edf->ecf", buf, wi1)
    h = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)).astype(
        x_loc.dtype)
    out_buf = jnp.einsum("ecf,efd->ecd", h, wo)

    out = jnp.zeros((T, D), jnp.float32)
    for j in range(K):
        gathered = out_buf[e_idx[:, j], s_idx[:, j]]
        out = out + weight[:, j, None] * gathered.astype(jnp.float32)
    out = jax.lax.psum(out.astype(x_loc.dtype), axis)
    return out.reshape(B, S, D)


def moe_ffn_sharded(x, params, cfg, mesh) -> jnp.ndarray:
    """Expert-parallel MoE: tokens over data axes, experts over 'model'.

    vs the einsum path: per-device buffers are (E/tp, C_loc, D) (never the
    full expert grid), the dispatch bookkeeping is shard-local, and the only
    collective is one activation-sized psum over 'model' per layer — the
    same wire cost as a dense TP MLP.
    """
    tp = mesh.shape.get("model", 1)
    if cfg.num_experts % tp:
        raise ValueError("experts must divide the model axis")
    dp = tuple(a for a in ("pod", "data") if a in mesh.shape)
    dp_ok = dp if x.shape[0] % math.prod(mesh.shape[a] for a in dp) == 0 \
        else ()
    xspec = P(dp_ok if dp_ok else None, None, None)

    fn = jax.shard_map(
        lambda xl, r, a, b, c: _local_moe(xl, r, a, b, c, cfg,
                                          cfg.num_experts // tp),
        mesh=mesh,
        in_specs=(xspec, P(None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=xspec,
        check_vma=False,
    )
    return fn(x, params["router"], params["wi_0"], params["wi_1"],
              params["wo"])


def _local_moe_tokens_gathered(x_loc, router, wi0, wi1, wo, cfg, e_lo_size,
                               dp_axes, tp_axis="model"):
    """Decode-path body: all-gather the (tiny) token batch over the data
    axes and keep expert weights fully resident, sharded over BOTH mesh axes
    (E over 'model', F over 'data').

    Valid because every shard then holds ALL tokens: the partial expert
    outputs (partial over the F contraction AND over local experts) psum
    over both axes into the full combine; each shard slices its tokens back.
    Comm per layer = token bytes (KBs at decode) instead of weight bytes.
    """
    B, S, D = x_loc.shape
    E, K = cfg.num_experts, cfg.moe_top_k
    x_all = x_loc
    for ax in dp_axes:
        x_all = jax.lax.all_gather(x_all, ax, axis=0, tiled=True)
    T = x_all.shape[0] * S
    xf = x_all.reshape(T, D)
    e_rank = jax.lax.axis_index(tp_axis)
    e_lo = e_rank * e_lo_size
    C = moe_capacity(T, E, K, cfg.capacity_factor)

    logits = jnp.einsum("td,de->te", xf, router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)
    if getattr(cfg, "moe_renormalize", True):
        top_p = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)
    flat_e = top_e.reshape(T * K)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.float32)
    pos = jnp.cumsum(onehot, axis=0) - onehot
    slot = jnp.sum(pos * onehot, axis=-1).astype(jnp.int32).reshape(T, K)
    local = (top_e >= e_lo) & (top_e < e_lo + e_lo_size)
    keep = (slot < C) & local
    weight = (top_p * keep.astype(top_p.dtype)).astype(jnp.float32)
    e_idx = jnp.clip(top_e - e_lo, 0, e_lo_size - 1)
    s_idx = jnp.minimum(slot, C - 1)

    buf = jnp.zeros((e_lo_size, C, D), x_loc.dtype)
    for j in range(K):
        src = jnp.where(keep[:, j, None], xf, 0).astype(x_loc.dtype)
        buf = buf.at[e_idx[:, j], s_idx[:, j]].add(src, mode="drop")

    g = jnp.einsum("ecd,edf->ecf", buf, wi0)   # F already local slice
    u = jnp.einsum("ecd,edf->ecf", buf, wi1)
    h = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)).astype(
        x_loc.dtype)
    out_buf = jnp.einsum("ecf,efd->ecd", h, wo)  # partial over F

    out = jnp.zeros((T, D), jnp.float32)
    for j in range(K):
        gathered = out_buf[e_idx[:, j], s_idx[:, j]]
        out = out + weight[:, j, None] * gathered.astype(jnp.float32)
    for ax in (tp_axis, *dp_axes):
        out = jax.lax.psum(out, ax)
    out = out.astype(x_loc.dtype).reshape(x_all.shape)
    # slice this shard's tokens back out (last gather = outermost blocks)
    idx = jnp.int32(0)
    for ax in reversed(dp_axes):
        idx = idx * jax.lax.psum(1, ax) + jax.lax.axis_index(ax)
    return jax.lax.dynamic_slice_in_dim(out, idx * B, B, axis=0)


def moe_ffn_sharded_decode(x, params, cfg, mesh) -> jnp.ndarray:
    """Serve-time MoE for small token counts (decode): resident weights."""
    tp = mesh.shape.get("model", 1)
    dp = tuple(a for a in ("pod", "data") if a in mesh.shape
               and x.shape[0] % mesh.shape[a] == 0)
    xspec = P(dp if dp else None, None, None)
    fn = jax.shard_map(
        lambda xl, r, a, b, c: _local_moe_tokens_gathered(
            xl, r, a, b, c, cfg, cfg.num_experts // tp, dp),
        mesh=mesh,
        in_specs=(xspec, P(None, None), P("model", None, "data"),
                  P("model", None, "data"), P("model", "data", None)),
        out_specs=xspec,
        check_vma=False,
    )
    return fn(x, params["router"], params["wi_0"], params["wi_1"],
              params["wo"])
