"""Mixture-of-experts layer with expert parallelism.

Two dispatch implementations behind ``cfg.moe_impl``:

* ``scatter`` (production): tokens are flattened, topk-routed, sorted by
  expert, placed into per-expert capacity buckets via scatter-add, expert
  FFNs run as one batched einsum over the expert axis (sharded over the
  ``model`` mesh axis → GSPMD inserts the all-to-alls), and gathered back.
  O(T·k) routing state — no dense [T, E, C] dispatch tensor.
* ``dense`` (oracle): per-expert masked einsum without capacity drops.
  Exact but O(T·E); used by smoke tests to validate ``scatter`` and by
  tiny-model training.

Load-balance auxiliary loss follows Switch/DeepSeek: mean(fraction of
tokens per expert × mean router prob per expert) · E · coef.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .layers import linear
from .meta import ParamMeta


def moe_meta(cfg) -> dict[str, ParamMeta]:
    m = cfg.moe
    d, dt = cfg.d_model, cfg.param_dtype
    out = {
        "router": ParamMeta((d, m.n_routed), ("embed", None), jnp.float32,
                            "normal", 0.02),
        "w_up": ParamMeta((m.n_routed, d, m.d_expert),
                          ("experts", "embed", "expert_mlp"), dt, "fan_in"),
        "w_gate": ParamMeta((m.n_routed, d, m.d_expert),
                            ("experts", "embed", "expert_mlp"), dt, "fan_in"),
        "w_down": ParamMeta((m.n_routed, m.d_expert, d),
                            ("experts", "expert_mlp", "embed"), dt, "fan_in"),
    }
    if m.n_shared > 0:
        ds = m.n_shared * m.d_expert
        out["shared_up"] = ParamMeta((d, ds), ("embed", "mlp"), dt, "fan_in")
        out["shared_gate"] = ParamMeta((d, ds), ("embed", "mlp"), dt,
                                       "fan_in")
        out["shared_down"] = ParamMeta((ds, d), ("mlp", "embed"), dt,
                                       "fan_in")
    return out


def _router(p, x2d, m):
    """Returns (weights [T,k], expert_idx [T,k], aux_loss scalar)."""
    logits = (x2d.astype(jnp.float32) @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                  # [T,E]
    weights, idx = jax.lax.top_k(probs, m.top_k)             # [T,k]
    weights = weights / jnp.maximum(
        weights.sum(-1, keepdims=True), 1e-9)                # renormalize
    # Switch aux loss
    t = x2d.shape[0]
    me = probs.mean(0)                                       # [E]
    ce = jnp.zeros((m.n_routed,), jnp.float32).at[idx.reshape(-1)].add(
        1.0 / (t * m.top_k))
    aux = m.n_routed * jnp.sum(me * ce) * m.router_aux_coef
    return weights, idx, aux


def _expert_ffn(p, h):
    """h: [E, C, D] -> [E, C, D]; batched over the (sharded) expert axis."""
    up = jnp.einsum("ecd,edf->ecf", h, p["w_up"])
    gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", h,
                                  p["w_gate"]).astype(jnp.float32))
    return jnp.einsum("ecf,efd->ecd", (gate.astype(h.dtype) * up),
                      p["w_down"])


def _moe_scatter(p, x2d, m, cfg):
    t, d = x2d.shape
    k, e = m.top_k, m.n_routed
    weights, idx, aux = _router(p, x2d, m)
    cap = max(1, int(m.capacity_factor * t * k / e))
    cap = min(cap, t)  # never more slots than tokens

    flat_e = idx.reshape(-1)                                  # [T*k]
    tok_of = jnp.arange(t * k) // k
    # rank within expert via stable sort
    order = jnp.argsort(flat_e, stable=True)                  # [T*k]
    counts = jnp.bincount(flat_e, length=e)                   # [E]
    seg_start = jnp.cumsum(counts) - counts                   # [E]
    rank_sorted = jnp.arange(t * k) - seg_start[flat_e[order]]
    rank = jnp.zeros((t * k,), jnp.int32).at[order].set(
        rank_sorted.astype(jnp.int32))
    keep = rank < cap
    dest = flat_e * cap + jnp.minimum(rank, cap - 1)          # [T*k]

    buf = jnp.zeros((e * cap, d), x2d.dtype)
    contrib = jnp.where(keep[:, None], x2d[tok_of], 0).astype(x2d.dtype)
    buf = buf.at[dest].add(contrib)
    # pin the dispatch buffer to expert parallelism: experts on the model
    # axis, capacity slots on the batch axes (GSPMD emits the all-to-alls)
    from repro.sharding.context import constrain

    buf = constrain(buf.reshape(e, cap, d), ("model", ("pod", "data"), None))
    h = _expert_ffn(p, buf)
    h = constrain(h, ("model", ("pod", "data"), None)).reshape(e * cap, d)
    out_slots = h[dest]                                       # [T*k, D]
    w = (weights.reshape(-1) * keep).astype(x2d.dtype)
    out = jnp.zeros((t, d), x2d.dtype).at[tok_of].add(
        out_slots * w[:, None])
    out = constrain(out, (("pod", "data"), None))
    return out, aux


def _moe_dense(p, x2d, m, cfg):
    """Oracle: no capacity, exact top-k routing via dense mask."""
    t, d = x2d.shape
    weights, idx, aux = _router(p, x2d, m)
    mask = jax.nn.one_hot(idx, m.n_routed, dtype=x2d.dtype)   # [T,k,E]
    comb = (mask * weights[..., None].astype(x2d.dtype)).sum(1)  # [T,E]
    h = jnp.einsum("td,te->etd", x2d, comb)                   # [E,T,D] weighted
    # run each expert on ALL tokens (oracle-only cost)
    out_e = _expert_ffn(p, jnp.broadcast_to(x2d[None], (m.n_routed, t, d)))
    out = jnp.einsum("etd,te->td", out_e, comb)
    del h
    return out, aux


def _bucket_by(key, values, n_buckets, cap, fill):
    """Scatter ``values`` rows into [n_buckets*cap, D] capacity slots.

    Returns (buffer, slot, keep): slot[i] is where row i landed; rows past
    capacity are dropped (keep=False).  Pure local compute (sort+scatter).
    """
    n = key.shape[0]
    order = jnp.argsort(key, stable=True)
    counts = jnp.bincount(key, length=n_buckets)
    seg = jnp.cumsum(counts) - counts
    rank_sorted = jnp.arange(n) - seg[key[order]]
    rank = jnp.zeros((n,), jnp.int32).at[order].set(
        rank_sorted.astype(jnp.int32))
    keep = rank < cap
    slot = key * cap + jnp.minimum(rank, cap - 1)
    buf = jnp.full((n_buckets * cap,) + values.shape[1:], fill,
                   values.dtype)
    buf = buf.at[slot].add(jnp.where(
        keep.reshape((-1,) + (1,) * (values.ndim - 1)), values, 0))
    return buf, slot, keep


def _moe_a2a(p, x2d, m, cfg, mesh, token_axes, expert_axis="model"):
    """Expert parallelism with explicit all-to-all token exchange.

    The GSPMD scatter formulation all-reduces the full [E·C, D] dispatch
    buffer per layer (measured: 17.7 TB/device/step on DeepSeek-V3 —
    §Perf log).  Here each token shard routes locally, exchanges only its
    own routed tokens (≈ cf·T_local·k·D bytes) over the expert axis, runs
    local expert FFNs, and reverses the exchange — the collective volume
    drops by ~E/ep·(T_global/T_local).
    """
    ep = mesh.shape[expert_axis]
    e_local = m.n_routed // ep
    tok_spec = (tuple(token_axes) if len(token_axes) != 1
                else token_axes[0]) or None

    def body(x_loc, router, w_up, w_gate, w_down):
        t_loc, d = x_loc.shape
        k = m.top_k
        logits = (x_loc.astype(jnp.float32) @ router)
        probs = jax.nn.softmax(logits, axis=-1)
        weights, idx = jax.lax.top_k(probs, k)
        weights = weights / jnp.maximum(weights.sum(-1, keepdims=True),
                                        1e-9)
        # load-balance aux from shard-local stats, averaged over shards
        me = probs.mean(0)
        ce = jnp.zeros((m.n_routed,), jnp.float32).at[idx.reshape(-1)].add(
            1.0 / (t_loc * k))
        aux = m.n_routed * jnp.sum(me * ce) * m.router_aux_coef
        if token_axes:
            aux = jax.lax.pmean(aux, tuple(token_axes))

        flat_e = idx.reshape(-1).astype(jnp.int32)           # [t*k]
        tok_of = jnp.arange(t_loc * k) // k
        dest = flat_e // e_local                             # owner shard
        cap = max(1, int(m.capacity_factor * t_loc * k / ep))
        send_tok, slot, keep = _bucket_by(dest, x_loc[tok_of], ep, cap,
                                          0)
        send_eid = jnp.full((ep * cap,), -1, jnp.int32).at[slot].max(
            jnp.where(keep, flat_e % e_local, -1))
        # exchange: shard j's block i goes to shard i
        recv_tok = jax.lax.all_to_all(
            send_tok.reshape(ep, cap, d), expert_axis, 0, 0)
        recv_eid = jax.lax.all_to_all(
            send_eid.reshape(ep, cap), expert_axis, 0, 0).reshape(-1)
        slots = recv_tok.reshape(ep * cap, d)
        valid = recv_eid >= 0
        # local per-expert capacity bucketing; invalid slots go to an
        # overflow bucket so they can't displace real tokens
        cap2 = (ep * cap) // e_local + 1
        buf, slot2, keep2 = _bucket_by(
            jnp.where(valid, recv_eid, e_local).astype(jnp.int32),
            jnp.where(valid[:, None], slots, 0), e_local + 1, cap2, 0)
        keep2 = keep2 & valid
        h = _expert_ffn({"w_up": w_up, "w_gate": w_gate, "w_down": w_down},
                        buf[: e_local * cap2].reshape(e_local, cap2, d))
        h_padded = jnp.concatenate(
            [h.reshape(e_local * cap2, d), jnp.zeros((cap2, d), h.dtype)])
        out_slots = h_padded[slot2]
        out_slots = jnp.where(keep2[:, None], out_slots, 0)
        # reverse exchange (all_to_all is its own inverse here)
        back = jax.lax.all_to_all(
            out_slots.reshape(ep, cap, d), expert_axis, 0, 0)
        back = back.reshape(ep * cap, d)[slot]               # [t*k, D]
        w = (weights.reshape(-1) * keep).astype(x_loc.dtype)
        out = jnp.zeros((t_loc, d), x_loc.dtype).at[tok_of].add(
            back * w[:, None])
        return out, aux

    from jax.sharding import PartitionSpec as P

    out, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(tok_spec, None), P(None, None),
                  P(expert_axis, None, None), P(expert_axis, None, None),
                  P(expert_axis, None, None)),
        out_specs=(P(tok_spec, None), P()),
        check_vma=False,
    )(x2d, p["router"], p["w_up"], p["w_gate"], p["w_down"])
    return out, aux


def _a2a_available(m, cfg, x2d):
    from repro.sharding.context import get_active_mesh, get_batch_axes

    mesh = get_active_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return None
    ep = mesh.shape["model"]
    if m.n_routed % ep != 0:
        return None
    # tokens stay sharded over ALL batch axes (incl. the expert axis: EP
    # exchanges between token shards; excluding it would replicate routing)
    token_axes = [a for a in get_batch_axes() if a in mesh.axis_names]
    total = 1
    for a in token_axes:
        total *= mesh.shape[a]
    while token_axes and x2d.shape[0] % total != 0:
        a = token_axes.pop()
        total //= mesh.shape[a]
    return mesh, tuple(token_axes)


def apply_moe(p, x, cfg):
    """x: [B, S, D] -> (out [B,S,D], aux_loss scalar)."""
    m = cfg.moe
    b, s, d = x.shape
    x2d = x.reshape(b * s, d)
    if cfg.moe_impl == "dense":
        out, aux = _moe_dense(p, x2d, m, cfg)
    elif cfg.moe_impl == "a2a":
        avail = _a2a_available(m, cfg, x2d)
        if avail is not None:
            out, aux = _moe_a2a(p, x2d, m, cfg, avail[0], avail[1])
        else:
            out, aux = _moe_scatter(p, x2d, m, cfg)
    else:
        out, aux = _moe_scatter(p, x2d, m, cfg)
    if m.n_shared > 0:
        up = linear(x2d, p["shared_up"])
        gate = jax.nn.silu(linear(x2d, p["shared_gate"]).astype(
            jnp.float32)).astype(x2d.dtype)
        out = out + linear(gate * up, p["shared_down"])
    return out.reshape(b, s, d), aux
