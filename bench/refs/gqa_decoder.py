"""Plain float32 reference of a decoder-only transformer with grouped
query attention, as StarCoder2 defines it: pre-LayerNorm blocks, RoPE
(rotate-half) on queries and keys, causal attention within a sliding
window, a non-gated tanh-GELU MLP, and an untied output head.

Keys are the Hugging Face ``config.json`` names.  Departures of the
configuration as run are keys of the configuration file (``use_bias``
false: no linear biases).  The reference imports nothing of the program
and makes its weights from the seed with ``bench/lib/weights.py``.  It
runs layer by layer over a few sequences, each padded to one length so
that one program serves them all.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import flops, lowp, weights

HIGHEST = lowp.HIGHEST
PAD = 512          # sequences are padded to a multiple of this
Q_BLOCK = 512      # queries per attention block


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"d": d, "h": h, "kv": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim", d // h),
            "f": cfg["intermediate_size"], "l": cfg["num_hidden_layers"],
            "v": cfg["vocab_size"]}


def counts(cfg: dict) -> flops.Counts:
    m = dims(cfg)
    return flops.dense_decoder(
        d_model=m["d"], n_heads=m["h"], n_kv_heads=m["kv"],
        head_dim=m["hd"], d_ff=m["f"], n_layers=m["l"], vocab=m["v"],
        gated_mlp=False, layernorm=True)


def program_overrides(cfg: dict) -> dict:
    """The program's ``ModelConfig`` fields for this configuration."""
    m = dims(cfg)
    if cfg.get("use_bias", False):
        raise ValueError("the program has no linear biases")
    return {"n_layers": m["l"], "d_model": m["d"], "n_heads": m["h"],
            "n_kv_heads": m["kv"], "head_dim": m["hd"], "d_ff": m["f"],
            "vocab_size": m["v"], "rope_theta": float(cfg["rope_theta"])}


def layout(cfg: dict) -> dict:
    """name -> (per-layer shape, init, scale, served dtype)."""
    m = dims(cfg)
    d, q, kv = m["d"], m["h"] * m["hd"], m["kv"] * m["hd"]
    dt = cfg["torch_dtype"]
    std = cfg["initializer_range"]
    norm = {"scale": ((d,), "around_one", 0.1, dt),
            "bias": ((d,), "normal", 0.1, dt)}
    out = {"embed": ((m["v"], d), "normal", std, dt),
           "lm_head": ((m["v"], d), "normal", std, dt),
           "attn.wq": ((d, q), "fan_in", 0.0, dt),
           "attn.wk": ((d, kv), "fan_in", 0.0, dt),
           "attn.wv": ((d, kv), "fan_in", 0.0, dt),
           "attn.wo": ((q, d), "fan_in", 0.0, dt),
           "mlp.w_up": ((d, m["f"]), "fan_in", 0.0, dt),
           "mlp.w_down": ((m["f"], d), "fan_in", 0.0, dt)}
    for n in ("final_norm", "norm1", "norm2"):
        for k, v in norm.items():
            out[f"{n}.{k}"] = v
    return out


def _served(key, lay: dict, name: str, layer=None) -> jax.Array:
    """A leaf in its served dtype.  Jitted callers return it as it is and
    widen it only inside the program that uses it: a narrowing cast and a
    widening one inside one jitted program may be dropped together, and
    the reference would then read values the program never served."""
    shape, init, scale, dt = lay[name]
    return weights.leaf(key, name, shape, init, scale, jnp.dtype(dt),
                        layer=layer)


def _widen(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def _layer_weights(key, cfg: dict, layer) -> dict:
    lay = layout(cfg)
    return {n: _served(key, lay, n, layer) for n in lay
            if n.split(".")[0] in ("attn", "mlp", "norm1", "norm2")}


def _layernorm(x, scale, bias, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _rope(x, pos, theta):
    """x: [T, heads, hd]; rotate the two halves."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _layer(x, w, cfg_items, quant):
    cfg = dict(cfg_items)
    w = _widen(w)
    m = dims(cfg)
    t = x.shape[0]
    g = m["h"] // m["kv"]
    eps = cfg["norm_epsilon"]
    window = cfg.get("sliding_window") or t
    lin = partial(lowp.linear, quant=quant)
    pos = jnp.arange(t)
    h = _layernorm(x, w["norm1.scale"], w["norm1.bias"], eps)
    q = _rope(lin(h, w["attn.wq"]).reshape(t, m["h"], m["hd"]), pos,
              cfg["rope_theta"])
    k = _rope(lin(h, w["attn.wk"]).reshape(t, m["kv"], m["hd"]), pos,
              cfg["rope_theta"])
    v = lin(h, w["attn.wv"]).reshape(t, m["kv"], m["hd"])
    qb = q.reshape(t // Q_BLOCK, Q_BLOCK, m["kv"], g, m["hd"])

    def block(args):
        qi, start = args
        qpos = start + jnp.arange(Q_BLOCK)
        s = jnp.einsum("qkgd,skd->kgqs", qi, k, precision=HIGHEST)
        s = s / math.sqrt(m["hd"])
        ok = (qpos[:, None] >= pos[None, :]) & (
            qpos[:, None] - pos[None, :] < window)
        p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", p, v, precision=HIGHEST)

    starts = jnp.arange(0, t, Q_BLOCK)
    att = jax.lax.map(block, (qb, starts)).reshape(t, m["h"] * m["hd"])
    x = x + lin(att, w["attn.wo"])
    h = _layernorm(x, w["norm2.scale"], w["norm2.bias"], eps)
    u = jax.nn.gelu(lin(h, w["mlp.w_up"]), approximate=True)
    return x + lin(u, w["mlp.w_down"])


@partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _head(x, table, scale, bias, cfg_items, quant):
    cfg = dict(cfg_items)
    table, scale, bias = _widen((table, scale, bias))
    h = _layernorm(x, scale, bias, cfg["norm_epsilon"])
    return lowp.linear(h, table.T, quant)


def _items(cfg: dict) -> tuple:
    keep = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "intermediate_size", "num_hidden_layers",
            "vocab_size", "rope_theta", "norm_epsilon", "sliding_window")
    return tuple((k, cfg[k]) for k in keep if k in cfg)


def logits_at(cfg: dict, seed: int, seqs: list[tuple[list[int], list[int]]],
              quant: str | None = None) -> list[np.ndarray]:
    """For each ``(tokens, positions)``: the float32 logits [len(positions),
    vocab] that the model gives after reading ``tokens[:p + 1]``, for each
    ``p`` in ``positions``."""
    key = weights.seed_key(seed)
    lay = layout(cfg)
    items = _items(cfg)
    t_pad = PAD * math.ceil(max(len(t) for t, _ in seqs) / PAD)
    embed = jax.jit(lambda k: _served(k, lay, "embed"))(key)
    xs = []
    for toks, _ in seqs:
        ids = jnp.asarray(np.pad(np.asarray(toks, np.int32),
                                 (0, t_pad - len(toks))))
        xs.append(embed[ids].astype(jnp.float32))
    del embed
    gen = jax.jit(lambda k, l: _layer_weights(k, cfg, l))
    for layer in range(cfg["num_hidden_layers"]):
        w = gen(key, layer)
        xs = [_layer(x, w, items, quant) for x in xs]
        del w
    head = jax.jit(lambda k: (_served(k, lay, "lm_head"),
                              _served(k, lay, "final_norm.scale"),
                              _served(k, lay, "final_norm.bias")))(key)
    out = []
    for x, (_, positions) in zip(xs, seqs):
        rows = x[jnp.asarray(positions, jnp.int32)]
        out.append(np.asarray(_head(rows, *head, items, quant)))
    return out
