"""Plain float32 reference of a Mamba-2 language model and its AdamW
training step.

Each block is ``x + out_proj(gated_rmsnorm(SSD(conv(in_proj(rmsnorm(x))))))``
with one B/C group.  The SSD is written in its quadratic ("attention")
form over the whole sequence, y_t = sum_{s<=t} C_t.B_s exp(sum_{s<k<=t}
dt_k A) dt_s x_s + D x_t, which is the definition and not the chunked
algorithm the program runs.  The head is tied to the embedding.

Departures of the configuration as run from the published Mamba-2 are
keys of the configuration file and are followed here: ``conv_bias``
false; ``conv_act_on_bc`` false (the SiLU after the convolution acts on x
only, not on B and C); RMSNorm epsilon ``norm_epsilon``.  Every activation
is float32 here (``residual_in_fp32`` false in the program is a precision
the reference does not copy); the parameters are stored in the served
dtype between steps, with no float32 master copy, as the training path
keeps them.

Keys are those of the published ``config.json`` plus the Mamba-2 layer
defaults under ``mamba2_defaults``.  The reference imports nothing of the
program and makes its weights from the seed with ``bench/lib/weights.py``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from bench.lib import flops, lowp, weights

HIGHEST = lowp.HIGHEST


def dims(cfg: dict) -> dict:
    m = cfg["mamba2_defaults"]
    pad = cfg.get("pad_vocab_size_multiple", 1)
    d = cfg["d_model"]
    di = m["expand"] * d
    return {"d": d, "l": cfg["n_layer"], "di": di, "n": m["d_state"],
            "k": m["d_conv"], "p": m["headdim"], "h": di // m["headdim"],
            "chunk": m["chunk_size"],
            "v": pad * math.ceil(cfg["vocab_size"] / pad)}


def counts(cfg: dict) -> flops.Counts:
    m = dims(cfg)
    return flops.mamba2(d_model=m["d"], n_layers=m["l"], vocab=m["v"],
                        d_state=m["n"], expand=m["di"] // m["d"],
                        head_dim=m["p"], chunk=m["chunk"])


def program_overrides(cfg: dict) -> dict:
    m = dims(cfg)
    if cfg.get("mamba2_defaults", {}).get("ngroups", 1) != 1:
        raise ValueError("the program has one B/C group")
    return {"n_layers": m["l"], "d_model": m["d"], "vocab_size": m["v"],
            "n_heads": m["h"], "n_kv_heads": m["h"],
            "tie_embeddings": bool(cfg["tie_embeddings"]),
            "ssm": {"d_state": m["n"], "d_conv": m["k"],
                    "expand": m["di"] // m["d"], "head_dim": m["p"],
                    "chunk": m["chunk"]}}


def layout(cfg: dict) -> dict:
    """name -> (per-layer shape, init, scale, served dtype)."""
    m = dims(cfg)
    d, di, n, h, k = m["d"], m["di"], m["n"], m["h"], m["k"]
    dt = cfg["torch_dtype"]
    f32 = "float32"
    return {
        "embed": ((m["v"], d), "normal", cfg["initializer_range"], dt),
        "final_norm.scale": ((d,), "around_one", 0.1, dt),
        "norm1.scale": ((d,), "around_one", 0.1, dt),
        "ssm.in_x": ((d, di), "fan_in", 0.0, dt),
        "ssm.in_z": ((d, di), "fan_in", 0.0, dt),
        "ssm.in_B": ((d, n), "fan_in", 0.0, dt),
        "ssm.in_C": ((d, n), "fan_in", 0.0, dt),
        "ssm.in_dt": ((d, h), "fan_in", 0.0, dt),
        "ssm.conv_x": ((k, di), "fan_in", 0.0, dt),
        "ssm.conv_B": ((k, n), "fan_in", 0.0, dt),
        "ssm.conv_C": ((k, n), "fan_in", 0.0, dt),
        "ssm.A_log": ((h,), "log_uniform_1_16", 0.0, f32),
        "ssm.D": ((h,), "ones", 0.0, f32),
        "ssm.dt_bias": ((h,), "dt_bias", 0.0, f32),
        "ssm.out_norm": ((di,), "around_one", 0.1, dt),
        "ssm.out_proj": ((di, d), "fan_in", 0.0, dt),
    }


def init_params(cfg: dict, seed: int) -> dict:
    """float32 copies of the served values: ``embed`` and
    ``final_norm.scale`` whole, every other leaf stacked over layers."""
    key = weights.seed_key(seed)
    lay = layout(cfg)
    n_layers = dims(cfg)["l"]

    def make(k):
        out = {}
        for name, (shape, init, scale, dt) in lay.items():
            if name in ("embed", "final_norm.scale"):
                out[name] = weights.leaf(k, name, shape, init, scale,
                                         jnp.dtype(dt))
            else:
                out[name] = weights.stacked(k, name, n_layers, shape, init,
                                            scale, jnp.dtype(dt))
        return out

    # the served values leave the jitted program in their own dtype and
    # are widened outside it: inside one program XLA may drop a narrowing
    # cast followed by a widening one, and the reference would then start
    # from values the program never held
    return {k: v.astype(jnp.float32) for k, v in jax.jit(make)(key).items()}


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _conv(x, w):
    """Causal depthwise convolution: x [B, S, C], w [K, C]."""
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(k))


def _ssd(x, dt, a, b, c):
    """Quadratic form.  x [S, H, P], dt [S, H], a [H], b/c [S, N]."""
    s = x.shape[0]
    cs = jnp.cumsum(dt * a[None], axis=0)                    # [S, H]
    diff = cs.T[:, :, None] - cs.T[:, None, :]               # [H, T, S]
    causal = jnp.tril(jnp.ones((s, s), bool))
    decay = jnp.exp(jnp.where(causal[None], diff, -jnp.inf))
    cb = jnp.matmul(c, b.T, precision=HIGHEST)               # [T, S]
    m = decay * cb[None] * dt.T[:, None, :]                  # [H, T, S]
    return jnp.einsum("hts,shp->thp", m, x, precision=HIGHEST)


def _block(x, w, cfg_items, quant):
    cfg = _thaw(cfg_items)
    m = dims(cfg)
    eps = cfg["norm_epsilon"]
    lin = partial(lowp.linear, quant=quant)
    bsz, s, _ = x.shape
    h = _rmsnorm(x, w["norm1.scale"], eps)
    xs = lin(h, w["ssm.in_x"])
    z = lin(h, w["ssm.in_z"])
    bb = lin(h, w["ssm.in_B"])
    cc = lin(h, w["ssm.in_C"])
    dt = jax.nn.softplus(lin(h, w["ssm.in_dt"]) + w["ssm.dt_bias"])
    xs = jax.nn.silu(_conv(xs, w["ssm.conv_x"]))
    bb, cc = _conv(bb, w["ssm.conv_B"]), _conv(cc, w["ssm.conv_C"])
    if cfg["conv_act_on_bc"]:
        bb, cc = jax.nn.silu(bb), jax.nn.silu(cc)
    a = -jnp.exp(w["ssm.A_log"])
    xh = xs.reshape(bsz, s, m["h"], m["p"])
    y = jax.vmap(_ssd, in_axes=(0, 0, None, 0, 0))(xh, dt, a, bb, cc)
    y = (y + xh * w["ssm.D"][None, None, :, None]).reshape(bsz, s, m["di"])
    g = y * jax.nn.silu(z)
    g = _rmsnorm(g, w["ssm.out_norm"], eps)
    return x + lin(g, w["ssm.out_proj"])


def _loss(params, tokens, labels, cfg_items, quant):
    cfg = _thaw(cfg_items)
    x = params["embed"][tokens]
    layer_w = {k: v for k, v in params.items()
               if k not in ("embed", "final_norm.scale")}

    @jax.checkpoint
    def body(h, w):
        return _block(h, w, cfg_items, quant), None

    x, _ = jax.lax.scan(body, x, layer_w)
    h = _rmsnorm(x, params["final_norm.scale"], cfg["norm_epsilon"])
    logits = lowp.linear(h, params["embed"].T, quant)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return (lse - gold).mean()


_grad = jax.jit(jax.value_and_grad(_loss),
                static_argnames=("cfg_items", "quant"))


def _items(cfg: dict) -> tuple:
    def freeze(v):
        return tuple(sorted(v.items())) if isinstance(v, dict) else v
    keep = ("d_model", "n_layer", "vocab_size", "pad_vocab_size_multiple",
            "norm_epsilon", "conv_act_on_bc", "mamba2_defaults")
    return tuple((k, freeze(cfg[k])) for k in keep if k in cfg)


def _thaw(items: tuple) -> dict:
    return {k: dict(v) if k == "mamba2_defaults" else v for k, v in items}


def _cosine_lr(hp: dict, step: int) -> float:
    """The launch path's schedule: linear warm-up, then cosine decay to
    ``min_ratio`` of the base rate over ``schedule_steps``."""
    base, warm = hp["lr"], hp["warmup"]
    if step < warm:
        return base * step / max(1.0, warm)
    frac = min(max((step - warm) / max(1.0, hp["schedule_steps"] - warm),
                   0.0), 1.0)
    return base * (hp["min_ratio"] + (1 - hp["min_ratio"])
                   * 0.5 * (1 + math.cos(math.pi * frac)))


@jax.jit
def _clip(grads, max_norm):
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values()))
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-12))
    return {k: g * scale for k, g in grads.items()}


@jax.jit
def _adamw(p, g, m, v, lr, count, b1, b2, eps, wd):
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    upd = (m / (1 - b1 ** count)) / (jnp.sqrt(v / (1 - b2 ** count)) + eps)
    return p - lr * upd - lr * wd * p, m, v


def _store(p: jax.Array, dtype) -> jax.Array:
    """``p`` rounded to the stored dtype, kept in float32.  Two casts run
    as two programs, so neither can be dropped as excess precision."""
    return p.astype(dtype).block_until_ready().astype(jnp.float32)


def _norms(tree: dict) -> dict:
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(v)))) for k, v in
            tree.items()}


def train_reference(cfg: dict, seed: int, batches, hp: dict,
                    quant: str | None = None, row_block: int = 1) -> dict:
    """Run ``len(batches)`` AdamW steps from the seeded weights.

    Returns the loss of each step, the per-leaf norms of the first
    step's gradient as the optimizer gets it (after clipping), and the
    per-leaf norms of the parameters' change after the last step.  The
    batch is taken ``row_block`` rows at a time, and the gradient of the
    mean loss is the mean of the blocks' gradients."""
    items = _items(cfg)
    stored = {k: jnp.dtype(v[3]) for k, v in layout(cfg).items()}
    p0 = init_params(cfg, seed)
    params = dict(p0)
    mom = {k: jnp.zeros_like(v) for k, v in params.items()}
    vel = {k: jnp.zeros_like(v) for k, v in params.items()}
    losses, first = [], None
    for step, (tokens, labels) in enumerate(batches):
        rows = tokens.shape[0]
        loss, grads = 0.0, None
        for r in range(0, rows, row_block):
            l, g = _grad(params, jnp.asarray(tokens[r:r + row_block]),
                         jnp.asarray(labels[r:r + row_block]),
                         cfg_items=items, quant=quant)
            share = min(row_block, rows - r) / rows
            loss += float(l) * share
            g = {k: v * share for k, v in g.items()}
            grads = g if grads is None else {k: grads[k] + g[k] for k in g}
        grads = _clip(grads, hp["grad_clip"])
        if first is None:
            first = _norms(grads)
        lr = _cosine_lr(hp, step)
        for k in params:
            p, mom[k], vel[k] = _adamw(
                params[k], grads[k], mom[k], vel[k], lr, step + 1,
                hp["b1"], hp["b2"], hp["eps"], hp["weight_decay"])
            # the configuration keeps its parameters in the served dtype
            # (no float32 master copy): each step's result is stored so
            params[k] = _store(p, stored[k])
        losses.append(loss)
    change = _norms({k: params[k] - p0[k] for k in params})
    return {"losses": losses, "grad_norms": first, "change_norms": change}
