"""Seeded weights, the same values wherever they are made.

A leaf is named (``attn.wq``) and may have a layer index.  Its values
depend only on the run's seed, that name, the layer and its per-layer
shape, so the harness can make the whole stacked tree for the program in
one jitted call on the device, and the reference can make one layer at a
time, later, in float32, and see the same numbers.  The values are
rounded to the dtype the program serves them in before anyone sees them.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

_MASK31 = (1 << 31) - 1


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole number, including seeds beyond 32 bits."""
    seed = int(seed) % (1 << 64)
    key = jax.random.PRNGKey(seed & _MASK31)
    for part in (seed >> 31 & _MASK31, seed >> 62):
        key = jax.random.fold_in(key, part)
    return key


def _leaf_key(key: jax.Array, name: str) -> jax.Array:
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & _MASK31)


def _values(key, shape: tuple, init: str, scale: float) -> jax.Array:
    """float32 values of one (per-layer) leaf."""
    if init == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if init == "ones":
        return jnp.ones(shape, jnp.float32)
    if init == "fan_in":
        return jax.random.normal(key, shape) / math.sqrt(max(1, shape[0]))
    if init == "normal":
        return jax.random.normal(key, shape) * scale
    if init == "around_one":          # norm scales: 1 + scale * N(0, 1)
        return 1.0 + jax.random.normal(key, shape) * scale
    if init == "log_uniform_1_16":    # Mamba-2 A_log: log(U[1, 16])
        return jnp.log(jax.random.uniform(key, shape, minval=1.0,
                                          maxval=16.0))
    if init == "dt_bias":             # Mamba-2: softplus^-1(dt), dt log-U
        lo, hi = math.log(1e-3), math.log(1e-1)
        dt = jnp.exp(jax.random.uniform(key, shape, minval=lo, maxval=hi))
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(f"unknown init {init!r}")


def leaf(key, name: str, shape: tuple, init: str, scale: float, dtype,
         layer: int | jax.Array | None = None) -> jax.Array:
    """One leaf (or one layer of a stacked leaf) in ``dtype``."""
    k = _leaf_key(key, name)
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    return _values(k, tuple(shape), init, scale).astype(dtype)


def stacked(key, name: str, layers: int, shape: tuple, init: str,
            scale: float, dtype) -> jax.Array:
    """``[layers, *shape]``: layer ``l`` equals ``leaf(..., layer=l)``."""
    return jax.vmap(lambda l: leaf(key, name, shape, init, scale, dtype,
                                   layer=l))(jnp.arange(layers))


def program_name(path) -> tuple[str, bool]:
    """The leaf name of a program parameter path, and whether it is
    stacked over layers: ``['stages'][0]['attn']['wq']`` is ``attn.wq``,
    stacked; ``['final_norm']['scale']`` is ``final_norm.scale``."""
    keys = [getattr(k, "key", getattr(k, "idx", None)) for k in path]
    if keys[0] == "stages":
        if keys[1] != 0:
            raise ValueError(f"only one stage of layers is laid out: {keys}")
        return ".".join(str(k) for k in keys[2:]), True
    return ".".join(str(k) for k in keys), False


def program_tree(structs, layout: dict, key: jax.Array):
    """The program's parameter tree, in the program's shapes and dtypes,
    from the seeded values of ``layout`` (name -> (per-layer shape, init,
    scale, dtype name)).  Call under ``jax.jit``.  A program leaf that the
    layout lacks, or whose shape or dtype differs, raises."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(structs)
    out = []
    for path, st in leaves:
        name, is_stacked = program_name(path)
        if name not in layout:
            raise KeyError(f"program parameter {name!r} has no layout")
        shape, init, scale, dtype = layout[name]
        dtype = jnp.dtype(dtype)
        want = ((st.shape[0],) if is_stacked else ()) + tuple(shape)
        if tuple(st.shape) != want or jnp.dtype(st.dtype) != dtype:
            raise ValueError(f"{name}: program has {st.shape} {st.dtype}, "
                             f"layout {want} {dtype}")
        if is_stacked:
            out.append(stacked(key, name, st.shape[0], shape, init, scale,
                               dtype))
        else:
            out.append(leaf(key, name, shape, init, scale, dtype))
    return jax.tree_util.tree_unflatten(treedef, out)
