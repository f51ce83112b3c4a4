"""Matrix products for the references: float32 at full precision, or the
control's lower precision.

On a TPU a float32 product runs in bfloat16 passes unless asked for
``HIGHEST``, so every product of a reference names it.  The control
rounds both operands of each linear layer to fp8 (e4m3: 4 exponent and 3
mantissa bits) with a scale per row of the activations and per column of
the weights -- the step from bfloat16 that would tempt a later change --
and multiplies the rounded values at full precision.  Its gradient passes
straight through the rounding, as fp8 training does.

The rounding is ``lax.reduce_precision``, not a cast to a narrow type and
back: inside a jitted program XLA may drop such a pair of casts as excess
precision (it does on the TPU), and the control would silently run in
float32.  ``reduce_precision`` keeps the IEEE layout, whose largest e4m3
value is 240, so the scale maps each slice's largest magnitude to 240.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 240.0


def fp8_round(x: jax.Array, axis: int) -> jax.Array:
    """``x`` rounded to fp8 e4m3 with one scale per slice along ``axis``,
    returned in float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax / FP8_MAX, 1e-30)
    q = jax.lax.reduce_precision(x / scale, exponent_bits=4,
                                 mantissa_bits=3) * scale
    return x + jax.lax.stop_gradient(q - x)


def linear(x: jax.Array, w: jax.Array, quant: str | None = None
           ) -> jax.Array:
    """``x @ w`` for ``x`` [..., K] and ``w`` [K, N]."""
    if quant == "fp8":
        x, w = fp8_round(x, -1), fp8_round(w, 0)
    elif quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.matmul(x, w, precision=HIGHEST)
