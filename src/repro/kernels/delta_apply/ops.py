"""Jitted wrapper: Pallas on TPU, interpret mode on CPU."""
import functools

import jax

from repro.kernels import interpret_mode
from repro.kernels.delta_apply.kernel import delta_apply_pallas


@functools.partial(jax.jit, static_argnames=())
def apply_delta(old: jax.Array, delta: jax.Array) -> jax.Array:
    return delta_apply_pallas(old, delta, interpret=interpret_mode())
