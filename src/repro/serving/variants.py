"""Tenant-variant helper shared by the launcher, example and benchmark:
derive a "fine-tuned" copy of a model's params (small deltas on the big
tensors) — the co-hosted model-variant regime where cross-tenant §V-C
delta installs have real structure to exploit."""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("scale",))
def _perturb(leaf, key, scale: float):
    x = leaf.astype(jnp.float32)
    noise = scale * jnp.std(x) * jax.random.normal(key, x.shape, jnp.float32)
    return (x + noise).astype(leaf.dtype)


def perturbed_variant(params: Any, scale: float = 0.02, seed: int = 1) -> Any:
    """Every tensor with ndim >= 2 and >= 1024 elements gets Gaussian noise
    of `scale` times its own std; smaller leaves are kept.  Runs on the
    device one leaf at a time, so a full-width tenant needs one leaf's
    float32 scratch beyond the two models, and returns device arrays of
    the input dtypes."""
    leaves, treedef = jax.tree.flatten(params)
    key = jax.random.PRNGKey(seed)
    out = [_perturb(leaf, jax.random.fold_in(key, i), scale)
           if leaf.ndim >= 2 and leaf.size >= 1024 else jnp.asarray(leaf)
           for i, leaf in enumerate(leaves)]
    return jax.tree.unflatten(treedef, out)
