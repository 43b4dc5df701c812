"""Weights made from `--seed`, on the device, in one jitted call, in the
type they are served in (matmul weights bf16 unless the engine holds
them in float32, norm scales float32).

`make_weights` gives the benchmark's own canonical layout, which the plain
reference reads; its leaves are the configuration's family's
(`bench/families/<name>.py`).  `engine_params` re-nests the same arrays
into the serving engine's parameter tree by the family's mapping and
checks it against the tree the engine itself would build, so a change of
the engine's layout fails loudly here rather than silently feeding it
wrong weights.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import families

# the engine pads the vocabulary axis of its embedding to this multiple;
# the padded rows are never a served token and the reference ignores them
VOCAB_MULTIPLE = 256
EMBED_STD = 0.02
NORM_JITTER = 0.1   # norm scales are 1 + 0.1 N(0, 1), so a misplaced norm shows


def seed_key(seed: int) -> jax.Array:
    """A raw uint32[2] PRNG key from any non-negative whole number (the
    seed may exceed 32 bits)."""
    state = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jnp.asarray(state, jnp.uint32)


def padded_vocab(vocab: int) -> int:
    return math.ceil(vocab / VOCAB_MULTIPLE) * VOCAB_MULTIPLE


def make_weights(seed: int, conf: dict) -> dict:
    """Canonical weights of configuration `conf`: flat dict of the leaves
    its family names, made on the default device by one jitted call.  A
    leaf's kind sets its values: 'embed' N(0, 0.02) in bf16; 'matmul'
    N(0, 1 / fan-in) with fan-in shape[-2], in bf16, or 'matmul_f32' in
    float32; 'norm' 1 + 0.1 N(0, 1) in float32."""
    leaves = families.of(conf).leaves(conf["config"])

    def build(key):
        out = {}
        for i, (name, shape, kind) in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            z = jax.random.normal(k, shape, jnp.float32)
            if kind == "embed":
                out[name] = (z * EMBED_STD).astype(jnp.bfloat16)
            elif kind == "matmul":
                out[name] = (z / math.sqrt(shape[-2])).astype(jnp.bfloat16)
            elif kind == "matmul_f32":
                out[name] = z / math.sqrt(shape[-2])
            else:
                out[name] = 1.0 + NORM_JITTER * z
        return out

    return jax.jit(build)(seed_key(seed))


def engine_params(w: dict, conf: dict, model_cfg) -> dict:
    """The engine's parameter tree for configuration `conf`, holding the
    canonical arrays themselves (no copy), as its family nests them.
    Raises when the tree, a shape or a dtype differs from what
    `repro.nn.model.init_serving_params` builds for `model_cfg`."""
    from repro.nn.model import init_serving_params

    params = families.of(conf).engine_params(w, model_cfg)
    want = jax.eval_shape(
        lambda: init_serving_params(jax.random.PRNGKey(0), model_cfg))
    got_def = jax.tree.structure(params)
    want_def = jax.tree.structure(want)
    if got_def != want_def:
        raise ValueError(f"engine parameter tree changed:\n  engine "
                         f"{want_def}\n  bench  {got_def}")
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(params),
                            jax.tree.leaves(want)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"{jax.tree_util.keystr(path)}: bench "
                             f"{a.shape} {a.dtype}, engine {b.shape} "
                             f"{b.dtype}")
    return params
