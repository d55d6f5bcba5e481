"""Seeded weights and inputs, made by the benchmark (not by the program):
what every block shares. A block module (``bench/blocks/<block>.py``)
makes its own layers from these leaves and conventions.

Each leaf has its own key, folded from the seed and the leaf's place, so
the reference can make one layer at a time, long after the program's copy
is freed, and get the same numbers: a top-level leaf's key is
``fold_in(seed key, TOP_LEAVES.index(name))``, a layer's leaves fold from
``fold_in(seed key, LAYER_BASE + layer)``. Scales follow the usual fan-in
rule (``_leaf``): projections N(0, 1/fan_in), embedding and head N(0, 1/d)
(the program scales embedded rows by sqrt(d)), norm scales N(0, 0.1^2)
around the program's ``(1 + gamma)`` convention.

Two planted structures make attention carry signal at long contexts, as
it does in trained models; with plain fan-in weights the scores' spread
is about 1, softmax over 4k-32k positions is nearly uniform, and the
attention output is nearly 0 whatever the index retrieves:

- every input row (token embeddings and image patches) shares one
  direction ``m`` of the same norm as the row's own part (``SHARED``);
- a block's ``wq`` and ``wk`` map ``m`` to the same vector per KV head,
  of equal size in every rotary pair with phases drawn from the seed.
  After rotary embedding, the score of a key ``D`` positions back then
  holds ``S * mean_i cos(w_i * D)``, a recency profile that falls off
  with log-distance, with the peak ``S = POSITION_PEAK *
  ln(rope_theta)``. With 1.6 most of the mass lies on the last 64
  positions (the local zone, attended exactly) and a few per cent
  further back, where the index holds it after each update; keys near in
  position are near in the planted part, so they cluster and recent
  clusters rank first.

The value and MLP input projections and the head are blind to ``m`` as
their norm scales it, so the shared part carries position into the
scores and nothing else: values, MLP and logits see only what is
particular to each token, and the attention output is an average of a
few recent tokens' values, far from 0. ``run.py`` checks a block's tree
against the program's own ``param_specs`` before serving.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NORM_SCALE = 0.1
SHARED = 1.0            # norm of the shared input direction / the row's own
POSITION_PEAK = 1.6     # peak positional score, in units of ln(rope_theta)
TOP_LEAVES = ("embed", "lm_head", "final_norm", "patches", "shared")
LAYER_BASE = 1000


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, also one above 2**32."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(0)
    while True:
        key = jax.random.fold_in(key, seed & 0x7FFFFFFF)
        seed >>= 31
        if not seed:
            return key


def _dtype(cfgd):
    return jnp.dtype(cfgd["dtype"])


def _leaf(key, name, shape, dtype):
    if name.startswith("ln") or name == "final_norm":
        return (NORM_SCALE * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    scale = (shape[1] if name == "embed" else shape[0]) ** -0.5
    return (jax.random.normal(key, shape, dtype) * scale).astype(dtype)


def shared_direction(cfgd, key):
    """The unit direction ``m`` that every input row shares, (d,) f32."""
    m = jax.random.normal(jax.random.fold_in(key, TOP_LEAVES.index("shared")),
                          (cfgd["d_model"],), jnp.float32)
    return m / jnp.linalg.norm(m)


def _blind(w, gamma, m):
    """``w`` (d, n) with its response to the normed shared direction
    ``(1 + gamma) * m`` removed: ``w - u (u^T w)``, ``u`` that direction
    made unit."""
    u = (1.0 + gamma.astype(jnp.float32)) * m
    u = u / jnp.linalg.norm(u)
    w32 = w.astype(jnp.float32)
    return (w32 - u[:, None] * (u @ w32)[None, :]).astype(w.dtype)


def top_weight(cfgd, key, name):
    d, v = cfgd["d_model"], cfgd["vocab"]
    shape = {"embed": (v, d), "lm_head": (d, v), "final_norm": (d,)}[name]
    w = _leaf(jax.random.fold_in(key, TOP_LEAVES.index(name)), name, shape,
              _dtype(cfgd))
    if name == "embed":     # rows are scaled by sqrt(d) where they are read
        m = SHARED * shared_direction(cfgd, key)
        w = (w.astype(jnp.float32) + m[None, :]).astype(w.dtype)
    if name == "lm_head":
        w = _blind(w, top_weight(cfgd, key, "final_norm"),
                   shared_direction(cfgd, key))
    return w


def make_patch_pool(cfgd, seed: int, n: int):
    """``n`` image-patch embedding blocks of shape (1, P, d), each its own
    device buffer (so a request's block is passed without a slicing op),
    made in one jitted call. They stand for the vision tower's projected
    output: unit-variance rows with the shared direction, like token
    embeddings at the model's input scale."""
    p, d = cfgd["num_patch_tokens"], cfgd["d_model"]
    root = seed_key(seed)
    key = jax.random.fold_in(root, TOP_LEAVES.index("patches"))

    @jax.jit
    def make(key):
        m = SHARED * d ** 0.5 * shared_direction(cfgd, root)
        return tuple((jax.random.normal(jax.random.fold_in(key, i), (1, p, d),
                                        jnp.float32) + m).astype(_dtype(cfgd))
                     for i in range(n))

    return list(make(key)) if n else []
