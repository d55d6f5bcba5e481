"""Seeded weights and inputs, made by the benchmark (not by the program).

Each leaf has its own key, folded from the seed and the leaf's place, so
the reference can make one layer at a time, long after the program's copy
is freed, and get the same numbers. Scales follow the usual fan-in rule:
projections N(0, 1/fan_in), embedding and head N(0, 1/d) (the
program scales embedded rows by sqrt(d)), norm scales
N(0, 0.1^2) around the program's ``(1 + gamma)`` convention.

Two planted structures make attention carry signal at long contexts, as
it does in trained models; with plain fan-in weights the scores' spread
is about 1, softmax over 4k-32k positions is nearly uniform, and the
attention output is nearly 0 whatever the index retrieves:

- every input row (token embeddings and image patches) shares one
  direction ``m`` of the same norm as the row's own part (``SHARED``);
- ``wq`` and ``wk`` map ``m`` to the same vector per KV head, of equal
  size in every rotary pair with phases drawn from the seed
  (``phase``). After rotary embedding, the score of a key ``D``
  positions back then holds ``S * mean_i cos(w_i * D)``, a recency
  profile that falls off with log-distance, with the peak ``S =
  POSITION_PEAK * ln(rope_theta)``. With 1.6 most of the mass lies on
  the last 64 positions (the local zone, attended exactly) and a few
  per cent further back, where the index holds it after each update;
  keys near in position are near in the planted part, so they cluster
  and recent clusters rank first.

``wv``, ``w_gate``, ``w_up`` and the head are blind to ``m`` as their
norm scales it (``_blind``), so the shared part carries position into
the scores and nothing else: values, MLP and logits see only what is
particular to each token, and the attention output is an average of a
few recent tokens' values, far from 0.

The tree is the program's parameter layout (``repro.models.transformer``):
``embed``, ``layers`` (stacked over depth), ``window``, ``final_norm`` and
``lm_head`` for an untied head. ``run.py`` checks it against the program's
own ``param_specs`` before serving.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

GLOBAL_WINDOW = 1.0e9     # the program's "no sliding window" value
NORM_SCALE = 0.1
SHARED = 1.0            # norm of the shared input direction / the row's own
POSITION_PEAK = 1.6     # peak positional score, in units of ln(rope_theta)
# leaf ids inside a layer and at the top level; the key of a leaf is
# fold_in(fold_in(seed key, LAYER_BASE + layer), leaf id)
LAYER_LEAVES = ("ln1", "ln2", "wq", "wk", "wv", "wo", "w_gate", "w_up",
                "w_down", "phase")
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


def layer_shapes(cfgd) -> dict:
    d, a = cfgd["d_model"], cfgd["attn"]
    hq, hkv = a["n_heads"] * a["head_dim"], a["n_kv_heads"] * a["head_dim"]
    f = cfgd["d_ff"]
    return {"ln1": (d,), "ln2": (d,), "wq": (d, hq), "wk": (d, hkv),
            "wv": (d, hkv), "wo": (hq, d), "w_gate": (d, f), "w_up": (d, f),
            "w_down": (f, d)}


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


def _positional(cfgd, key, lk, wq, wk):
    """``wq`` and ``wk`` with the planted positional term: both map ``m``
    to ``c_h`` scaled so that a key at distance 0 scores ``S`` (see the
    module docstring). ``c_h`` has one unit-size entry per rotary pair
    (first half cos, second half sin of the pair's phase)."""
    a, d = cfgd["attn"], cfgd["d_model"]
    hd, hkv, g = a["head_dim"], a["n_kv_heads"], a["n_heads"] // a["n_kv_heads"]
    phase = 2 * jnp.pi * jax.random.uniform(
        jax.random.fold_in(lk, LAYER_LEAVES.index("phase")), (hkv, hd // 2))
    c = jnp.concatenate([jnp.cos(phase), jnp.sin(phase)], -1)     # (hkv, hd)
    peak = POSITION_PEAK * jnp.log(a["rope_theta"])
    # the normed input's component along m, and the size that gives
    # (size * along)^2 * (hd / 2) / sqrt(hd) = peak
    along = SHARED * d ** 0.5 / (1.0 + SHARED ** 2) ** 0.5
    size = jnp.sqrt(2.0 * peak / hd ** 0.5) / along
    m = shared_direction(cfgd, key)[:, None]
    ck = (size * c).reshape(1, hkv * hd)
    cq = jnp.repeat(size * c, g, axis=0).reshape(1, hkv * g * hd)
    return ((wq.astype(jnp.float32) + m * cq).astype(wq.dtype),
            (wk.astype(jnp.float32) + m * ck).astype(wk.dtype))


def _blind(w, gamma, m):
    """``w`` (d, n) with its response to the normed shared direction
    ``(1 + gamma) * m`` removed: ``w - u (u^T w)``, ``u`` that direction
    made unit."""
    u = (1.0 + gamma.astype(jnp.float32)) * m
    u = u / jnp.linalg.norm(u)
    w32 = w.astype(jnp.float32)
    return (w32 - u[:, None] * (u @ w32)[None, :]).astype(w.dtype)


def layer_weights(cfgd, key, layer):
    """Flat dict of one layer's leaves (``layer`` may be traced)."""
    lk = jax.random.fold_in(key, LAYER_BASE + layer)
    out = {n: _leaf(jax.random.fold_in(lk, LAYER_LEAVES.index(n)), n, s,
                    _dtype(cfgd))
           for n, s in layer_shapes(cfgd).items()}
    out["wq"], out["wk"] = _positional(cfgd, key, lk, out["wq"], out["wk"])
    m = shared_direction(cfgd, key)
    out["wv"] = _blind(out["wv"], out["ln1"], m)
    out["w_gate"] = _blind(out["w_gate"], out["ln2"], m)
    out["w_up"] = _blind(out["w_up"], out["ln2"], m)
    return out


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


def _nest(flat):
    return {"ln1": flat["ln1"], "ln2": flat["ln2"],
            "attn": {k: flat[k] for k in ("wq", "wk", "wv", "wo")},
            "mlp": {k: flat[k] for k in ("w_gate", "w_up", "w_down")}}


def make_params(cfgd, seed: int):
    """All weights in the program's layout, on the device, in one jitted
    call, in the type they are served in."""
    key = seed_key(seed)
    n = cfgd["n_layers"]
    pattern = cfgd["attn"].get("pattern", ["g"])
    window = [float(cfgd["attn"]["sliding_window"])
              if pattern[i % len(pattern)] == "l" else GLOBAL_WINDOW
              for i in range(n)]

    @jax.jit
    def make(key):
        layers = jax.vmap(lambda l: _nest(layer_weights(cfgd, key, l)))(
            jnp.arange(n))
        params = {"embed": top_weight(cfgd, key, "embed"), "layers": layers,
                  "window": jnp.asarray(window, jnp.float32),
                  "final_norm": top_weight(cfgd, key, "final_norm")}
        if not cfgd["tie_embeddings"]:
            params["lm_head"] = top_weight(cfgd, key, "lm_head")
        return params

    return make(key)


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
