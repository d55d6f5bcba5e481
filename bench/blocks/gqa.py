"""The dense grouped-query block: weights, float32 reference and matmul count.

One layer is pre-norm attention then a pre-norm gated MLP, each added to
the residual:

    h = rms_norm(x, ln1);  q, k, v = h wq, h wk, h wv
    q, k = rope(q), rope(k)          (half-split, every layer)
    x = x + causal_attention(q, k, v) wo          (grouped-query heads)
    h = rms_norm(x, ln2);  x = x + (silu(h w_gate) * (h w_up)) w_down

RMSNorm scales by ``(1 + gamma)``, as the program keeps its norms; there
are no biases, attention is global in every layer and the head is tied or
untied as the configuration says. The configuration's widths are
``d_model``, ``d_ff`` and ``attn``'s ``n_heads``, ``n_kv_heads``,
``head_dim`` and ``rope_theta``.

The program's parameter layout (``repro.models.transformer``) is
``embed``, ``layers`` (stacked over depth: ``ln1``, ``ln2``,
``attn{wq,wk,wv,wo}``, ``mlp{w_gate,w_up,w_down}``), ``window``,
``final_norm`` and ``lm_head`` for an untied head. The planted structures
of ``bench.weights`` enter the layer here: ``wq`` and ``wk`` map the
shared direction ``m`` to a rotary recency profile (``_positional``), and
``wv``, ``w_gate``, ``w_up`` are blind to ``m`` as their norm scales it
(``bench.weights._blind``).

A configuration this reference does not compute (windowed layers, logit
soft-capping, experts, state-space layers, another family) is refused.
"""
from __future__ import annotations

from functools import lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import (PAD, ROW_BLOCK, _attention, _linear, _rms_norm,
                             _rope)
from bench.weights import (LAYER_BASE, POSITION_PEAK, SHARED, _blind, _dtype,
                           _leaf, seed_key, shared_direction, top_weight)

GLOBAL_WINDOW = 1.0e9     # the program's "no sliding window" value
FAMILIES = ("dense", "vlm")
# leaf ids inside a layer; the key of a leaf is
# fold_in(fold_in(seed key, LAYER_BASE + layer), leaf id)
LAYER_LEAVES = ("ln1", "ln2", "wq", "wk", "wv", "wo", "w_gate", "w_up",
                "w_down", "phase")


def check(cfgd) -> None:
    """Refuse a configuration whose layer this block does not compute."""
    a = cfgd["attn"]
    if "l" in a.get("pattern", ["g"]):
        raise ValueError("gqa block: attn.pattern has windowed layers ('l'); "
                         "its reference attends globally in every layer")
    if a.get("softcap") is not None:
        raise ValueError("gqa block: attn.softcap is set; its reference "
                         "does not cap attention logits")
    for key in ("moe", "ssm"):
        if cfgd.get(key) is not None:
            raise ValueError(f"gqa block: a {key!r} section is set; its "
                             "reference has a dense MLP and attention only")
    if cfgd.get("family") not in FAMILIES:
        raise ValueError(f"gqa block: family {cfgd.get('family')!r} is not "
                         f"one of {FAMILIES}")


# ---- weights ---------------------------------------------------------------

def layer_shapes(cfgd) -> dict:
    d, a = cfgd["d_model"], cfgd["attn"]
    hq, hkv = a["n_heads"] * a["head_dim"], a["n_kv_heads"] * a["head_dim"]
    f = cfgd["d_ff"]
    return {"ln1": (d,), "ln2": (d,), "wq": (d, hq), "wk": (d, hkv),
            "wv": (d, hkv), "wo": (hq, d), "w_gate": (d, f), "w_up": (d, f),
            "w_down": (f, d)}


def _positional(cfgd, key, lk, wq, wk):
    """``wq`` and ``wk`` with the planted positional term: both map ``m``
    to ``c_h`` scaled so that a key at distance 0 scores ``S`` (see
    ``bench.weights``). ``c_h`` has one unit-size entry per rotary pair
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


def _nest(flat):
    return {"ln1": flat["ln1"], "ln2": flat["ln2"],
            "attn": {k: flat[k] for k in ("wq", "wk", "wv", "wo")},
            "mlp": {k: flat[k] for k in ("w_gate", "w_up", "w_down")}}


def make_params(cfgd, seed: int):
    """All weights in the program's layout, on the device, in one jitted
    call, in the type they are served in."""
    check(cfgd)
    key = seed_key(seed)
    n = cfgd["n_layers"]

    @jax.jit
    def make(key):
        layers = jax.vmap(lambda l: _nest(layer_weights(cfgd, key, l)))(
            jnp.arange(n))
        params = {"embed": top_weight(cfgd, key, "embed"), "layers": layers,
                  "window": jnp.full((n,), GLOBAL_WINDOW, jnp.float32),
                  "final_norm": top_weight(cfgd, key, "final_norm")}
        if not cfgd["tie_embeddings"]:
            params["lm_head"] = top_weight(cfgd, key, "lm_head")
        return params

    return make(key)


# ---- reference -------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfgd_items", "quant"))
def _layer(w, x, cfgd_items, quant):
    c = _unfreeze(cfgd_items)
    a = c["attn"]
    T = x.shape[0]
    eps = c["norm_eps"]
    pos = jnp.arange(T)
    h = _rms_norm(x, w["ln1"], eps)
    q = _linear(h, w["wq"], quant).reshape(T, a["n_heads"], a["head_dim"])
    k = _linear(h, w["wk"], quant).reshape(T, a["n_kv_heads"], a["head_dim"])
    v = _linear(h, w["wv"], quant).reshape(T, a["n_kv_heads"], a["head_dim"])
    q, k = _rope(q, pos, a["rope_theta"]), _rope(k, pos, a["rope_theta"])
    x = x + _linear(_attention(q, k, v), w["wo"], quant)

    def mlp(xb):
        hb = _rms_norm(xb, w["ln2"], eps)
        g = jax.nn.silu(_linear(hb, w["w_gate"], quant))
        return xb + _linear(g * _linear(hb, w["w_up"], quant), w["w_down"],
                            quant)

    rows = x.reshape(T // ROW_BLOCK, ROW_BLOCK, -1)
    return jax.lax.map(mlp, rows).reshape(x.shape)


def _frozen(cfgd):
    """Hashable view of the sizes the reference needs (a jit static)."""
    keep = ("d_model", "d_ff", "vocab", "norm_eps", "n_layers", "dtype")
    attn = tuple(sorted((k, v) for k, v in cfgd["attn"].items()
                        if k in ("n_heads", "n_kv_heads", "head_dim",
                                 "rope_theta")))
    return tuple(sorted([(k, cfgd[k]) for k in keep] + [("attn", attn)]))


def _unfreeze(frozen) -> dict:
    c = dict(frozen)
    c["attn"] = dict(c["attn"])
    return c


@lru_cache(maxsize=None)
def _layer_maker(frozen):
    cfgd = _unfreeze(frozen)
    return jax.jit(lambda key, layer: layer_weights(cfgd, key, layer))


def final_hidden(cfgd, seed: int, tokens: np.ndarray, rows: slice,
                 patches=None, quant: Optional[str] = None):
    """Final-norm hidden states (n, d) f32 at positions ``rows`` of a
    forward pass over ``tokens``, float32 at ``HIGHEST`` (``quant``: the
    control's rounding of every linear layer). ``patches``: (1, P, d)
    image-patch embeddings for the first P positions, or None."""
    check(cfgd)
    key = seed_key(seed)
    T = len(tokens)
    Tp = -(-T // PAD) * PAD
    toks = np.zeros(Tp, np.int32)
    toks[:T] = tokens
    embed = top_weight(cfgd, key, "embed")
    x = embed[jnp.asarray(toks)].astype(jnp.float32) * np.sqrt(cfgd["d_model"])
    del embed
    if patches is not None:
        P = patches.shape[1]
        x = x.at[:P].set(patches[0].astype(jnp.float32))
    frozen = _frozen(cfgd)
    make_layer = _layer_maker(frozen)
    for layer in range(cfgd["n_layers"]):
        x = _layer(make_layer(key, layer), x, frozen, quant)
    gamma = top_weight(cfgd, key, "final_norm")
    return _rms_norm(x[rows], gamma, cfgd["norm_eps"])


# ---- operations ------------------------------------------------------------

def decode_matmul_flops(cfgd) -> int:
    """Matmul operations of one slot's decode step: 2 per parameter of the
    q/k/v/o projections, the gated MLP and the LM head; attention itself
    excluded."""
    check(cfgd)
    d, a = cfgd["d_model"], cfgd["attn"]
    hq, hkv = a["n_heads"] * a["head_dim"], a["n_kv_heads"] * a["head_dim"]
    per_layer = d * (hq + 2 * hkv) + hq * d + 3 * d * cfgd["d_ff"]
    return 2 * (cfgd["n_layers"] * per_layer + d * cfgd["vocab"])
