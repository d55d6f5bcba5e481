"""Plain float32 reference of the served model, and the comparison that
decides ``correct``.

The reference imports nothing of the program. Its layers are the
configuration's block (``bench/blocks/<block>.py``, named by the file's
``block`` key): the block's ``final_hidden`` makes the weights again from
the seed, one layer at a time, and runs the architecture's equations in
float32 with every matmul at ``HIGHEST`` precision, with exact attention
(no wave index, no cache). This module holds what every block shares:
the linear layer and its control rounding, RMSNorm with a ``(1 + gamma)``
scale, half-split rotary embeddings, exact causal grouped-query
attention, the head and the comparison. Image-patch embeddings replace
the token embeddings of the first ``num_patch_tokens`` positions.

For one served request (prompt, served tokens) the forward pass runs over
the prompt and every served token but the last; the row at position
``len(prompt) - 1 + j`` gives the reference's logits for served token
``j``. Its gap is ``max(logits) - logits[served token]``, zero where the
program's greedy token is the reference's best. ``correct`` compares the
widest and the mean gap of a sample of requests with the cell's limits.

A control is the same model computed one precision step below the
configuration's bfloat16: every linear layer's weights rounded per output
column and its input rows per row (symmetric absmax scales) to int8
(``quant="int8"``) or to float8 e4m3 (``quant="fp8"``). It is read as the
gap, in the float32 reference, of the token the control puts first at
each position.
"""
from __future__ import annotations

from functools import partial
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from bench import spec
from bench.weights import seed_key, top_weight

HI = jax.lax.Precision.HIGHEST
PAD = 1024          # positions are padded to a multiple (fewer compiles)
Q_BLOCK = 256       # attention query rows per block
ROW_BLOCK = 1024    # MLP rows per block
HEAD_BLOCK = 128    # rows per block of the (rows, vocab) logits


def _q8(x, axis):
    """Symmetric int8 rounding along ``axis`` (absmax scale), dequantized."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _f8(x, axis):
    """float8 e4m3 rounding along ``axis`` (absmax scaled to 448)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


QUANT = {"int8": _q8, "fp8": _f8}


def _linear(a, w, quant):
    w = w.astype(jnp.float32)
    if quant is not None:
        a, w = QUANT[quant](a, -1), QUANT[quant](w, 0)
    return jnp.matmul(a, w, precision=HI)


def _rms_norm(x, g, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + g.astype(jnp.float32))


def _rope(x, pos, theta):
    """x: (T, H, hd); rotates the first half of each head against the
    second half at frequencies theta^(-2i/hd)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Exact causal attention. q: (T, Hq, hd); k, v: (T, Hkv, hd)."""
    T, Hq, hd = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    kpos = jnp.arange(T)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        qb = qb.reshape(Q_BLOCK, Hkv, G, hd)
        s = jnp.einsum("qhgd,khd->hgqk", qb, k, precision=HI) / np.sqrt(hd)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, None, None, :] <= qpos[None, None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hgqk,khd->qhgd", p, v, precision=HI)
        return o.reshape(Q_BLOCK, Hq * hd)

    return jax.lax.map(block, jnp.arange(T // Q_BLOCK)).reshape(T, Hq * hd)


def _head(cfgd, key):
    name = "embed" if cfgd["tie_embeddings"] else "lm_head"
    w = top_weight(cfgd, key, name)
    return w.T if cfgd["tie_embeddings"] else w


@partial(jax.jit, static_argnames=("quant",))
def _head_rows(h, w, tok, quant):
    """Per row: (max logit, logit of ``tok``, argmax) of ``h @ w``."""
    n = h.shape[0]
    pad = -n % HEAD_BLOCK
    hp = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, HEAD_BLOCK, h.shape[1])
    tp = jnp.pad(tok, (0, pad)).reshape(-1, HEAD_BLOCK)

    def one(args):
        hb, tb = args
        lg = _linear(hb, w, quant)
        return (lg.max(-1), jnp.take_along_axis(lg, tb[:, None], 1)[:, 0],
                jnp.argmax(lg, -1).astype(jnp.int32))

    mx, at, am = jax.lax.map(one, (hp, tp))
    return mx.reshape(-1)[:n], at.reshape(-1)[:n], am.reshape(-1)[:n]


def served_gaps(cfgd, seed: int, prompt: np.ndarray, served: List[int],
                patches=None, controls=()):
    """Gaps (n,) of the served tokens in the float32 reference, and for
    each control in ``controls`` (``"int8"``, ``"fp8"``) the gaps of that
    control's own first choices at the same positions. Returns ``(gaps,
    {control: gaps})``."""
    served = np.asarray(served, np.int32)
    tokens = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    rows = slice(len(prompt) - 1, len(prompt) - 1 + len(served))
    key = seed_key(seed)
    h = spec.load_block(cfgd).final_hidden(cfgd, seed, tokens, rows, patches)
    w = _head(cfgd, key)
    mx, at, _ = _head_rows(h, w, jnp.asarray(served), None)
    gaps = np.asarray(mx - at, np.float64)
    ctl = {}
    for quant in controls:
        c_tok = control_choices(cfgd, seed, prompt, served, patches, quant)
        mx, at, _ = _head_rows(h, w, jnp.asarray(c_tok), None)
        ctl[quant] = np.asarray(mx - at, np.float64)
    return gaps, ctl


def control_choices(cfgd, seed: int, prompt: np.ndarray, served: List[int],
                    patches=None, quant: str = "fp8") -> np.ndarray:
    """The ``quant`` control's first choice (n,) at the position of each
    served token, given the prompt and the served tokens before it."""
    served = np.asarray(served, np.int32)
    tokens = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    rows = slice(len(prompt) - 1, len(prompt) - 1 + len(served))
    hq = spec.load_block(cfgd).final_hidden(cfgd, seed, tokens, rows, patches,
                                            quant=quant)
    _, _, c_tok = _head_rows(hq, _head(cfgd, seed_key(seed)),
                             jnp.asarray(served), quant)
    return np.asarray(c_tok)


def control_decode(cfgd, seed: int, prompt: np.ndarray, n: int,
                   patches=None, quant: str = "fp8") -> np.ndarray:
    """``n`` tokens decoded greedily by the ``quant`` control after
    ``prompt``, each step a whole forward pass (small sizes only)."""
    w = _head(cfgd, seed_key(seed))
    final_hidden = spec.load_block(cfgd).final_hidden
    tokens = np.asarray(prompt, np.int32)
    out = []
    for _ in range(n):
        row = slice(len(tokens) - 1, len(tokens))
        h = final_hidden(cfgd, seed, tokens, row, patches, quant=quant)
        _, _, tok = _head_rows(h, w, jnp.zeros(1, jnp.int32), quant)
        out.append(int(tok[0]))
        tokens = np.append(tokens, np.int32(out[-1]))
    return np.asarray(out, np.int32)
