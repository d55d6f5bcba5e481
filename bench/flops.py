"""Operations and bytes from shapes, and the table of peaks.

Nothing here reads the program: the wave-index zone sizes are worked out
from the configuration's ``retro`` knobs with the paper's arithmetic
(clusters of ``avg_cluster`` tokens over the region between the sink and
the local window, ``retrieval_frac`` and ``estimation_frac`` of them).
"""
from __future__ import annotations

import json
from dataclasses import dataclass

from bench.spec import BENCH_DIR

F32 = 4
I32 = 4


def peaks(device_kind: str) -> dict:
    with open(BENCH_DIR / "peaks.json") as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def dtype_bytes(dtype: str) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[dtype]


@dataclass(frozen=True)
class Zones:
    sink: int           # sink tokens
    local_buf: int      # local window + staging buffer (tokens)
    r: int              # retrieved clusters
    e: int              # estimated clusters
    cap: int            # tokens per cluster block


def zones(retro: dict, max_context: int) -> Zones:
    """Zone sizes of a decode step at a ``max_context`` geometry."""
    region = max(0, max_context - retro["sink"] - retro["local"])
    seg = retro["prefill_segment"]
    m_prefill = (region // seg) * (seg // retro["avg_cluster"])
    if region % seg:
        m_prefill += max(1, (region % seg) // retro["avg_cluster"])
    m = max(1, max_context // retro["avg_cluster"])
    r = min(max(1, round(m * retro["retrieval_frac"])), m_prefill)
    e = min(max(1, round(m * retro["estimation_frac"])), max(0, m_prefill - r))
    return Zones(sink=retro["sink"],
                 local_buf=retro["local"] + retro["update_segment"],
                 r=r, e=e, cap=retro["cluster_cap"])


def wave_attention_call(cfgd: dict, batch: int, max_context: int):
    """(flops, bytes) of one paged wave-attention call over ``batch`` rows
    of every KV head: the steady zone (sink + local buffer) and the ``r``
    retrieved cluster blocks attended exactly, the estimation zone folded
    from its ``r + e`` centroid scores and value sums. Bytes count each
    operand once, the key/value zones at the stores' dtype with their
    int32 positions, the query, estimation tensors and output in f32."""
    a = cfgd["attn"]
    hkv, hd = a["n_kv_heads"], a["head_dim"]
    g = a["n_heads"] // hkv
    z = zones(cfgd["retro"], max_context)
    sb = dtype_bytes(cfgd["dtype"])
    est = z.r + z.e
    tokens = z.sink + z.local_buf + z.r * z.cap
    per_row_bytes = (
        2 * g * hd * F32                                    # q in, out
        + 2 * tokens * hd * sb                              # steady + clusters
        + (z.local_buf + z.r * z.cap) * I32                 # their positions
        + 2 * g * est * F32 + est * hd * F32)               # estimation zone
    per_row_flops = 4 * g * hd * tokens + 2 * g * est * hd
    rows = batch * hkv
    return per_row_flops * rows, per_row_bytes * rows


def decode_matmul_flops(cfgd: dict) -> int:
    """Matmul operations of one slot's decode step: 2 per parameter of the
    q/k/v/o projections, the gated MLP and the LM head; attention itself
    excluded."""
    d, a = cfgd["d_model"], cfgd["attn"]
    hq, hkv = a["n_heads"] * a["head_dim"], a["n_kv_heads"] * a["head_dim"]
    per_layer = d * (hq + 2 * hkv) + hq * d + 3 * d * cfgd["d_ff"]
    return 2 * (cfgd["n_layers"] * per_layer + d * cfgd["vocab"])


def roofline_seconds(flops: float, nbytes: float, peak: dict):
    """(least seconds, bound) of work on a chip with ``peak``."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
