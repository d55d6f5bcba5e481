"""``bench/flops.py`` against hand counts at a tiny shape."""
import pytest

from bench import flops

RETRO = {"avg_cluster": 16, "cluster_cap": 32, "prefill_segment": 8192,
         "update_segment": 1024, "sink": 4, "local": 64,
         "retrieval_frac": 0.018, "estimation_frac": 0.232}
CFG = {"d_model": 8, "d_ff": 16, "vocab": 10, "n_layers": 2,
       "dtype": "bfloat16", "retro": RETRO,
       "attn": {"n_heads": 4, "n_kv_heads": 2, "head_dim": 4}}


def test_zones_at_32k_follow_the_paper_budgets():
    z = flops.zones(RETRO, 32768)
    # 2048 clusters of 16: 1.8 % retrieved, 23.2 % estimated
    assert (z.r, z.e, z.sink, z.local_buf, z.cap) == (37, 475, 4, 1088, 32)


def test_zones_clamp_to_the_clustered_region():
    z = flops.zones(RETRO, 100)     # 32 tokens between sink and local
    assert (z.r, z.e) == (1, 1)


def test_wave_attention_call_by_hand():
    # 32k context: 37 retrieved blocks of 32, 512 estimation entries;
    # G = 2 query heads per KV head, hd = 4, 2 KV heads, batch 3
    fl, nb = flops.wave_attention_call(CFG, 3, 32768)
    tokens = 4 + 1088 + 37 * 32
    per_row_bytes = (2 * 2 * 4 * 4 + 2 * tokens * 4 * 2
                     + (1088 + 37 * 32) * 4 + 2 * 2 * 512 * 4 + 512 * 4 * 4)
    per_row_flops = 4 * 2 * 4 * tokens + 2 * 2 * 512 * 4
    assert nb == 6 * per_row_bytes
    assert fl == 6 * per_row_flops


def test_roofline_names_its_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_seconds(50.0, 100.0, peak) == (10.0, "memory")
    assert flops.roofline_seconds(5000.0, 10.0, peak) == (50.0, "compute")


def test_unknown_device_kind_is_an_error():
    assert flops.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")
