"""Tiny configuration and traffic for the CPU tests of the harness: the
configurations' and traffic files' shapes at toy sizes."""
import copy
import json

from bench.spec import BENCH_DIR

RETRO = {"avg_cluster": 8, "cluster_cap": 16, "prefill_segment": 256,
         "update_segment": 128, "sink": 4, "local": 32,
         "retrieval_frac": 0.06, "estimation_frac": 0.25, "kmeans_iters": 3}


def tiny_config(name: str = "mistral-7b") -> dict:
    with open(BENCH_DIR / "configs" / f"{name}.json") as f:
        cfgd = json.load(f)
    cfgd = copy.deepcopy(cfgd)
    cfgd.update(n_layers=2, d_model=64, d_ff=128, vocab=512)
    # the published head size: the planted positional scores need its
    # rotary frequencies (bench/weights.py)
    cfgd["attn"].update(n_heads=4, n_kv_heads=2,
                        head_dim=cfgd["attn"]["head_dim"])
    cfgd["retro"].update(RETRO)
    if cfgd["num_patch_tokens"]:
        cfgd["num_patch_tokens"] = 40
    return cfgd


def tiny_traffic(name: str = "doc32k-decode") -> dict:
    with open(BENCH_DIR / "traffic" / f"{name}.json") as f:
        traffic = json.load(f)
    traffic = copy.deepcopy(traffic)
    traffic["engine"].update(max_context=320, gen_headroom=256,
                             prefill_chunk=64)
    if traffic["patch_pool"]:
        traffic.update(prompt_lens=[96, 160, 200], output_lens=[8, 12, 16],
                       check_tokens=40)
    else:
        # short prompts, long answers past one index update: the served
        # tokens are most of what decode attends to
        traffic.update(prompt_lens=[40, 64], output_lens=[200, 250],
                       check_tokens=100)
    return traffic
