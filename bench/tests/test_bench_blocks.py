"""Block modules (``bench/blocks/<block>.py``): what the ``gqa`` block
computes and refuses, how ``spec`` finds a block and builds the program's
sub-configs, and a whole tiny cell run through a block that lives outside
``bench/blocks/``, as a later configuration's would."""
import copy

import pytest

from bench import readings, run, spec
from bench.tests.tiny import tiny_config, tiny_traffic

GQA = spec.load_block({"block": "gqa"})
CFG = {"family": "dense", "d_model": 8, "d_ff": 16, "vocab": 10,
       "n_layers": 2, "attn": {"n_heads": 4, "n_kv_heads": 2, "head_dim": 4}}
CPU_DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
SEED = 2 ** 33 + 5
LIMIT = 0.45            # the tiny cells' limit (test_bench_run.TINY_LIMIT)


def test_decode_matmul_flops_by_hand():
    # per layer: q 8x16, k 8x8, v 8x8, o 16x8, MLP 3 x 8x16; head 8x10
    per_layer = 8 * 16 + 8 * 8 + 8 * 8 + 16 * 8 + 3 * 8 * 16
    assert GQA.decode_matmul_flops(CFG) == 2 * (2 * per_layer + 8 * 10)


def _unsupported(change):
    cfgd = copy.deepcopy(CFG)
    change(cfgd)
    return cfgd


UNSUPPORTED = {
    "pattern": lambda c: c["attn"].update(pattern=["l", "g"],
                                          sliding_window=128),
    "softcap": lambda c: c["attn"].update(softcap=50.0),
    "moe": lambda c: c.update(moe={"num_experts": 8, "top_k": 2,
                                   "d_expert": 16}),
    "family": lambda c: c.update(family="ssm"),
}
CALLS = {
    "make_params": lambda cfgd: GQA.make_params(cfgd, 0),
    "final_hidden": lambda cfgd: GQA.final_hidden(
        cfgd, 0, [1, 2, 3], slice(0, 1)),
    "decode_matmul_flops": GQA.decode_matmul_flops,
}


@pytest.mark.parametrize("call", CALLS)
@pytest.mark.parametrize("key", UNSUPPORTED)
def test_gqa_refuses_what_its_reference_does_not_compute(key, call):
    with pytest.raises(ValueError, match=key):
        CALLS[call](_unsupported(UNSUPPORTED[key]))


def test_a_configuration_must_name_its_block(tmp_path, monkeypatch):
    with pytest.raises(KeyError, match="'block'"):
        spec.load_block({"family": "dense"})
    with pytest.raises(FileNotFoundError, match="no-such-block"):
        spec.load_block({"block": "no-such-block"})
    with pytest.raises(ValueError):
        spec.load_block({"block": "../run"})
    (tmp_path / "half.py").write_text("def make_params(cfgd, seed):\n"
                                      "    return {}\n")
    monkeypatch.setattr(spec, "BLOCKS_DIR", tmp_path)
    with pytest.raises(AttributeError, match="final_hidden"):
        spec.load_block({"block": "half"})


def test_model_config_builds_sub_configs_from_the_file():
    from repro.configs.base import AttnConfig, MoEConfig
    cfgd = tiny_config()
    cfgd["family"] = "moe"
    cfgd["attn"].update(pattern=["l", "l", "l", "g"], sliding_window=128)
    cfgd["moe"] = {"num_experts": 8, "top_k": 2, "d_expert": 64}
    cfg = spec.model_config(cfgd)
    assert cfg.moe == MoEConfig(num_experts=8, top_k=2, d_expert=64)
    assert isinstance(cfg.attn, AttnConfig)
    assert cfg.attn.pattern == ("l", "l", "l", "g")
    assert cfg.retro.sink == cfgd["retro"]["sink"] and cfg.ssm is None
    cfgd["moe"]["no_such_knob"] = 1
    with pytest.raises(TypeError, match="no_such_knob"):
        spec.model_config(cfgd)


OUTSIDE = '''"""A block outside bench/blocks/: gqa's functions, each call counted."""
import importlib.util

from bench import spec

_spec = importlib.util.spec_from_file_location(
    "outside_gqa", spec.BENCH_DIR / "blocks" / "gqa.py")
gqa = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(gqa)
CALLS = []


def make_params(cfgd, seed):
    CALLS.append("make_params")
    return gqa.make_params(cfgd, seed)


def final_hidden(cfgd, seed, tokens, rows, patches=None, quant=None):
    CALLS.append("final_hidden")
    return gqa.final_hidden(cfgd, seed, tokens, rows, patches, quant)


def decode_matmul_flops(cfgd):
    CALLS.append("decode_matmul_flops")
    return gqa.decode_matmul_flops(cfgd)
'''


def _tiny_cell(cfgd, traffic):
    return run.run_cell({"name": "tiny"}, cfgd, traffic,
                        {"max_logit_gap": LIMIT},
                        [{"name": "out_tok_s", "unit": "tokens/s"}], SEED,
                        1.0, False, CPU_DEVICE)


def test_a_block_outside_the_blocks_directory_runs_a_whole_cell(
        tmp_path, monkeypatch):
    """The harness (run, readings, reference) reaches a configuration's
    layers only through its ``block``: a block module in another directory
    serves and checks a tiny cell with the same results as ``gqa``."""
    traffic = tiny_traffic("imgdoc-qa")
    traffic["blocks"] = 1
    cfgd = tiny_config("llava-next-34b")
    want = _tiny_cell(cfgd, traffic)

    (tmp_path / "outside.py").write_text(OUTSIDE)
    monkeypatch.setattr(spec, "BLOCKS_DIR", tmp_path)
    cfgd = dict(cfgd, block="outside")
    outside = spec.load_block(cfgd)
    got = _tiny_cell(cfgd, traffic)
    assert {"make_params", "final_hidden"} <= set(outside.CALLS)
    assert got["correct"] is want["correct"] is True
    assert got["checks"] == want["checks"]
    assert (got["attempted"], got["failed"]) == (want["attempted"],
                                                 want["failed"])

    outside.CALLS.clear()
    (rec,) = readings.readings(cfgd, traffic, [SEED], set(), n=2,
                               emit=lambda line: None)
    assert outside.CALLS.count("make_params") == 1
    assert "final_hidden" in outside.CALLS and rec["failed"] == 0
