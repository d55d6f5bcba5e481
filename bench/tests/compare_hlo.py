"""Compare the optimized HLO of the engine's jitted serve stages between two
checkouts, at every cell's shapes, for a described TPU v5e (no chip needed).

    python3 bench/tests/compare_hlo.py dump TREE OUT    # one checkout
    python3 bench/tests/compare_hlo.py diff OUT_A OUT_B

``dump`` compiles ``decode``, ``flush``, ``chunk``, ``fin`` (at the longest
prompt) and, in a cell with patches, ``chunk_pe`` from TREE's ``src/``, with
the cell read from this checkout's ``BENCHMARK.json``, and writes each
program's text to ``OUT/<cell>.<stage>.hlo``. ``diff`` drops what only
names or locates code (``metadata={...}``, the file, function and stack
frame tables, and the Mosaic kernels' serialized bodies, which carry source
locations), renames instructions in order of first use, and prints per
program whether the two are identical. Run ``dump`` once per checkout, each
in a process of its own (two ``repro`` packages cannot share one).
"""
from __future__ import annotations

import difflib
import os
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
DEBUG_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def dump(tree: Path, out: Path) -> None:
    sys.path[:0] = [str(tree / "src"), str(ROOT)]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import spec
    from repro.kernels.wave_attention import ops
    from repro.models import model as M
    from repro.serving.engine import ServeEngine

    jax.config.update("jax_enable_compilation_cache", False)
    ops.on_cpu = lambda: False      # compile the Mosaic kernel, not its emulation
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def specs(fn):
        return jax.tree.map(lambda a: sd(a.shape, a.dtype), jax.eval_shape(fn))

    out.mkdir(parents=True, exist_ok=True)
    bench = spec.load_benchmark()
    for cell in bench["workloads"]:
        cfgd = spec.load_config(bench, cell["config"])
        traffic = spec.load_traffic(cell["traffic"])
        cfg, e = spec.model_config(cfgd), traffic["engine"]
        B, ctx, gh, C = (e["slots"], e["max_context"], e["gen_headroom"],
                         e["prefill_chunk"])
        params = jax.tree.map(lambda a: sd(a.shape, a.dtype),
                              M.param_specs(cfg))
        eng = ServeEngine(cfg, params, runtime=e["runtime"],
                          admission=e["admission"], attn_impl=e["attn_impl"],
                          offload=e["offload"], max_context=ctx,
                          gen_headroom=gh, prefill_chunk=C)
        state = specs(lambda: M.make_serve_state(
            cfg, B, ctx, runtime=e["runtime"], gen_headroom=gh,
            zero_fill=True))
        cst = specs(lambda: M.make_prefill_chunk_state(
            cfg, 1, ctx, runtime=e["runtime"], chunk=C, gen_headroom=gh))
        decode, flush = eng._decode_fns(B, ctx)
        chunk, chunk_pe = eng._chunk_fns(ctx)
        toks, clen = sd((1, C), jnp.int32), sd((1,), jnp.int32)
        stages = {
            "decode": lambda: decode.lower(params, state, sd((B,), jnp.int32),
                                           sd((B,), jnp.bool_)),
            "flush": lambda: flush.lower(state),
            "chunk": lambda: chunk.lower(params, cst, toks, clen),
            "fin": lambda: eng._finalize_fn(max(traffic["prompt_lens"]),
                                            ctx).lower(state, cst,
                                                       sd((), jnp.int32))}
        if traffic["patch_pool"]:
            pe = sd((1, cfgd["num_patch_tokens"], cfg.d_model),
                    jnp.dtype(cfg.dtype))
            stages["chunk_pe"] = lambda: chunk_pe.lower(params, cst, toks,
                                                        clen, pe)
        for name, lower in stages.items():
            text = lower().compile().as_text()
            (out / f"{cell['name']}.{name}.hlo").write_text(text)
            print(f"{cell['name']} {name}: {len(text)} chars", flush=True)


def normalize(text: str) -> str:
    """The program without what only names or locates code."""
    kept, skipping = [], False
    for line in text.split("\n"):
        if line in DEBUG_TABLES:
            skipping = True
        elif skipping and line.startswith("%"):
            skipping = False
        if not skipping:
            kept.append(line)
    text = re.sub(r",? metadata=\{[^{}]*\}", "", "\n".join(kept))
    text = re.sub(r'"body":"[^"]*"', '"body":"<kernel>"', text)
    names: dict = {}
    return re.sub(r"%[A-Za-z_][\w.\-]*",
                  lambda m: names.setdefault(m.group(0), f"%v{len(names)}"),
                  text)


def diff(a: Path, b: Path) -> int:
    differ = 0
    for fa in sorted(a.glob("*.hlo")):
        fb = b / fa.name
        d = list(difflib.unified_diff(
            normalize(fa.read_text()).split("\n"),
            normalize(fb.read_text()).split("\n"), lineterm="", n=0)) \
            if fb.is_file() else ["missing in " + str(b)]
        print(fa.stem, "identical" if not d else f"{len(d)} lines differ")
        for line in d[:20]:
            print("   ", line[:300])
        differ += bool(d)
    return differ


if __name__ == "__main__":
    if sys.argv[1:2] == ["dump"]:
        dump(Path(sys.argv[2]).resolve(), Path(sys.argv[3]))
    elif sys.argv[1:2] == ["diff"]:
        sys.exit(1 if diff(Path(sys.argv[2]), Path(sys.argv[3])) else 0)
    else:
        sys.exit(__doc__)
