"""Smoke run of the served path on one TPU chip.

    python chip_smoke.py [--seed N]

Serves gemma2-2b at its published widths (d_model 2304, 8 query / 4 KV
heads of 256, d_ff 9216, vocab 256000, bf16, attention softcap 50,
alternating 4096-token window and global layers) with random weights from
``--seed``, through ``ServeEngine.serve`` on the retro runtime with chunked
admission and the fused paged kernel (``attn_impl="fused"``). Depth is cut
to ``SMOKE_LAYERS``: with two slots at a 12k context every admitting slot
holds its own wave-index build next to the served state, and at all 26
layers that alone exceeds the chip's 16 GiB.

Phases, in one process (the chip belongs to one process at a time):

1. compile the decode step and assert that it holds the Mosaic kernel
   (``tpu_custom_call``): the kernel runs, not the interpreter or the jnp
   emulation;
2. direct: 4 requests on 2 slots, prompts of about 12k, 9k and 6k tokens
   (8192-token segmented clustering runs at admission), one request decoding
   past a 1024-token decode-time flush;
3. offload: the same queue with the cluster stores on the host; its tokens
   must equal the direct phase's, token for token;
4. kernel check: fused decode attention against ``impl="jnp"`` on one real
   layer state (layer 0's K/V of a 12k prompt, built by the wave index),
   for a window and a global layer.

Any failed check raises and exits nonzero. Times printed on the way are
smoke timings of one cold run, not benchmark numbers. The last line of
stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.gemma2_2b import CONFIG  # noqa: E402
from repro.core import attention as wa  # noqa: E402
from repro.core.wave_index import append_token, prefill_build  # noqa: E402
from repro.core.zones import plan_zones  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models import transformer  # noqa: E402
from repro.serving.engine import Request, ServeEngine  # noqa: E402

SMOKE_LAYERS = 8
SLOTS = 2
MAX_CONTEXT = 12288
GEN_HEADROOM = 2048
PREFILL_CHUNK = 1024
PROMPT_LENS = (12000, 9000, 6000, 9000)
# the first request decodes past one decode-time flush (update_segment)
LONG_NEW_TOKENS = CONFIG.retro.update_segment + 32
SHORT_NEW_TOKENS = 16
# fused vs jnp on bf16 stores: the kernel folds an f32 query against
# f32-widened blocks, the jnp path a bf16-rounded query with f32
# accumulation, so they differ by about bf16 rounding of the output scale
KERNEL_REL_TOL = 2e-2


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def make_queue(cfg, prompt_lens, new_tokens, seed: int):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, cfg.vocab, n).astype(np.int32),
                    max_new_tokens=t)
            for n, t in zip(prompt_lens, new_tokens)]


def make_engine(cfg, params, *, offload: bool, max_context: int,
                gen_headroom: int, prefill_chunk: int) -> ServeEngine:
    return ServeEngine(cfg, params, runtime="retro", admission="chunked",
                       attn_impl="fused", offload=offload,
                       max_context=max_context, gen_headroom=gen_headroom,
                       prefill_chunk=prefill_chunk)


def compile_decode(engine: ServeEngine, params, slots: int, max_context: int):
    """AOT-compile the engine's direct decode step (the serve loop reuses the
    executable). Returns (compiled, seconds)."""
    decode, _ = engine._decode_fns(slots, max_context)
    specs = M.serve_state_specs(engine.cfg, slots, max_context,
                                gen_headroom=engine.gen_headroom)
    t0 = time.perf_counter()
    compiled = decode.lower(params, specs,
                            jax.ShapeDtypeStruct((slots,), jnp.int32),
                            jax.ShapeDtypeStruct((slots,), jnp.bool_)
                            ).compile()
    return compiled, time.perf_counter() - t0


def serve_phase(name: str, engine: ServeEngine, reqs, slots: int):
    t0 = time.perf_counter()
    metrics = engine.serve(reqs, batch_size=slots)
    wall = time.perf_counter() - t0
    for i, r in enumerate(reqs):
        check(r.status == "ok", f"{name}: request {i} ended {r.status!r}")
        check(len(r.out_tokens) == r.max_new_tokens,
              f"{name}: request {i} gave {len(r.out_tokens)} of "
              f"{r.max_new_tokens} tokens")
        check(all(0 <= t < engine.cfg.vocab for t in r.out_tokens),
              f"{name}: request {i} sampled an id outside the vocabulary")
    check(metrics.flushes >= 1, f"{name}: no decode-time flush ran")
    print(f"smoke timing [{name}]: wall {wall:.1f}s, TTFT p50 "
          f"{metrics.ttft_p50_s:.2f}s, decode {metrics.decode_tps:.1f} tok/s "
          f"over {metrics.steps} steps, {metrics.flushes} flush(es)",
          flush=True)
    return metrics


def kernel_check(cfg, params, prompt_len: int, max_context: int,
                 gen_headroom: int, seed: int):
    """Fused decode attention vs ``impl="jnp"`` on one layer state built by
    the wave index from layer 0's real K/V. Returns the worst relative
    error max|fused - jnp| / max|jnp| over a window and a global layer."""
    a, retro = cfg.attn, cfg.retro
    plan = plan_zones(max_context, retro, gen_headroom)
    tokens = jnp.asarray(np.random.default_rng(seed + 1).integers(
        0, cfg.vocab, (1, prompt_len)), jnp.int32)

    @jax.jit
    def build(params, tokens):
        lp = jax.tree.map(lambda x: x[0], params["layers"])
        x = transformer.embed_tokens(params, cfg, tokens)
        h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = L.attention_qkv(lp["attn"], h, a.n_heads, a.n_kv_heads,
                                  a.head_dim, jnp.arange(prompt_len),
                                  a.rope_theta)
        st = prefill_build(k[:, :-1], v[:, :-1], retro, plan.m_max,
                           dtype=jnp.dtype(cfg.dtype))
        return q[:, -1], append_token(st, k[:, -1], v[:, -1])

    @partial(jax.jit, static_argnames=("impl", "windowed"))
    def attend(q, st, impl, windowed):
        window = jnp.float32(a.sliding_window) if windowed else None
        return wa.wave_attention_decode(q, st, retro, plan, window=window,
                                        softcap=a.softcap, impl=impl).out

    q, st = build(params, tokens)
    worst = 0.0
    for windowed in (True, False):
        ref = np.asarray(attend(q, st, "jnp", windowed), np.float32)
        out = np.asarray(attend(q, st, "fused", windowed), np.float32)
        check(np.isfinite(out).all(), "fused attention output not finite")
        check(out.shape == ref.shape, f"fused shape {out.shape} != {ref.shape}")
        err = float(np.abs(out - ref).max() / max(np.abs(ref).max(), 1e-30))
        print(f"kernel check ({'window' if windowed else 'global'} layer): "
              f"max|fused-jnp|/max|jnp| = {err:.3e}", flush=True)
        worst = max(worst, err)
    return worst


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}")
    print(f"cache dir: {enable_compile_cache()}", flush=True)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {device}", flush=True)

    cfg = CONFIG.replace(n_layers=SMOKE_LAYERS)
    t0 = time.perf_counter()
    params = jax.jit(lambda k: M.init_params(cfg, k))(
        jax.random.PRNGKey(args.seed))
    jax.block_until_ready(params)
    print(f"set-up: {cfg.arch_id} at {cfg.n_layers} layers, "
          f"{sum(x.size for x in jax.tree.leaves(params)) / 1e9:.2f}B params "
          f"initialised in {time.perf_counter() - t0:.1f}s", flush=True)

    geom = dict(max_context=MAX_CONTEXT, gen_headroom=GEN_HEADROOM,
                prefill_chunk=PREFILL_CHUNK)
    new_tokens = (LONG_NEW_TOKENS,) + (SHORT_NEW_TOKENS,) * (
        len(PROMPT_LENS) - 1)

    direct = make_engine(cfg, params, offload=False, **geom)
    compiled, secs = compile_decode(direct, params, SLOTS, MAX_CONTEXT)
    mem = compiled.memory_analysis()
    print(f"set-up: decode step compiled in {secs:.1f}s; memory_analysis "
          f"argument {mem.argument_size_in_bytes} output "
          f"{mem.output_size_in_bytes} alias {mem.alias_size_in_bytes} "
          f"temp {mem.temp_size_in_bytes} bytes", flush=True)
    check("tpu_custom_call" in compiled.as_text(),
          "the compiled decode step holds no tpu_custom_call (Mosaic kernel)")
    del compiled

    reqs_d = make_queue(cfg, PROMPT_LENS, new_tokens, args.seed)
    serve_phase("direct", direct, reqs_d, SLOTS)
    del direct

    reqs_o = make_queue(cfg, PROMPT_LENS, new_tokens, args.seed)
    m_off = serve_phase("offload", make_engine(cfg, params, offload=True,
                                               **geom), reqs_o, SLOTS)
    for i, (d, o) in enumerate(zip(reqs_d, reqs_o)):
        check(d.out_tokens == o.out_tokens,
              f"request {i}: offload tokens differ from direct")
    print(f"offload == direct: {len(reqs_d)} requests, "
          f"{sum(len(r.out_tokens) for r in reqs_d)} tokens identical; "
          f"cache hit ratio {m_off.cache_hit_ratio:.3f}, "
          f"{m_off.bytes_over_link} bytes over the link", flush=True)

    err = kernel_check(cfg, params, PROMPT_LENS[0], **{
        k: geom[k] for k in ("max_context", "gen_headroom")}, seed=args.seed)
    check(err <= KERNEL_REL_TOL,
          f"fused vs jnp relative error {err:.3e} > {KERNEL_REL_TOL}")

    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'n/a')}",
          flush=True)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
