"""Serving launcher: continuous-batching demo with the wave-index runtime.

Ragged prompt lengths and staggered generation lengths exercise the slot
scheduler: finished requests free their slot mid-stream and queued requests
are admitted mid-stream — by default one fixed-size prefill chunk at a time,
interleaved between decode steps (``--admission blocking`` restores the
monolithic per-slot prefill for comparison; inter-token p50/p99 shows the
admission interference each mode leaves behind).

    PYTHONPATH=src python -m repro.launch.serve --arch gemma2_2b --reduced \
        --requests 6 --batch 2 --prompt-lens 640,512,700 --new-tokens 16 \
        --stagger 8 --admission chunked --prefill-chunk 128
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs.registry import get_config, reduced_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.serving.engine import Request, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--runtime", default="retro", choices=["retro", "full"])
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-lens", default="640",
                    help="comma-separated lengths, cycled over the queue")
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--stagger", type=int, default=0,
                    help="request i generates new-tokens + i*stagger tokens")
    ap.add_argument("--admission", default="chunked",
                    choices=["chunked", "blocking"])
    ap.add_argument("--attn-impl", default=None, choices=["jnp", "fused"],
                    help="retro decode-attention implementation: 'jnp' "
                         "(reference execution-buffer path) or 'fused' "
                         "(gather-free paged Pallas wave-attention kernel — "
                         "retrieved clusters read from the stores in place, "
                         "no gather temp; interpret-mode on CPU). Default: "
                         "the config's retro.attn_impl")
    ap.add_argument("--prefill-chunk", type=int, default=256,
                    help="chunked-admission tokens per scheduler iteration")
    ap.add_argument("--prefill-bucket", type=int, default=1,
                    help="blocking-mode prompt-length bucket")
    ap.add_argument("--offload", action="store_true",
                    help="host-offload wave buffer (paper Sec. 4.3): cluster "
                         "payload stores live host-side; decode retrieval "
                         "goes through a device block cache with cache-slot "
                         "indirection into the paged kernel. Token-for-token "
                         "identical to the direct-store path; requires the "
                         "retro runtime on an attention family")
    ap.add_argument("--cache-frac", type=float, default=None,
                    help="device block-cache size as a fraction of the "
                         "cluster store (offload mode; clamped >= 1 slot). "
                         "Default: the config's retro.cache_frac")
    ap.add_argument("--cache-policy", default=None,
                    choices=["lru", "fifo", "clock"],
                    help="block-cache replacement policy (offload mode)")
    ap.add_argument("--fault-profile", default=None,
                    help="retrofault: inject link faults into the offload "
                         "miss-fetch path, e.g. "
                         "'transient=0.2,corrupt=0.01,spike=0.1,seed=3' "
                         "(seed-deterministic; rates are per-attempt "
                         "probabilities). Failed fetches are masked out of "
                         "the retrieval zone and covered by the estimation "
                         "zone (degraded decode)")
    ap.add_argument("--fetch-deadline", type=float, default=None,
                    help="per-translate-call virtual fetch budget in "
                         "seconds; overdue misses degrade instead of "
                         "stalling the step")
    ap.add_argument("--fetch-retries", type=int, default=2,
                    help="bounded retries per miss fetch (exponential "
                         "virtual backoff)")
    ap.add_argument("--max-decode-steps", type=int, default=None,
                    help="per-request watchdog: finish a request with "
                         "status='timeout' after this many decode steps")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    params = M.init_params(cfg, jax.random.PRNGKey(args.seed))
    lens = [int(x) for x in args.prompt_lens.split(",")]
    engine = ServeEngine(cfg, params, runtime=args.runtime, gen_headroom=512,
                         admission=args.admission,
                         prefill_chunk=args.prefill_chunk,
                         prefill_bucket=args.prefill_bucket,
                         attn_impl=args.attn_impl, offload=args.offload,
                         cache_frac=args.cache_frac,
                         cache_policy=args.cache_policy,
                         fault_profile=args.fault_profile,
                         fetch_deadline_s=args.fetch_deadline,
                         fetch_retries=args.fetch_retries,
                         max_decode_steps=args.max_decode_steps)
    rng = np.random.default_rng(args.seed)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab, lens[i % len(lens)])
                    .astype(np.int32),
                    max_new_tokens=args.new_tokens + i * args.stagger)
            for i in range(args.requests)]
    m = engine.serve(reqs, batch_size=args.batch)
    print(f"served {len(reqs)} requests on {args.batch} slots "
          f"({args.runtime}{'+offload' if args.offload else ''}, "
          f"{args.admission} admission, "
          f"{engine.attn_impl} attention): "
          f"prefill {m.prefill_s:.2f}s, "
          f"decode {m.tokens_out} tokens @ {m.decode_tps:.1f} tok/s, "
          f"slot occupancy {m.slot_occupancy:.2f}, "
          f"itl p50/p99 {m.itl_p50_s * 1e3:.1f}/{m.itl_p99_s * 1e3:.1f} ms")
    if args.offload:
        print(f"  wave buffer: hit {m.cache_hit_ratio:.3f} "
              f"(effective {m.effective_cache_hit_ratio:.3f}, "
              f"{m.cache_pending_hits} pending hits), "
              f"link {m.bytes_over_link / 2**20:.1f} MiB, "
              f"cache {m.bytes_from_cache / 2**20:.1f} MiB")
        if args.fault_profile or m.cache_faults or m.degraded_steps:
            print(f"  retrofault: {m.cache_faults} faults, "
                  f"{m.cache_retries} retries, "
                  f"{m.cache_corrupt_fetches} corrupt, "
                  f"{m.cache_failed_fetches} failed fetches; "
                  f"{m.degraded_steps}/{m.steps} degraded steps "
                  f"({m.dropped_cluster_steps} cluster-steps dropped)")
    for i, r in enumerate(reqs):
        status = "" if r.status == "ok" else f" [{r.status}]"
        print(f"  req {i}: prompt {len(r.prompt)}, out {len(r.out_tokens)}, "
              f"ttft {r.ttft_s:.2f}s (queue {r.queue_s:.2f}s + admit "
              f"{r.admit_s:.2f}s), decode {r.decode_tps:.1f} tok/s"
              f"{status}")
    print("sample output tokens:", reqs[0].out_tokens[:10])


if __name__ == "__main__":
    main()
