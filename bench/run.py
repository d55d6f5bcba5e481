"""Serving benchmark of one cell on the chip it is started on.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<traffic>.json``). One run is one process:

1. set-up (``setup_s``, from process start): weights made on the device
   from ``--seed`` in one jitted call; one ``ServeEngine``; warm-up queues
   served through it that compile every program the window can run;
2. the window: one ``ServeEngine.serve`` call over the first N requests of
   the seeded stream, N fixed by the traffic file's ``blocks`` (fixed work,
   sized so that the window lasts about ``run_seconds``; ``--seconds`` does
   not change it); the number of programs built inside it is counted and
   printed (it should be 0); with ``--trace 1`` a part of the window is
   profiled, as the traffic file's ``trace`` places it (``WindowTrace``);
3. the peak device memory is read and the program's state freed;
4. ``correct``: a sample of the finished requests (drawn from the seed,
   the longest among them) is run through the plain float32 reference
   (``bench/reference.py``); the widest gap by which a served token's
   logit lies below the reference's best, and the mean gap over the
   sample, are held to the cell's limits (``bench/cells/<cell>.json``).

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics, each read by
``bench/metrics/<name>.py``), ``device``, ``breakdown`` (traced runs) and
``checks`` (each compared number with its limit), also printed as the last
lines of stderr. Without a TPU, or with fewer chips than the cell asks
for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from bench import spec, traffic as gen  # noqa: E402
from bench.reduce_trace import WINDOW_SPAN  # noqa: E402

CACHE_DIR = ROOT / ".bench_cache"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def find_chips(chips: int) -> dict:
    """The device line, or exit 2 where JAX finds no TPU or too few."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"bench: needs a TPU, JAX found {devs[0].platform!r} "
            f"({devs[0].device_kind}, {len(devs)} device(s))")
        sys.exit(2)
    if len(devs) < chips:
        log(f"bench: the cell needs {chips} chips, JAX found {len(devs)}")
        sys.exit(2)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def enable_compile_cache() -> None:
    """JAX's persistent compile cache at a fixed path in the checkout,
    holding every program (no minimum compile time or size)."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts programs built (compiled or loaded from the cache) while on."""

    def __init__(self):
        import jax.monitoring
        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **_):
        if self.on and event == COMPILE_EVENT:
            self.count += 1


class GcClock:
    """Seconds that Python's cyclic collector ran while on."""

    def __init__(self):
        self.on, self.seconds, self._t = False, 0.0, None
        gc.callbacks.append(self._event)

    def _event(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            if self.on:
                self.seconds += time.perf_counter() - self._t
            self._t = None


class WindowTrace:
    """Profiles a part of the window: from the moment the window's
    requests have been served ``from_share`` of the tokens they ask for,
    for ``span_s`` seconds or until the window closes. A thread of its own
    watches the requests' served tokens, starts the profiler, holds the
    host span ``bench.window`` (the traced window the reduction reads) and
    stops the profiler, while the main thread serves; Python calls are not
    traced. The trigger is a count of tokens, so the span meets the same
    steps of the schedule however fast they run; it is short because the
    profiler takes some seconds per traced second to collect its trace."""

    POLL_S = 0.01

    def __init__(self, trace_dir: str, span_s: float, reqs,
                 from_share: float):
        import threading
        self._dir, self._span_s, self._reqs = trace_dir, span_s, list(reqs)
        self._start_at = from_share * sum(r.max_new_tokens for r in reqs)
        self._done = threading.Event()
        self._thread = threading.Thread(target=self._hold)
        self._thread.start()

    def _served(self) -> int:
        return sum(len(r.out_tokens) for r in self._reqs)

    def _hold(self) -> None:
        import jax
        while self._served() < self._start_at:
            if self._done.wait(self.POLL_S):
                return                      # the window closed first
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # Python calls: most events
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                self._done.wait(self._span_s)
        finally:
            jax.profiler.stop_trace()

    def close(self) -> None:
        self._done.set()
        self._thread.join()


def _requests(specs, pool):
    from repro.serving.engine import Request
    return [Request(prompt=s.prompt, max_new_tokens=s.max_new_tokens,
                    extra=None if s.patch is None
                    else {"patch_embeds": pool[s.patch]}) for s in specs]


def make_engine(cfg, traffic: dict, params):
    """The cell's one ``ServeEngine``, as its traffic file sets it up."""
    from repro.serving.engine import ServeEngine
    eng = traffic["engine"]
    return ServeEngine(cfg, params, runtime=eng["runtime"],
                       admission=eng["admission"],
                       attn_impl=eng["attn_impl"], offload=eng["offload"],
                       max_context=eng["max_context"],
                       gen_headroom=eng["gen_headroom"],
                       prefill_chunk=eng["prefill_chunk"])


def warm_up(engine, cfgd: dict, traffic: dict, seed: int, pool) -> None:
    """Serve the warm-up queues, which build every program the window can
    run (``traffic.warmup_batches``)."""
    for batch in gen.warmup_batches(traffic, cfgd["vocab"], seed,
                                    cfgd["retro"]["update_segment"]):
        engine.serve(_requests(batch, pool),
                     batch_size=traffic["engine"]["slots"])


def _check_params(cfg, params) -> None:
    """The benchmark's weights have the program's parameter layout."""
    import jax

    from repro.models import model as M
    want = jax.tree.map(lambda s: (s.shape, s.dtype), M.param_specs(cfg))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if want != got:
        raise ValueError("benchmark weights do not match the program's "
                         f"parameter layout:\n{got}\nvs\n{want}")


def sample_for_check(reqs, seed: int, min_tokens: int):
    """Indices of finished requests to compare: the one with the most
    served tokens, then others in an order drawn from the seed, until the
    sample holds ``min_tokens`` served tokens."""
    done = [i for i, r in enumerate(reqs) if r.status == "ok" and r.out_tokens]
    if not done:
        return []
    longest = max(done, key=lambda i: (len(reqs[i].out_tokens), -i))
    rest = [i for i in done if i != longest]
    order = [longest] + [rest[j] for j in
                         np.random.default_rng([2, seed]).permutation(len(rest))]
    out, n = [], 0
    for i in order:
        out.append(i)
        n += len(reqs[i].out_tokens)
        if n >= min_tokens:
            break
    return out


def gap_stats(gaps) -> dict:
    """The numbers ``correct`` may compare, from the served tokens' gaps:
    the widest, and the mean over every served token of the sample."""
    if len(gaps) == 0:
        return {"max_logit_gap": float("inf"), "mean_logit_gap": float("inf")}
    return {"max_logit_gap": float(gaps.max()),
            "mean_logit_gap": float(gaps.mean())}


def decide(stats: dict, limits: dict, failed: int, n_tokens: int,
           check_tokens: int):
    """``correct`` and the checks beside their limits: every number of
    ``stats`` that the cell limits at or under its limit, no request
    failed, and at least ``check_tokens`` served tokens compared."""
    checks = {k: {"value": stats[k], "limit": v} for k, v in limits.items()}
    checks["failed_requests"] = {"value": failed, "limit": 0}
    checks["sampled_tokens_min"] = {"value": n_tokens, "limit": check_tokens}
    correct = (all(stats[k] <= v for k, v in limits.items()) and failed == 0
               and n_tokens >= check_tokens)
    return correct, checks


def compare(cfgd, traffic, seed, reqs, specs, pool, controls=()):
    """Run the reference over the check's sample of finished requests.
    Returns the sample's indices, the served tokens' gaps, and for each
    control in ``controls`` the gaps of its own first choices at the same
    positions (``reference.served_gaps``)."""
    from bench import reference
    sample = sample_for_check(reqs, seed, traffic["check_tokens"])
    gaps, ctl = [np.zeros(0)], {c: [np.zeros(0)] for c in controls}
    for i in sample:
        patches = None if specs[i].patch is None else pool[specs[i].patch]
        g, c = reference.served_gaps(cfgd, seed, specs[i].prompt,
                                     reqs[i].out_tokens, patches, controls)
        gaps.append(g)
        for k, v in c.items():
            ctl[k].append(v)
    return (sample, np.concatenate(gaps),
            {k: np.concatenate(v) for k, v in ctl.items()})


def run_cell(cell: dict, cfgd: dict, traffic: dict, limits: dict,
             metrics: list, seed: int, seconds: float, trace: bool,
             device: dict, t_process: float = T_PROCESS,
             engine_hook=None, keep_trace=None) -> dict:
    """Set up, serve the window, read the metrics, check the outputs.
    ``engine_hook(engine)`` (tests) may wrap the engine before serving;
    ``keep_trace`` is a path the raw trace is copied to."""
    import jax

    from bench import flops, weights

    cfg = spec.model_config(cfgd)
    slots = traffic["engine"]["slots"]
    counter = CompileCounter()

    params = weights.make_params(cfgd, seed)
    _check_params(cfg, params)
    pool = weights.make_patch_pool(cfgd, seed, traffic.get("patch_pool", 0))
    engine = make_engine(cfg, traffic, params)
    if engine_hook is not None:
        engine_hook(engine)
    warm_up(engine, cfgd, traffic, seed, pool)
    setup_s = time.perf_counter() - t_process
    log(f"bench: set-up {setup_s:.3f} s")

    n = gen.window_requests(traffic)
    specs = gen.request_stream(traffic, cfgd["vocab"], seed, n)
    reqs = _requests(specs, pool)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    tracer = WindowTrace(trace_dir, traffic["trace"]["seconds"], reqs,
                         traffic["trace"]["from_share"]) if trace else None
    gc_clock = GcClock()
    counter.on = gc_clock.on = True
    t0 = time.perf_counter()
    serve_metrics = engine.serve(reqs, batch_size=slots)
    wall_s = time.perf_counter() - t0
    counter.on = gc_clock.on = False
    if tracer is not None:
        tracer.close()
    log(f"bench: window {wall_s:.3f} s, {n} requests, "
        f"{serve_metrics.tokens_out} tokens; programs built in the window: "
        f"{counter.count}")
    gaps = np.asarray(serve_metrics.step_s or [0.0])
    log(f"bench: admission {serve_metrics.prefill_s:.3f} s, decode "
        f"{serve_metrics.decode_s:.3f} s; {gaps.size} token gaps, "
        f"{gaps.sum():.3f} s in all, longest {1e3 * gaps.max():.1f} ms, "
        f"{int((gaps > 0.25).sum())} over 250 ms")
    log("bench: first tokens at "
        + ", ".join(f"{r.ttft_s:.3f}" for r in reqs) + " s; "
        f"{int((gaps > 0.1).sum())} gaps over 100 ms, "
        f"{gaps[gaps > 0.1].sum():.3f} s; the collector "
        f"{gc_clock.seconds:.3f} s")

    stats = jax.devices()[0].memory_stats() or {}
    device = dict(device, memory_peak_bytes=int(stats.get("peak_bytes_in_use",
                                                          0)))
    failed = sum(r.status != "ok" or len(r.out_tokens) != r.max_new_tokens
                 for r in reqs)
    del engine, params
    gc.collect()

    reduced = None
    if trace:
        from bench.reduce_trace import reduce_file
        files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
        if files:
            reduced = reduce_file(files[-1])
            if keep_trace:
                shutil.copyfile(files[-1], keep_trace)
            device.update(busy_s=reduced["busy_s"],
                          window_s=reduced["window_s"])
        shutil.rmtree(trace_dir, ignore_errors=True)

    run = SimpleNamespace(cell=cell, cfgd=cfgd, traffic=traffic,
                          serve=serve_metrics, requests=reqs, wall_s=wall_s,
                          setup_s=setup_s, trace=reduced, device=device,
                          peaks=flops.peaks(device["kind"]), flops=flops)
    out_metrics = {}
    for m in metrics:
        value = spec.load_reader(m["name"])(run)
        if value is not None:
            out_metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    t_ref = time.perf_counter()
    sample, gaps, _ = compare(cfgd, traffic, seed, reqs, specs, pool)
    stats = gap_stats(gaps)
    log(f"bench: served-token gaps over {len(gaps)} tokens: {stats}")
    correct, checks = decide(stats, limits, failed, len(gaps),
                             traffic["check_tokens"])
    log(f"bench: reference over {len(sample)} requests in "
        f"{time.perf_counter() - t_ref:.3f} s")

    result = {"correct": correct, "attempted": len(reqs), "failed": failed,
              "metrics": out_metrics, "device": device}
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    return result


def main(argv=None) -> None:
    args = parse_args(argv)
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, args.workload)
    cfgd = spec.load_config(bench, cell["config"])
    traffic = spec.load_traffic(cell["traffic"])
    limits = spec.load_cell_limits(cell["name"])
    metrics = spec.cell_metrics(bench, cell["name"], bool(args.trace))
    device = find_chips(cell["chips"])
    enable_compile_cache()
    log(f"bench: {cell['name']} seed {args.seed} on {device}")
    result = run_cell(cell, cfgd, traffic, limits, metrics, args.seed,
                      args.seconds, bool(args.trace), device)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
