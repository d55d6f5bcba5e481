"""Readings that a cell's ``max_logit_gap`` limit is set from.

    python bench/readings.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 1,2,3] [--faults state-unchanged,kernel-zeroed \
         --fault-seeds 1,2,3] [--requests N]

One process builds the cell's engine once, then for each seed makes that seed's weights, serves a window of
the cell's own load through the same engine (the first ``--requests``
requests of the seeded stream, at the cell's slots; by default the whole
window), frees the weights and reads the gaps of the same sample a
benchmark run compares (the lower reading). For each control seed it also
reads the int8 and the fp8 controls' gaps of their own first choices at
the same positions (the upper reading). Each fault of ``bench/faults.py``
gets an engine of its own, built with the fault in place, and is read the
same way on each fault seed. Each reading gives both numbers
``run.gap_stats`` makes (the widest gap and the mean gap of the sample)
and ``correct`` as a benchmark run decides it (``run.decide``) against
the cell's limits.
One JSON line per reading on stdout. Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from bench import faults  # noqa: E402
from bench import run as harness  # noqa: E402
from bench import spec, traffic as gen  # noqa: E402

CONTROLS = ("int8", "fp8")


def _engine(cfgd, traffic, hook=None):
    """An engine with no weights yet and no warm-up: readings time nothing,
    so the first window compiles what it runs."""
    engine = harness.make_engine(spec.model_config(cfgd), traffic, None)
    if hook is not None:
        hook(engine)
    return engine


def _read(engine, cfgd, traffic, seed, n, controls, label, limits):
    from bench import weights
    if engine.params is None:
        engine.params = spec.load_block(cfgd).make_params(cfgd, seed)
    pool = weights.make_patch_pool(cfgd, seed, traffic.get("patch_pool", 0))
    specs = gen.request_stream(traffic, cfgd["vocab"], seed, n)
    reqs = harness._requests(specs, pool)
    t0 = time.perf_counter()
    engine.serve(reqs, batch_size=traffic["engine"]["slots"])
    wall = time.perf_counter() - t0
    engine.params = None
    gc.collect()
    t0 = time.perf_counter()
    sample, prog, ctl = harness.compare(cfgd, traffic, seed, reqs, specs,
                                        pool, controls)
    failed = sum(r.status != "ok" or len(r.out_tokens) != r.max_new_tokens
                 for r in reqs)
    stats = harness.gap_stats(prog)
    correct, _ = harness.decide(stats, limits, failed, len(prog),
                                traffic["check_tokens"])
    return {"seed": seed, "what": label, "window_s": wall, "requests": n,
            "failed": failed,
            "sample": [[len(specs[i].prompt), len(reqs[i].out_tokens)]
                       for i in sample],
            "reference_s": time.perf_counter() - t0,
            "program": stats, "correct": correct,
            "controls": {k: harness.gap_stats(v) for k, v in ctl.items()}}


def readings(cfgd, traffic, seeds, control_seeds, fault_names=(),
             fault_seeds=(), n=None, limits=None, emit=print):
    n = n or gen.window_requests(traffic)
    limits = limits or {}
    out = []
    runs = [(None, seeds)] + [(f, fault_seeds) for f in fault_names]
    for fault, fseeds in runs:
        if not fseeds:
            continue
        engine = _engine(cfgd, traffic,
                         None if fault is None else faults.FAULTS[fault])
        for seed in fseeds:
            ctl = CONTROLS if fault is None and seed in control_seeds else ()
            rec = _read(engine, cfgd, traffic, seed, n, ctl,
                        fault or "program", limits)
            emit(json.dumps(rec))
            out.append(rec)
        del engine
        faults.restore()
        gc.collect()
    return out


def _ints(text):
    return [int(s) for s in text.split(",") if s]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--requests", type=int, default=0)
    args = ap.parse_args()
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, args.workload)
    harness.find_chips(cell["chips"])
    harness.enable_compile_cache()
    readings(spec.load_config(bench, cell["config"]),
             spec.load_traffic(cell["traffic"]), _ints(args.seeds),
             set(_ints(args.control_seeds)),
             [f for f in args.faults.split(",") if f],
             _ints(args.fault_seeds), args.requests or None,
             spec.load_cell_limits(cell["name"]),
             emit=lambda line: print(line, flush=True))


if __name__ == "__main__":
    main()
