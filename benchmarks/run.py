# One benchmark per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
#
# ``--quick`` runs the continuous-serving smoke comparison (chunked vs
# blocking admission on the same ragged queue), the jnp-vs-fused decode
# attention comparison (per-step latency p50/p99 + cost_analysis bytes), the
# host-offload serving comparison (serve-level wave-buffer hit ratio /
# link traffic at several cache fractions, outputs vs the direct store), and
# the retrofault degradation trajectory (decode tps + degraded-step fraction
# under seeded fault schedules at rates {0, 0.05, 0.2}) and writes them to a
# ``BENCH_throughput.json`` artifact so the perf trajectory is recorded per
# PR. It also runs the fig18 fidelity snapshot (attention rel-err at the
# paper budget with/without estimation, hot-token recall, estimation-zone
# Jensen logit error) into a ``BENCH_accuracy.json`` artifact.
from __future__ import annotations

import json
import sys
import time


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    args = [a for a in sys.argv[1:] if a != "--quick"]
    quick = "--quick" in sys.argv[1:]
    if quick:
        from benchmarks import bench_throughput
        print("name,us_per_call,derived")
        t0 = time.time()
        res = bench_throughput.compare_admission(quick=True)
        res["attn_impl"] = bench_throughput.compare_attn_impl(quick=True)
        res["offload"] = bench_throughput.compare_offload(quick=True)
        res["degradation"] = bench_throughput.compare_degradation(quick=True)
        with open("BENCH_throughput.json", "w") as f:
            json.dump(res, f, indent=2)
            f.write("\n")
        print(f"# quick smoke done in {time.time() - t0:.1f}s "
              f"-> BENCH_throughput.json", flush=True)
        print(json.dumps(res, indent=2))
        assert res["outputs_equal"], \
            "chunked admission changed outputs vs blocking"
        assert res["attn_impl"]["outputs_equal"], \
            "fused attention changed outputs vs jnp"
        assert res["attn_impl"]["bytes_drop_frac"] > 0, \
            "fused decode step did not reduce bytes accessed"
        assert res["offload"]["outputs_equal"], \
            "host-offload serving changed outputs vs the direct store"
        fr = res["offload"]["cache_fracs"]
        assert all(v["bytes_over_link"] > 0 for v in fr.values()), \
            "offload serving recorded no link traffic"
        assert all(v["offload_vs_direct_tps"] > 0 for v in fr.values()), \
            "offload comparison missing the offload-vs-direct tps ratio"
        assert res["degradation"]["outputs_equal"], \
            "zero-rate fault schedule changed outputs vs fault-free offload"
        assert res["degradation"]["completes_under_faults"], \
            "a faulted serve run dropped tokens (request did not complete)"
        dr = res["degradation"]["fault_rates"]
        assert dr["0.0"]["degraded_steps"] == 0, \
            "zero-rate fault schedule recorded degraded steps"
        assert all(v["decode_tps"] > 0 for v in dr.values()), \
            "degradation comparison missing decode tps"

        from benchmarks import bench_accuracy_budget
        acc = bench_accuracy_budget.compare_accuracy(quick=True)
        with open("BENCH_accuracy.json", "w") as f:
            json.dump(acc, f, indent=2)
            f.write("\n")
        print("# accuracy snapshot -> BENCH_accuracy.json", flush=True)
        print(json.dumps(acc, indent=2))
        assert acc["rel_err_est"] < acc["rel_err_noest"], \
            "estimation zone did not improve fidelity at the paper budget"
        assert acc["at_frac_0.1"]["rel_err_est"] < acc["rel_err_est"], \
            "attention error did not shrink with a larger retrieval budget"
        assert acc["at_frac_0.1"]["hot_recall"] >= acc["hot_recall"] > 0, \
            "hot-token recall not positive / not monotone in budget"
        assert acc["est_zone_max_abs_logit_err"] < 2.0, \
            "estimation-zone Jensen logit error blew past the Eq.2-4 regime"
        return

    from benchmarks import (bench_accuracy_budget, bench_cache,
                            bench_estimation, bench_longgen, bench_niah,
                            bench_prefill, bench_segment_size,
                            bench_throughput)
    suites = [
        ("fig18_accuracy_vs_budget", bench_accuracy_budget.run),
        ("fig19a_estimation", bench_estimation.run),
        ("fig19b_segment_size", bench_segment_size.run),
        ("fig13_decode_throughput", bench_throughput.run),
        ("attn_impl_jnp_vs_fused", bench_throughput.run_attn_impl),
        ("fig16_wave_buffer", bench_cache.run),
        ("fig16_serve_offload", bench_throughput.run_offload),
        ("retrofault_degradation", bench_throughput.run_degradation),
        ("fig15_prefill_overhead", bench_prefill.run),
        ("fig17b_long_generation", bench_longgen.run),
        ("fig10_niah_trained_model", bench_niah.run),
        ("ragged_continuous_serving", bench_throughput.run_ragged_continuous),
    ]
    only = args[0] if args else None
    print("name,us_per_call,derived")
    for name, fn in suites:
        if only and only not in name:
            continue
        t0 = time.time()
        fn()
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)


if __name__ == '__main__':
    main()
