"""Share of its roofline that the paged wave-attention kernel reaches, in
%: the least time of its calls (the larger of operations over peak FLOP/s
and bytes over HBM bandwidth, ``bench.flops.wave_attention_call`` at the
cell's slots and context) over the kernel's device time in the trace
(operations named ``paged_wave_attention``). The kernel is bound by
memory at these shapes."""

KERNEL = "paged_wave_attention"


def read(run):
    if run.trace is None or KERNEL not in run.trace["ops"]:
        return None
    calls, secs = run.trace["ops"][KERNEL]
    eng = run.traffic["engine"]
    fl, nb = run.flops.wave_attention_call(run.cfgd, eng["slots"],
                                           eng["max_context"])
    least, _ = run.flops.roofline_seconds(fl, nb, run.peaks)
    return 100.0 * calls * least / secs
