"""Model FLOP/s utilization of the decode step on the device, in %: the
matmul operations of one ``jit_decode`` call (``bench.flops.
decode_matmul_flops`` for each of the cell's slots, which every call
computes; attention left out) times its calls in the trace, over the
program's device time and the chip's bf16 peak."""


def read(run):
    if run.trace is None or "decode" not in run.trace["modules"]:
        return None
    calls, secs = run.trace["modules"]["decode"]
    ops = (run.flops.decode_matmul_flops(run.cfgd)
           * run.traffic["engine"]["slots"] * calls)
    return 100.0 * ops / (secs * run.peaks["bf16_flops_per_s"])
