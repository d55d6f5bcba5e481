"""Record the small profiler trace that ``test_bench_trace.py`` reduces.

    python3 bench/tests/record_trace.py [OUT]

Needs a TPU. Serves four requests through ``ServeEngine`` at a small
size (2 layers, head size 128, the fused paged kernel, one decode-time
index update) with the harness's traced window, and copies the raw
``.xplane.pb`` to ``OUT`` (default ``small.xplane.pb``). Prints the
reduced trace as one JSON line.

    python3 bench/tests/record_trace.py --shrink IN OUT

keeps the part of a recorded trace that the test needs (``shrink``), and
writes it gzipped: ``bench/tests/data/small.xplane.pb.gz``.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from bench import run  # noqa: E402
from bench.tests.tiny import tiny_config, tiny_traffic  # noqa: E402

OUT = Path("small.xplane.pb")


def small_config() -> dict:
    cfgd = tiny_config("mistral-7b")
    cfgd.update(n_layers=2, d_model=512, d_ff=1024, vocab=2048)
    cfgd["attn"].update(n_heads=4, n_kv_heads=2, head_dim=128)
    cfgd["retro"].update(prefill_segment=512, update_segment=128,
                         sink=4, local=64, avg_cluster=16, cluster_cap=32)
    return cfgd


def small_traffic() -> dict:
    traffic = tiny_traffic("doc32k-decode")
    traffic["engine"].update(max_context=1024, gen_headroom=256,
                             prefill_chunk=256)
    traffic.update(prompt_lens=[768, 1024], output_lens=[8, 140],
                   blocks=1, check_tokens=50,
                   trace={"from_share": 0.0, "seconds": 60.0})
    return traffic


NEEDED = ("decode", "chunk", "fin", "flush", "argmax_ids")
DEVICE_LINES = ("XLA Modules", "XLA Ops")


def shrink(src: Path, dst: Path, margin_ns: int = 1_000_000) -> None:
    """Cut a trace down to a few short stretches of time: around one run
    of each program in ``NEEDED``, ``margin_ns`` on either side, taken from
    the shortest stretch of the recording that holds them all. Events
    outside the stretches, event statistics, device lines other than
    ``DEVICE_LINES`` and planes other than the first chip's and the host's
    go, so the device reads idle between the stretches; the host span
    ``bench.window`` is narrowed to run from the first kept stretch to
    the last. Reads ``src`` raw, writes ``dst`` gzipped. Needs
    TensorFlow's ``xplane_pb2``."""
    import gzip
    import re

    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    from bench.reduce_trace import MODULE_LINE, WINDOW_SPAN
    xs = xplane_pb2.XSpace()
    xs.ParseFromString(src.read_bytes())
    dev = next(p for p in xs.planes if p.name == "/device:TPU:0")
    host = next(p for p in xs.planes if p.name == "/host:CPU")
    mods = next(ln for ln in dev.lines if ln.name == MODULE_LINE)
    runs = sorted((mods.timestamp_ns + e.offset_ps // 1000,
                   re.match(r"(?:jit_)?([^(]+)",
                            dev.event_metadata[e.metadata_id].name).group(1),
                   e.duration_ps // 1000)
                  for e in mods.events)
    best, j, seen = None, 0, {}
    for i, (t, name, _) in enumerate(runs):   # shortest stretch with all
        seen[name] = seen.get(name, 0) + 1
        while all(seen.get(n, 0) for n in NEEDED):
            if best is None or t - runs[j][0] < best[1] - best[0]:
                best = (runs[j][0], t)
            seen[runs[j][1]] -= 1
            j += 1
    stretches = []
    for name in NEEDED:
        t, _, dur = next(r for r in runs if r[1] == name
                         and best[0] <= r[0] <= best[1])
        stretches.append((t - margin_ns, t + dur + margin_ns))
    lo = min(s for s, _ in stretches)
    hi = max(e for _, e in stretches)

    def kept(line, e, names):
        t = line.timestamp_ns + e.offset_ps // 1000
        return (names is not None and names[e.metadata_id].name == WINDOW_SPAN
                or any(a <= t <= b for a, b in stretches))

    def keep(line, names=None):
        evs = [e for e in line.events if kept(line, e, names)]
        del line.events[:]
        for e in evs:
            del e.stats[:]
            line.events.append(e)

    for line in list(dev.lines):
        if line.name not in DEVICE_LINES:
            dev.lines.remove(line)
        else:
            keep(line)
    for line in host.lines:
        for e in line.events:
            if host.event_metadata[e.metadata_id].name == WINDOW_SPAN:
                e.offset_ps = (lo - line.timestamp_ns) * 1000
                e.duration_ps = (hi - lo) * 1000
        keep(line, host.event_metadata)
    for plane in list(xs.planes):
        if plane not in (dev, host):
            xs.planes.remove(plane)
    for plane in (dev, host):
        for md in plane.event_metadata.values():
            del md.stats[:]
    with gzip.open(dst, "wb") as f:
        f.write(xs.SerializeToString())


def main() -> None:
    if sys.argv[1:2] == ["--shrink"]:
        shrink(Path(sys.argv[2]), Path(sys.argv[3]))
        return
    out = Path(sys.argv[1]) if len(sys.argv) > 1 else OUT
    device = run.find_chips(1)
    run.enable_compile_cache()
    res = run.run_cell({"name": "small"}, small_config(), small_traffic(),
                       {"max_logit_gap": 10.0}, [], 11, 1.0, True, device,
                       keep_trace=str(out))
    from bench.reduce_trace import reduce_file
    red = reduce_file(out)
    print(json.dumps({"bytes": out.stat().st_size, "correct": res["correct"],
                      "modules": red["modules"], "busy_s": red["busy_s"],
                      "window_s": red["window_s"],
                      "device_ops": red["device_ops"],
                      "idle_gaps": red["idle_gaps"],
                      "ops": sorted(red["ops"])}), flush=True)


if __name__ == "__main__":
    main()
