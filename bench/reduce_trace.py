"""Profiler trace -> device time per program and per operation, busy and
idle time, and what the host was doing in the idle gaps.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
JAX (``jax.profiler.ProfileData``). On a TPU each chip is a plane
``/device:TPU:<n>``; its line ``XLA Modules`` holds one event per program
execution, named after the jitted function (``jit_decode(...)``), and its
line ``XLA Ops`` one event per operation, named after the HLO instruction
(the paged kernel's custom call is ``%paged_wave_attention.<n> = ...``). Host
threads are lines of the plane ``/host:CPU``, on the same clock.

Only events inside the traced window count: the host span named
``bench.window``, which the harness holds over the traced part of its call
into the engine. ``reduce_file`` adds the device time by named scope and
the idle time behind the serve loop's readbacks (``bench/scopes.py``).
"""
from __future__ import annotations

import bisect
import gzip
import heapq
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
_SUFFIX = re.compile(r"(\.\d+)+$")
_MODULE = re.compile(r"^(?:jit_)?([^(]+)")


def op_key(name: str) -> str:
    """``paged_wave_attention.6`` -> ``paged_wave_attention``. On a TPU an
    operation's event is named by its whole HLO instruction,
    ``%paged_wave_attention.6 = f32[...] custom-call(...)``: the name is
    what precedes `` = ``."""
    return _SUFFIX.sub("", name.split(" = ", 1)[0].lstrip("%"))


def module_key(name: str) -> str:
    """``jit_decode(123)`` -> ``decode``."""
    m = _MODULE.match(name)
    return op_key(m.group(1)) if m else name


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def _events(line):
    for ev in line.events if line is not None else ():
        yield ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns)


def window_of(host_planes) -> Optional[Tuple[int, int]]:
    for plane in host_planes:
        for line in plane.lines:
            for name, s, e in _events(line):
                if name == WINDOW_SPAN:
                    return s, e
    return None


def reduce_profile(pd, top: int = 10) -> Dict:
    """Reduce a ``ProfileData``. Returns ``modules`` and ``ops``
    ({name: [calls, device seconds]}, summed over chips), ``busy_s`` (union
    of operation intervals, averaged over chips), ``window_s``,
    ``device_ops`` (the ``top`` operations by time, each named
    ``<program>/<operation>`` after the program run it lies in) and
    ``idle_gaps`` (idle device time by the innermost host event covering
    it, ``top`` largest)."""
    planes = list(pd.planes)
    devices = [p for p in planes if p.name.startswith("/device:TPU:")]
    hosts = [p for p in planes if p.name.startswith("/host:")]
    if not devices:
        raise ValueError("trace holds no /device:TPU:<n> plane")
    win = window_of(hosts)
    if win is None:
        raise ValueError(f"trace holds no host span {WINDOW_SPAN!r}")
    lo, hi = win
    modules: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    ops: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    by_program: Dict[str, float] = defaultdict(float)
    busy_total = 0.0
    gaps: List[Tuple[int, int]] = []
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        runs = []                                   # (start, end, program)
        for name, s, e in _events(lines.get(MODULE_LINE)):
            s, e = _clip(s, e, lo, hi)
            if e > s:
                rec = modules[module_key(name)]
                rec[0] += 1
                rec[1] += (e - s) * 1e-9
                runs.append((s, e, module_key(name)))
        runs.sort()
        starts = [r[0] for r in runs]
        busy: List[Tuple[int, int]] = []
        for name, s, e in _events(lines.get(OPS_LINE)):
            s, e = _clip(s, e, lo, hi)
            if e <= s:
                continue
            rec = ops[op_key(name)]
            rec[0] += 1
            rec[1] += (e - s) * 1e-9
            busy.append((s, e))
            j = bisect.bisect_right(starts, s) - 1
            prog = runs[j][2] if j >= 0 and runs[j][1] >= e else "?"
            by_program[f"{prog}/{op_key(name)}"] += (e - s) * 1e-9
        merged = _union(busy)
        busy_total += sum(e - s for s, e in merged) * 1e-9
        t = lo
        for s, e in merged:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < hi:
            gaps.append((t, hi))
    n_dev = len(devices)
    device_ops = sorted(by_program.items(), key=lambda kv: -kv[1])[:top]
    idle = _attribute_gaps(gaps, hosts, lo, hi)
    return {
        "modules": {k: list(v) for k, v in modules.items()},
        "ops": {k: list(v) for k, v in ops.items()},
        "busy_s": busy_total / n_dev,
        "window_s": (hi - lo) * 1e-9,
        "chips": n_dev,
        "device_ops": [[k, v] for k, v in device_ops],
        "idle_gaps": [[k, v / n_dev] for k, v in sorted(
            idle.items(), key=lambda kv: -kv[1])[:top]],
    }


def _attribute_gaps(gaps, hosts, lo, hi) -> Dict[str, float]:
    """Seconds of idle device time by the innermost host event (the
    shortest event on any host thread, other than the window span) that
    covers the middle of each gap; ``host: no event`` where none does."""
    spans = sorted((s, e, name) for plane in hosts for line in plane.lines
                   for name, s, e in _events(line)
                   if name != WINDOW_SPAN and e > lo and s < hi)
    out: Dict[str, float] = defaultdict(float)
    active: List[Tuple[int, int, str]] = []     # heap of (length, end, name)
    i = 0
    for g0, g1 in sorted(gaps, key=lambda g: g[0] + g[1]):
        mid = (g0 + g1) // 2
        while i < len(spans) and spans[i][0] <= mid:
            s, e, name = spans[i]
            heapq.heappush(active, (e - s, e, name))
            i += 1
        while active and active[0][1] < mid:
            heapq.heappop(active)
        label = op_key(active[0][2]) if active else "host: no event"
        out[label] += (g1 - g0) * 1e-9
    return out


def read_raw(path) -> bytes:
    """The serialized XSpace of an ``.xplane.pb`` file (or a gzipped one,
    ``.gz``)."""
    raw = Path(path).read_bytes()
    return gzip.decompress(raw) if str(path).endswith(".gz") else raw


def reduce_file(path) -> Dict:
    """Reduce an ``.xplane.pb`` file (or a gzipped one, ``.gz``):
    ``reduce_profile``'s keys, and ``scopes`` ({program: {scope: [leaf
    operations, device seconds]}}) and ``sync_idle_s`` of
    ``bench.scopes.reduce_profile``."""
    from jax.profiler import ProfileData

    from bench import scopes
    raw = read_raw(path)
    pd = ProfileData.from_serialized_xspace(raw)
    out = reduce_profile(pd)
    by_scope = scopes.reduce_profile(pd, scopes.op_paths(raw))
    out.update(scopes=by_scope["scopes"], sync_idle_s=by_scope["sync_idle_s"])
    return out
