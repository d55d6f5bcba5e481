"""Device time by named scope, and device idle behind the serve loop's
readbacks, from a profiler trace.

    python3 bench/scopes.py TRACE.xplane.pb[.gz]

prints both as one JSON line (``reduce_file``).

Scopes. The program names the operations of its serve path with
``jax.named_scope`` (``repro.serving.trace``; the names are copied in
``SCOPES``, so this reader also runs against a program that has none). XLA
keeps the scope path in each instruction's ``op_name`` metadata. On a TPU
v5e the profiler writes it into the statistic ``tf_op`` of the *event
metadata* of an ``XLA Ops`` event, for example
``jit(decode)/while/body/closed_call/rank/top_k:``, beside ``program_id``,
the number in the program's ``XLA Modules`` event (``jit_decode(<id>)``).
``jax.profiler.ProfileData`` gives the events but not their metadata's
statistics, so ``op_paths`` reads those from the raw ``XSpace`` bytes.

An operation's scope is the innermost name of ``SCOPES`` on its path, and
``loop`` where there is none: the layer loop's own slicing, carries and
copies, or every operation of a program without scopes. Only leaf
operations count: an event that holds others on its line (``while``) is
left out, the operations inside it count. A fusion carries the metadata
of one of the operations fused into it.

Readbacks. ``sync_idle_s`` is the device idle time inside the traced window
whose middle lies in a ``serve.harvest`` or ``serve.first_token`` host span,
a gap being labelled by the innermost ``serve.*`` span that covers it.

Times are clipped to the host span ``bench.window``, as in
``reduce_trace.reduce_profile``; scope times are summed over chips,
``sync_idle_s`` averaged over them.
"""
from __future__ import annotations

import bisect
import heapq
import json
import re
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import reduce_trace as rt  # noqa: E402

SCOPES = ("embed", "qkv", "cache_update", "rank", "attend", "ffn", "unembed",
          "index_build")
LOOP = "loop"
SERVE_SPAN = "serve."
SYNC_SPANS = ("serve.harvest", "serve.first_token")
DEVICE_PLANE = "/device:TPU:"
TF_OP, PROGRAM_ID = "tf_op", "program_id"
_PROGRAM_ID = re.compile(r"\((\d+)\)\s*$")


# ---- the XSpace protobuf, read by field number -----------------------------
# XSpace: planes 1. XPlane: name 2, event_metadata 4 and stat_metadata 5
# (maps: key 1, value 2). XEventMetadata: name 2, stats 5. XStatMetadata:
# id 1, name 2. XStat: metadata_id 1, uint64 3, int64 4, str 5, ref 7 (the
# id of a stat metadata whose name is the value).

def _varint(buf, i: int) -> Tuple[int, int]:
    n = shift = 0
    while True:
        b = buf[i]
        i += 1
        n |= (b & 0x7F) << shift
        if b < 0x80:
            return n, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint or a fixed
    field, a memoryview for a length-delimited one."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 1:
            v, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif wire == 5:
            v, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} in an XSpace")
        yield key >> 3, v


def _map_value(entry):
    return next((v for f, v in _fields(entry) if f == 2), b"")


def op_paths(raw: bytes) -> Dict[Tuple[int, str], str]:
    """``{(program id, operation event name): op_name path}`` from the event
    metadata of every ``/device:TPU:<n>`` plane of a serialized XSpace."""
    out: Dict[Tuple[int, str], str] = {}
    for num, plane in _fields(memoryview(raw)):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 4:
                events.append(_map_value(v))
            elif f == 5:
                md = dict(_fields(_map_value(v)))
                stat_names[md.get(1, 0)] = bytes(md.get(2, b"")).decode()
        if not name.startswith(DEVICE_PLANE):
            continue
        for md in events:
            ev_name, path, prog = "", None, None
            for f, v in _fields(md):
                if f == 2:
                    ev_name = bytes(v).decode()
                elif f == 5:
                    stat = dict(_fields(v))
                    kind = stat_names.get(stat.get(1))
                    if kind == TF_OP:
                        path = (bytes(stat[5]).decode() if 5 in stat
                                else stat_names.get(stat.get(7), ""))
                    elif kind == PROGRAM_ID:
                        prog = stat.get(3, stat.get(4, 0)) % 2**64
            if path is not None and prog is not None:
                out[(prog, ev_name)] = path.rstrip(":")
    return out


# ---- reduction -------------------------------------------------------------

def scope_of(path: Optional[str]) -> str:
    """The innermost name of ``SCOPES`` on an ``op_name`` path (a component
    wrapped by a transformation, ``jvp(rank)``, counts as its name), or
    ``loop``."""
    for part in reversed(path.split("/") if path else ()):
        name = part.rsplit("(", 1)[-1].rstrip(")")
        if name in SCOPES:
            return name
    return LOOP


def program_id(module_event: str) -> Optional[int]:
    """``jit_decode(123)`` -> 123."""
    m = _PROGRAM_ID.search(module_event)
    return int(m.group(1)) % 2**64 if m else None


def _leaves(ops: List[Tuple[int, int, str]]):
    """The operations of one line that hold no other. Events on a line nest
    or follow each other; sorted by start, longest first, an event holds
    another when the next one starts and ends inside it."""
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    for k, (s, e, name) in enumerate(ops):
        if k + 1 < len(ops) and ops[k + 1][0] < e and ops[k + 1][1] <= e:
            continue
        yield s, e, name


def _sync_idle(gaps, hosts, lo: int, hi: int) -> float:
    """Seconds of ``gaps`` whose middle lies, innermost, in a readback
    span (``SYNC_SPANS``) among the ``serve.*`` host spans."""
    spans = sorted((s, e, name) for plane in hosts for line in plane.lines
                   for name, s, e in rt._events(line)
                   if name.startswith(SERVE_SPAN) and e > lo and s < hi)
    total, active, i = 0.0, [], 0               # heap of (length, end, name)
    for g0, g1 in sorted(gaps, key=lambda g: g[0] + g[1]):
        mid = (g0 + g1) // 2
        while i < len(spans) and spans[i][0] <= mid:
            s, e, name = spans[i]
            heapq.heappush(active, (e - s, e, name))
            i += 1
        while active and active[0][1] < mid:
            heapq.heappop(active)
        if active and active[0][2] in SYNC_SPANS:
            total += (g1 - g0) * 1e-9
    return total


def reduce_profile(pd, paths: Dict[Tuple[int, str], str]) -> Dict:
    """Reduce a ``ProfileData`` with the ``op_paths`` of its bytes. Returns
    ``scopes`` ({program: {scope: [leaf operations, device seconds]}}),
    ``calls`` ({program: runs in the window}), ``sync_idle_s`` and
    ``window_s``."""
    planes = list(pd.planes)
    devices = [p for p in planes if p.name.startswith(DEVICE_PLANE)]
    hosts = [p for p in planes if p.name.startswith("/host:")]
    if not devices:
        raise ValueError(f"trace holds no {DEVICE_PLANE}<n> plane")
    win = rt.window_of(hosts)
    if win is None:
        raise ValueError(f"trace holds no host span {rt.WINDOW_SPAN!r}")
    lo, hi = win
    scopes: Dict[str, Dict[str, List[float]]] = defaultdict(
        lambda: defaultdict(lambda: [0, 0.0]))
    calls: Dict[str, int] = defaultdict(int)
    idle = 0.0
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        runs = []                               # (start, end, program, id)
        for name, s, e in rt._events(lines.get(rt.MODULE_LINE)):
            s, e = rt._clip(s, e, lo, hi)
            if e > s:
                calls[rt.module_key(name)] += 1
                runs.append((s, e, rt.module_key(name), program_id(name)))
        runs.sort()
        starts = [r[0] for r in runs]
        ops = []
        for name, s, e in rt._events(lines.get(rt.OPS_LINE)):
            s, e = rt._clip(s, e, lo, hi)
            if e > s:
                ops.append((s, e, name))
        for s, e, name in _leaves(ops):
            j = bisect.bisect_right(starts, s) - 1
            prog, pid = (runs[j][2], runs[j][3]) \
                if j >= 0 and runs[j][1] >= e else ("?", None)
            rec = scopes[prog][scope_of(paths.get((pid, name)))]
            rec[0] += 1
            rec[1] += (e - s) * 1e-9
        gaps, t = [], lo
        for s, e in rt._union([(s, e) for s, e, _ in ops]):
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < hi:
            gaps.append((t, hi))
        idle += _sync_idle(gaps, hosts, lo, hi)
    return {"scopes": {p: {k: list(v) for k, v in sc.items()}
                       for p, sc in scopes.items()},
            "calls": dict(calls),
            "sync_idle_s": idle / len(devices),
            "window_s": (hi - lo) * 1e-9}


def reduce_file(path) -> Dict:
    """Reduce an ``.xplane.pb`` file (or a gzipped one, ``.gz``)."""
    from jax.profiler import ProfileData
    raw = rt.read_raw(path)
    return reduce_profile(ProfileData.from_serialized_xspace(raw),
                          op_paths(raw))


if __name__ == "__main__":
    print(json.dumps(reduce_file(sys.argv[1])))
