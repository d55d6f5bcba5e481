"""``bench/scopes.py``: device time by named scope and device idle behind the
serve loop's readbacks, by hand on a made-up trace, on hand-encoded XSpace
bytes, and on the small trace recorded on a TPU v5e; and the reader of
``host_wait_share``."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import reduce_trace as rt
from bench import scopes, spec

DATA = Path(__file__).resolve().parent / "data" / "small.xplane.pb.gz"
US = 1000


def _ev(name, start_us, end_us):
    return NS(name=name, start_ns=start_us * US,
              duration_ns=(end_us - start_us) * US)


def _line(name, events):
    return NS(name=name, events=events)


# one chip, a 100 us window: a decode run [10, 50) whose layer loop holds a
# nested loop, and a chunk run [60, 90); the device idles in [0, 10) while
# the host is in serve.harvest, in [50, 60) under no serve.* span and in
# [90, 100) in serve.first_token (inside serve.admit, around a host event
# of the runtime's own)
OPS = [("while.1", 10, 40, "jit(decode)/while"),
       ("fusion.2", 10, 20, "jit(decode)/while/body/closed_call/rank/top_k:"),
       ("while.3", 20, 35, "jit(decode)/while/body/while"),
       ("copy.4", 20, 30, "jit(decode)/while/body/while:"),
       ("fusion.5", 30, 35, "jit(decode)/while/body/vmap(attend)/dot:"),
       ("copy.6", 35, 40, None),
       ("fusion.7", 40, 50, "jit(decode)/unembed/dot_general:"),
       ("fusion.8", 60, 90, "jit(chunk)/while/body/index_build/sort:")]
PROGRAMS = {"decode": (7, 10, 50), "chunk": (2**64 - 3, 60, 90)}
HOST = [(rt.WINDOW_SPAN, 0, 100), ("serve.harvest", 0, 12),
        ("serve.admit", 62, 100), ("serve.first_token", 88, 100),
        ("np.asarray", 92, 98)]


def _program_of(start):
    return next(p for p, (_, s, e) in PROGRAMS.items() if s <= start < e)


def _profile():
    device = NS(name="/device:TPU:0", lines=[
        _line(rt.MODULE_LINE, [_ev(f"jit_{p}({pid})", s, e)
                               for p, (pid, s, e) in PROGRAMS.items()]),
        _line(rt.OPS_LINE, [_ev(n, s, e) for n, s, e, _ in OPS])])
    host = NS(name="/host:CPU", lines=[
        _line("python", [_ev(n, s, e) for n, s, e in HOST])])
    paths = {(PROGRAMS[_program_of(s)][0], n): p.rstrip(":")
             for n, s, _, p in OPS if p is not None}
    return NS(planes=[host, device]), paths


def _expected(red):
    sc = red["scopes"]
    assert sc["decode"] == {"rank": [1, pytest.approx(10e-6)],
                            "loop": [2, pytest.approx(15e-6)],
                            "attend": [1, pytest.approx(5e-6)],
                            "unembed": [1, pytest.approx(10e-6)]}
    assert sc["chunk"] == {"index_build": [1, pytest.approx(30e-6)]}
    assert red["calls"] == {"decode": 1, "chunk": 1}
    assert red["sync_idle_s"] == pytest.approx(20e-6)
    assert red["window_s"] == pytest.approx(100e-6)


def test_scopes_by_hand():
    """Loops that hold operations are not counted, their bodies are; an
    operation with no scope, or no path at all, is the loop's; idle counts
    where the innermost serve.* span is a readback, and nowhere else."""
    pd, paths = _profile()
    _expected(scopes.reduce_profile(pd, paths))


@pytest.mark.parametrize("path, scope", [
    ("jit(decode)/while/body/closed_call/rank/top_k", "rank"),
    ("jit(fin)/vmap(index_build)/vmap(vmap())/add", "index_build"),
    ("jit(decode)/while/body/attend/rank/top_k", "rank"),
    ("jit(decode)/while", "loop"), ("jit(embed_tokens)/mul", "loop"),
    (None, "loop")])
def test_scope_is_the_innermost_vocabulary_name(path, scope):
    assert scopes.scope_of(path) == scope


# ---- hand-encoded XSpace bytes ---------------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | 0x80 if n else b)
        if not n:
            return bytes(out)


def _msg(*fields):
    """A protobuf message of (field number, int or str or bytes) pairs."""
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def _plane(name, lines, events, stat_names, stats_of):
    """An XPlane: ``lines`` [(line name, [(event name, start, end)])] in us,
    its event metadata from ``events`` (names in order of id) with the stats
    ``stats_of(name)`` gives, and its stat metadata from ``stat_names``."""
    ids = {n: i + 1 for i, n in enumerate(events)}
    out = [(1, 1), (2, name)]
    for k, (line, evs) in enumerate(lines):
        out.append((3, _msg((1, k + 1), (2, line), (3, 0), *[
            (4, _msg((1, ids[n]), (2, s * US * 1000), (3, (e - s) * US * 1000)))
            for n, s, e in evs])))
    for n, i in ids.items():
        out.append((4, _msg((1, i), (2, _msg((1, i), (2, n), *[
            (5, st) for st in stats_of(n)])))))
    for i, n in stat_names.items():
        out.append((5, _msg((1, i), (2, _msg((1, i), (2, n))))))
    return _msg(*out)


def _xspace():
    pd, _ = _profile()
    host, device = pd.planes
    # stats: tf_op by string (id 1) or by reference to a stat name (id 2),
    # program_id (id 3) as a uint64; id 9 names the second path
    paths = {n: p for n, _, _, p in OPS if p is not None}
    by_ref = "fusion.7"

    def stats_of(name):
        if name.startswith("jit_") or name not in paths:
            return []
        pid = PROGRAMS[_program_of(next(s for n, s, _, _ in OPS
                                        if n == name))][0]
        tf_op = (_msg((1, 2), (7, 9)) if name == by_ref
                 else _msg((1, 1), (5, paths[name])))
        return [tf_op, _msg((1, 3), (3, pid))]

    def lines(plane):
        return [(ln.name, [(e.name, e.start_ns // US,
                            (e.start_ns + e.duration_ns) // US)
                           for e in ln.events]) for ln in plane.lines]

    names = [e.name for ln in device.lines for e in ln.events]
    dev = _plane("/device:TPU:0", lines(device), names,
                 {1: "tf_op", 2: "tf_op", 3: "program_id", 9: paths[by_ref]},
                 stats_of)
    hst = _plane("/host:CPU", lines(host),
                 [e.name for ln in host.lines for e in ln.events],
                 {1: "tf_op"}, lambda n: [_msg((1, 1), (5, "host/rank"))])
    return _msg((1, hst), (1, dev))


def test_op_paths_reads_device_event_metadata():
    """Paths come from the device planes' event metadata, by string or by
    reference, keyed by program id and event name; JAX's own reader sees
    the same events in the same bytes."""
    from jax.profiler import ProfileData
    raw = _xspace()
    got = scopes.op_paths(raw)
    assert got == {(PROGRAMS[_program_of(s)][0], n): p.rstrip(":")
                   for n, s, _, p in OPS if p is not None}
    pd = ProfileData.from_serialized_xspace(raw)
    seen = {p.name: {ln.name: [e.name for e in ln.events] for ln in p.lines}
            for p in pd.planes}
    assert seen["/device:TPU:0"][rt.OPS_LINE] == [n for n, *_ in OPS]
    assert seen["/host:CPU"]["python"] == [n for n, *_ in HOST]


def test_reduce_file_on_encoded_trace(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace())
    _expected(scopes.reduce_file(path))


def test_recorded_trace_reduces_to_the_loop():
    """The recorded v5e trace predates the scopes and keeps no event
    statistics: each program's leaf operations all fall under ``loop``,
    within the program's device time, and no readback span is there."""
    red = scopes.reduce_file(DATA)
    mods = rt.reduce_file(DATA)["modules"]
    for prog, sc in red["scopes"].items():
        assert set(sc) == {scopes.LOOP}, prog
        if prog != "?":
            assert 0 < sc[scopes.LOOP][1] <= mods[prog][1] + 1e-9
            assert red["calls"][prog] == mods[prog][0]
    assert {"decode", "chunk", "fin", "flush"} <= set(red["scopes"])
    assert red["sync_idle_s"] == 0.0


def test_vocabulary_is_the_programs():
    from repro.serving import trace
    assert set(scopes.SCOPES) == set(trace.SCOPES)
    assert set(scopes.SYNC_SPANS) == {trace.HARVEST, trace.FIRST_TOKEN}
    assert all(s.startswith(scopes.SERVE_SPAN) for s in
               (trace.ADMIT, trace.FIRST_TOKEN, trace.DECODE, trace.HARVEST,
                trace.FLUSH))


@pytest.mark.parametrize("serve, value", [
    (NS(), None), (NS(harvest_wait_s=1.0), None),
    (NS(harvest_wait_s=1.5, first_token_wait_s=0.5), 40.0)],
    ids=["no-counters", "one-counter", "both"])
def test_host_wait_share_reader(serve, value):
    """None from a program without the readback counters."""
    read = spec.load_reader("host_wait_share")
    got = read(NS(serve=serve, wall_s=5.0, trace=None))
    assert got == (value if value is None else pytest.approx(value))
