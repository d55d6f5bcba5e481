"""retrolint self-tests: every rule against a known-good and a known-bad
fixture.

The bad fixtures double as the CI tripwire: each is a complete source
snippet that, if seeded into ``src/``, MUST make ``repro.launch.lint`` exit
non-zero (the good twin must stay silent). ``run_selftests()`` executes the
whole table and returns the failures; the CLI (``--selftest``) and
``tests/test_analysis.py`` both consume it.

AST/Pallas fixtures run through the real source-level drivers. The jaxpr
rules (RL101/RL102) are exercised with real traced functions — tiny jits
with a deliberately smuggled callback / un-aliasable donation.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List

from repro.analysis.ast_rules import lint_source
from repro.analysis.findings import Finding
from repro.analysis.pallas_check import check_source

# --------------------------------------------------------------- AST fixtures
_RL001_BAD = '''
import numpy as np

def decode_step(state):  # retrolint: hot
    ids = np.asarray(state.idx)           # unsanctioned host sync
    return ids
'''

_RL001_GOOD = '''
import numpy as np

def decode_step(state):  # retrolint: hot
    ids = np.asarray(state.idx)  # retrolint: sync(control-plane readback)
    return ids

def cold_path(state):
    return np.asarray(state.idx)          # not a hot function: fine
'''

_RL002_BAD = '''
import jax

@jax.jit
def f(x):
    if x > 0:                             # traced-value branch
        return x
    return -x
'''

_RL002_GOOD = '''
import jax
import jax.numpy as jnp

@jax.jit
def f(x, flag=None):
    if flag is None:                      # static identity check: fine
        x = x + 1
    for i in range(x.shape[0]):           # shape is static: fine
        x = x + i
    return jnp.where(x > 0, x, -x)        # data-dependent: on device
'''

_RL003_BAD = '''
import jax

def build(fns):
    out = []
    for f in fns:
        out.append(jax.jit(f))            # fresh jit cache per iteration
    return out
'''

_RL003_GOOD = '''
import jax

def build(fns):
    jitted = [jax.jit(f) for f in fns]    # comprehension builder: cached once

    def runner(xs):
        for f, x in zip(jitted, xs):      # calling in a loop is fine
            f(x)
    return runner
'''

_RL004_BAD = '''
import jax
from functools import partial

@partial(jax.jit, donate_argnums=(0,))
def step(state, x):
    return state

def loop(state, xs):
    for x in xs:
        out = step(state, x)              # state re-donated every iteration
    return out
'''

_RL004_GOOD = '''
import jax
from functools import partial

@partial(jax.jit, donate_argnums=(0,))
def step(state, x):
    return state

def loop(state, xs):
    for x in xs:
        state = step(state, x)            # rebound from the result
    return state
'''

# ------------------------------------------------------------ Pallas fixtures
_RL201_GOOD = '''
import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

def _db_kernel(idx_ref, kst_ref, kdb_scr, ksem, o_ref, *, r):
    def dmas(slot, jc):
        cid = idx_ref[0, jc]
        return (pltpu.make_async_copy(kst_ref.at[0, cid], kdb_scr.at[slot],
                                      ksem.at[slot]),)

    for c in dmas(0, 0):                  # warm up slot 0
        c.start()

    def body(jc, carry):
        cur = jax.lax.rem(jc, 2)
        nxt = jax.lax.rem(jc + 1, 2)

        @pl.when(jc + 1 < r)
        def _prefetch():
            for c in dmas(nxt, jc + 1):   # prefetch next into OTHER slot
                c.start()

        for c in dmas(cur, jc):           # await current before reading
            c.wait()
        o_ref[0] = kdb_scr[cur]
        return carry

    jax.lax.fori_loop(0, r, body, 0)
'''

# read without ever waiting: the headline silent data race
_RL201_BAD_NOWAIT = _RL201_GOOD.replace(
    """        for c in dmas(cur, jc):           # await current before reading
            c.wait()
""", "")

# prefetch into the slot currently being folded
_RL201_BAD_SAME_SLOT = _RL201_GOOD.replace("dmas(nxt, jc + 1)",
                                           "dmas(cur, jc + 1)")

# warm-up removed: first wait has nothing in flight
_RL201_BAD_NO_WARMUP = _RL201_GOOD.replace(
    """    for c in dmas(0, 0):                  # warm up slot 0
        c.start()
""", "")

_RL202_BAD = '''
from jax.experimental import pallas as pl

def build(x, table):
    bad = lambda b, j: (b, table.lookup(j), 0)    # arbitrary call: impure
    return pl.BlockSpec((1, 8, 128), bad)
'''

_RL202_GOOD = '''
import jax.numpy as jnp
from jax.experimental import pallas as pl

def build(nlb, r):
    lmap = lambda b, j, *_: (b, jnp.clip(j - 1, 0, nlb - 1), 0)
    cmap = lambda b, j, idx_ref, *_: (b, idx_ref[b, j], 0, 0)
    return pl.BlockSpec((1, 8, 128), lmap), pl.BlockSpec((1, 1, 64), cmap)
'''

_RL203_BAD = '''
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

def build_kernel():
    return [pltpu.VMEM((4096, 4096, 4), jnp.float32)]   # 256 MiB scratch
'''

_RL203_GOOD = '''
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

def build_kernel(cap, hd):
    return [pltpu.VMEM((2, cap, hd), jnp.float32)]
'''


@dataclass
class Fixture:
    rule: str
    bad: str
    good: str
    checker: Callable[[str], List[Finding]]


def _ast(src: str) -> List[Finding]:
    return lint_source(src, "selftest.py")


def _pallas(src: str) -> List[Finding]:
    return check_source(src, "selftest.py")


FIXTURES: List[Fixture] = [
    Fixture("RL001", _RL001_BAD, _RL001_GOOD, _ast),
    Fixture("RL002", _RL002_BAD, _RL002_GOOD, _ast),
    Fixture("RL003", _RL003_BAD, _RL003_GOOD, _ast),
    Fixture("RL004", _RL004_BAD, _RL004_GOOD, _ast),
    Fixture("RL201", _RL201_BAD_NOWAIT, _RL201_GOOD, _pallas),
    Fixture("RL201", _RL201_BAD_SAME_SLOT, _RL201_GOOD, _pallas),
    Fixture("RL201", _RL201_BAD_NO_WARMUP, _RL201_GOOD, _pallas),
    Fixture("RL202", _RL202_BAD, _RL202_GOOD, _pallas),
    Fixture("RL203", _RL203_BAD, _RL203_GOOD, _pallas),
]

# bad fixtures by rule, exported so tests can seed them into a fake src/
# tree and assert the CLI gate trips
BAD_FIXTURES: Dict[str, str] = {}
for _fx in FIXTURES:
    BAD_FIXTURES.setdefault(_fx.rule, _fx.bad)


# -------------------------------------------------- traced-rule self-tests
def _selftest_rl101() -> List[str]:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.analysis.jaxpr_check import callback_findings
    aval = (jax.ShapeDtypeStruct((8,), jnp.float32),)

    def bad(x):
        return jax.pure_callback(
            np.sin, jax.ShapeDtypeStruct(x.shape, x.dtype), x)

    def good(x):
        return jnp.sin(x)

    fails = []
    if not any(f.rule == "RL101" for f in callback_findings(bad, aval, "bad")):
        fails.append("RL101: callback stage not flagged")
    if callback_findings(good, aval, "good"):
        fails.append("RL101: pure stage falsely flagged")
    return fails


def _selftest_rl102() -> List[str]:
    import jax
    import jax.numpy as jnp
    from repro.analysis.jaxpr_check import donation_findings
    aval = (jax.ShapeDtypeStruct((128,), jnp.float32),)

    def update(x):
        return x + 1.0                      # same shape: donation aliases

    def reduce(x):
        return jnp.sum(x)                   # no matching output: silent copy

    good = jax.jit(update, donate_argnums=(0,))
    bad = jax.jit(reduce, donate_argnums=(0,))
    fails = []
    if donation_findings(good, aval, (0,), (0,), "good"):
        fails.append("RL102: aliasing donation falsely flagged")
    if not any(f.rule == "RL102"
               for f in donation_findings(bad, aval, (0,), (0,), "bad")):
        fails.append("RL102: non-aliasing donation not flagged")
    if not any(f.rule == "RL102"
               for f in donation_findings(good, aval, (), (0,), "missing")):
        fails.append("RL102: missing contracted donation not flagged")
    return fails


def _selftest_rl103() -> List[str]:
    import jax
    import jax.numpy as jnp
    from repro.analysis.jaxpr_check import CompileLog

    def shapely_stage(x):
        return x * 2.0

    jitted = jax.jit(shapely_stage)
    with CompileLog() as clog:
        jitted(jnp.zeros((4,), jnp.float32))
        jitted(jnp.zeros((4,), jnp.float32))    # cache hit: no recompile
        jitted(jnp.zeros((8,), jnp.float32))    # new shape: recompile
    n = clog.counts.get("shapely_stage", 0)
    if n != 2:
        return [f"RL103: compile log counted {n} compiles, expected 2"]
    return []


# ---------------------------------------------- retrosched (RL3xx) fixtures
# Schedule fixtures are op sequences resolved through the REAL SERVE_STAGES
# effects declarations (schedule_model.build_trace), so each selftest
# exercises exactly the model the live engine trace is held to. Ops outside
# the table (a rogue host mirror, a donation with no rebind) inject raw
# effects via the extras channel.
def _sched_check(schedule, rule: str, expect: bool, label: str) -> List[str]:
    from repro.analysis.schedule_check import check_trace
    from repro.analysis.schedule_model import build_trace
    hits = [f for f in check_trace(build_trace(schedule, 2))
            if f.rule == rule]
    if expect and not hits:
        return [f"{rule}: {label} schedule not flagged"]
    if not expect and hits:
        return [f"{rule}: {label} schedule falsely flagged: "
                f"{hits[0].render()}"]
    return []


def _selftest_rl301() -> List[str]:
    from repro.analysis.schedule_check import reference_schedule
    # attend dispatched BEFORE the staging write: move each layer's
    # cache-stage dispatch to just after its attend
    bad: List[tuple] = []
    held = None
    for ev in reference_schedule():
        if ev[2] in ("cache_stage", "cache_upd"):
            held = ev
            continue
        bad.append(ev)
        if ev[2] == "attend_fn" and held is not None:
            bad.append(held)
            held = None
    fails = _sched_check(bad, "RL301", True, "attend-before-staging-write")
    fails += _sched_check(reference_schedule(), "RL301", False,
                          "pipelined reference")
    return fails


def _selftest_rl302() -> List[str]:
    from repro.analysis.schedule_check import reference_schedule
    # admissions queued by the drain but the next step stages with
    # cache_stage — the mapping table got remapped without its mirror edge
    fails = _sched_check(reference_schedule(drop_mirror=True), "RL302",
                         True, "mirror-dropping")
    fails += _sched_check(reference_schedule(), "RL302", False,
                          "pipelined reference")
    return fails


def _selftest_rl303() -> List[str]:
    from repro.analysis.schedule_check import reference_schedule
    mirror = {"effects": {"writes": ("cache_body[l]",)}}
    logits_sync = {"effects": {"reads": ("logits",)}}

    def with_host_mirror(synced: bool):
        # the mirror targets layer 1: layer 0's attend is already proven
        # complete by layer 1's id sync, so only the last attend is in flight
        sched = list(reference_schedule(steps=1))
        tail = [(0, 1, "host_mirror", "host", mirror)]
        if synced:       # sync on the logits first: attend proven complete
            tail.insert(0, (0, -1, "sample_sync", "sync", logits_sync))
        return sched + tail

    fails = _sched_check(with_host_mirror(False), "RL303", True,
                         "unsynced host mirror")
    fails += _sched_check(with_host_mirror(True), "RL303", False,
                          "synced host mirror")
    return fails


def _selftest_rl304() -> List[str]:
    from repro.analysis.schedule_check import reference_schedule
    # the pre-pipeline engine order: drain(l) runs BEFORE rank(l+1) is
    # dispatched, so the id sync idles behind independent host work
    fails = _sched_check(reference_schedule(pipelined=False), "RL304",
                         True, "unpipelined")
    fails += _sched_check(reference_schedule(), "RL304", False,
                          "pipelined reference")
    return fails


def _selftest_rl305() -> List[str]:
    from repro.analysis.schedule_check import reference_schedule
    # rank donates the live tree but (unlike the real stage) does not return
    # a rebound copy — the later attend reads clobbered memory
    leaky = {"effects": {"reads": ("hidden", "live[l]"),
                         "writes": ("ctx[l]", "ids[l]"),
                         "donates": ("live[l]",)}}
    bad = [ev if ev[2] != "rank_fn" else ev[:4] + (leaky,)
           for ev in reference_schedule(steps=1)]
    fails = _sched_check(bad, "RL305", True, "donation-without-rebind")
    fails += _sched_check(reference_schedule(), "RL305", False,
                          "pipelined reference")
    return fails


# ------------------------------------------------------- retronum (RL4xx)
def _num_check(fn, avals, rule: str, want_bad: bool, label: str,
               contract=None) -> List[str]:
    """Trace ``fn`` through the retronum pass; assert the rule fires (bad
    twin) or that NO error fires at all (good twin)."""
    from repro.analysis.numerics_check import numerics_findings
    fs = numerics_findings(fn, avals, label,
                           path="src/repro/analysis/selftest.py",
                           contract=contract)
    errs = [f for f in fs if f.severity == "error"]
    if want_bad:
        if not any(f.rule == rule for f in errs):
            return [f"{rule}: {label} not flagged"]
        return []
    if errs:
        return [f"{rule}: {label} falsely flagged: {errs[0].render()}"]
    return []


def _selftest_rl401() -> List[str]:
    import jax
    import jax.numpy as jnp
    aval = (jax.ShapeDtypeStruct((8, 16), jnp.bfloat16),)
    # a bf16 LSE chain: exp runs on the storage dtype
    fails = _num_check(lambda x: jax.nn.softmax(x, axis=-1), aval,
                       "RL401", True, "bf16 softmax chain")
    fails += _num_check(
        lambda x: jax.nn.softmax(x.astype(jnp.float32), axis=-1), aval,
        "RL401", False, "f32-upcast softmax chain")
    return fails


def _selftest_rl402() -> List[str]:
    import jax
    import jax.numpy as jnp
    a = jax.ShapeDtypeStruct((2048, 2048), jnp.bfloat16)   # 8 MiB "store"
    b = jax.ShapeDtypeStruct((2048, 64), jnp.bfloat16)
    # (a) sub-f32 operands, accumulator defaults to bf16
    fails = _num_check(
        lambda x, y: jnp.einsum("ij,jk->ik", x, y), (a, b),
        "RL402", True, "einsum without preferred_element_type")
    # (b) the hoisted-cast hazard: whole-store astype(f32) before the dot
    fails += _num_check(
        lambda x, y: jnp.einsum("ij,jk->ik", x.astype(jnp.float32),
                                y.astype(jnp.float32)), (a, b),
        "RL402", True, "explicit whole-store pre-upcast")
    fails += _num_check(
        lambda x, y: jnp.einsum("ij,jk->ik", x, y,
                                preferred_element_type=jnp.float32), (a, b),
        "RL402", False, "storage operands + preferred_element_type")
    return fails


def _selftest_rl403() -> List[str]:
    import jax
    import jax.numpy as jnp
    aval = (jax.ShapeDtypeStruct((8, 8), jnp.float32),)
    fails = _num_check(
        lambda x: x.astype(jnp.bfloat16).astype(jnp.float32) + 1.0, aval,
        "RL403", True, "f32->bf16->f32 round trip")
    fails += _num_check(lambda x: x + 1.0, aval,
                        "RL403", False, "straight f32 chain")
    return fails


def _selftest_rl404() -> List[str]:
    import jax
    import jax.numpy as jnp
    aval = (jax.ShapeDtypeStruct((8, 8), jnp.float32),)
    # narrowed mid-stage, then general compute consumes the bf16 value
    fails = _num_check(
        lambda x: x.astype(jnp.bfloat16) * jnp.bfloat16(2.0), aval,
        "RL404", True, "mid-stage downcast consumed by compute")
    # output-only narrowing: the sanctioned final astype
    fails += _num_check(
        lambda x: (x * 2.0).astype(jnp.bfloat16), aval,
        "RL404", False, "output-only downcast")
    return fails


def _selftest_rl405() -> List[str]:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.analysis.numerics_check import parts_findings
    f32 = jnp.float32
    avals = (jax.ShapeDtypeStruct((2, 4), f32),
             jax.ShapeDtypeStruct((2,), f32),
             jax.ShapeDtypeStruct((2,), f32))
    fails = []
    fs = parts_findings(
        lambda n, d, m: (n.astype(jnp.bfloat16), d, m), avals,
        "bf16-num", path="selftest")
    if not any(f.rule == "RL405" for f in fs):
        fails.append("RL405: bf16 LSE-merge partial not flagged")
    fs = parts_findings(lambda n, d, m: (n, d, m), avals,
                        "f32-parts", path="selftest")
    if fs:
        fails.append(f"RL405: f32 parts falsely flagged: {fs[0].render()}")
    # collective flavor: a psum over bf16 partials inside shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))

    def collective(cast):
        def body(x):
            y = x.astype(jnp.bfloat16) if cast else x
            return jax.lax.psum(y, "x")
        return jax.shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                             check_vma=False)
    aval = (jax.ShapeDtypeStruct((8,), f32),)
    fails += _num_check(collective(True), aval,
                        "RL405", True, "psum over bf16 partials")
    fails += _num_check(collective(False), aval,
                        "RL405", False, "psum over f32 partials")
    return fails


def _selftest_rl406() -> List[str]:
    from repro.analysis.numerics_check import (_pallas_avals,
                                               numerics_findings)
    inventory: List = []
    fn, avals = _pallas_avals(double_buffer=True)
    fs = numerics_findings(fn, avals, "paged_wave_attention",
                           path="src/repro/kernels/wave_attention/ops.py",
                           inventory=inventory)
    fails = []
    if [f for f in fs if f.severity == "error"]:
        fails.append(f"RL406: kernel trace errored: {fs[0].render()}")
    if not inventory:
        fails.append("RL406: paged-kernel VMEM cast inventory came back "
                     "empty — the kernel-inlining path broke")
    if any(f.severity != "advice" or f.rule != "RL406" for f in inventory):
        fails.append("RL406: inventory entries must be RL406 advice")
    return fails


def run_selftests(include_traced: bool = True) -> List[str]:
    """Run every fixture; return failure descriptions (empty = all pass)."""
    fails: List[str] = []
    for i, fx in enumerate(FIXTURES):
        bad_hits = [f for f in fx.checker(fx.bad) if f.rule == fx.rule]
        if not bad_hits:
            fails.append(f"{fx.rule} (fixture {i}): bad snippet not flagged")
        good_hits = [f for f in fx.checker(fx.good)
                     if f.severity == "error"]
        if good_hits:
            fails.append(
                f"{fx.rule} (fixture {i}): good snippet flagged: "
                f"{good_hits[0].render()}")
    fails += _selftest_rl301()
    fails += _selftest_rl302()
    fails += _selftest_rl303()
    fails += _selftest_rl304()
    fails += _selftest_rl305()
    if include_traced:
        fails += _selftest_rl101()
        fails += _selftest_rl102()
        fails += _selftest_rl103()
        fails += _selftest_rl401()
        fails += _selftest_rl402()
        fails += _selftest_rl403()
        fails += _selftest_rl404()
        fails += _selftest_rl405()
        fails += _selftest_rl406()
    return fails
