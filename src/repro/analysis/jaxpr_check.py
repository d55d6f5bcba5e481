"""Trace-time contract checks (RL101-RL104) over the jitted serve stages.

Rather than hardcoding what the engine jits, the checker *records* it:
``StageRecorder`` monkeypatches ``jax.jit`` while a real (tiny-config) serve
run executes, capturing for every jit built at runtime its function name, the
jit kwargs (``donate_argnums``), the underlying jitted object, and the
argument avals of its first call. Stages registered in
``serving.engine.SERVE_STAGES`` are then held to their contract:

* RL101 — the stage jaxpr contains no callback / host-transfer primitive;
* RL102 — declared donations match the contract AND every donated leaf
  lowers to a real output alias (``tf.aliasing_output`` in the MLIR), with
  the "donated buffers were not usable" UserWarning treated as a violation;
* RL103 — across the run each stage compiles exactly its budgeted number of
  times (counted from the ``jax_log_compiles`` log stream);
* RL104 — (advice) an un-donated large input with an identically-shaped
  output, the usual signature of an in-place update paying a copy.

Everything runs on CPU with the tiny geometry below (same scale as the
tier-1 system tests); one full check is two short serve runs.
"""
from __future__ import annotations

import functools
import logging
import re
import warnings
from collections import Counter
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.tree_util as jtu
import numpy as np

from repro.analysis import numerics_check
from repro.analysis.findings import Finding

ENGINE_PATH = "src/repro/serving/engine.py"

_CALLBACK_TAGS = ("callback", "infeed", "outfeed")
_TRANSFER_PRIMS = {"device_put"}

_COMPILE_RE = re.compile(r"Compiling jit\(([\w.<>\[\]-]+)\) with global shapes")

# RL104 only looks at inputs at least this large — below it a defensive copy
# is noise, not a throughput bug
_RL104_MIN_BYTES = 1 << 16


def _aval(x):
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)
    return jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype)


@dataclass
class StageRecord:
    name: str
    fn: Any
    jitted: Any
    jit_kwargs: Dict[str, Any]
    avals: Optional[Tuple] = None       # per-arg aval pytrees, first call


class StageRecorder:
    """Context manager: patch ``jax.jit`` to record every jit built (and the
    avals of its first call) while leaving behavior untouched."""

    def __init__(self) -> None:
        self.records: Dict[str, StageRecord] = {}

    def __enter__(self) -> "StageRecorder":
        self._orig = jax.jit
        recorder = self

        def recording_jit(fun=None, **kw):
            if fun is None:                     # jax.jit(**kw) decorator form
                return functools.partial(recording_jit, **kw)
            jitted = recorder._orig(fun, **kw)
            name = getattr(fun, "__name__", "<anonymous>")
            rec = recorder.records.setdefault(
                name, StageRecord(name, fun, jitted, dict(kw)))

            @functools.wraps(fun)
            def wrapper(*args, **kwargs):
                if rec.avals is None and not kwargs:
                    try:
                        rec.avals = tuple(jtu.tree_map(_aval, a)
                                          for a in args)
                    except (TypeError, ValueError):
                        pass
                return jitted(*args, **kwargs)

            wrapper._retrolint_jitted = jitted
            return wrapper

        jax.jit = recording_jit
        return self

    def __exit__(self, *exc) -> None:
        jax.jit = self._orig


class CompileLog:
    """Context manager counting XLA compilations per function name via the
    ``jax_log_compiles`` log stream (logger ``jax._src.interpreters.pxla``)."""

    def __init__(self) -> None:
        self.counts: Counter = Counter()

    def __enter__(self) -> "CompileLog":
        log = self

        class _H(logging.Handler):
            def emit(self, record):
                m = _COMPILE_RE.search(record.getMessage())
                if m:
                    log.counts[m.group(1)] += 1

        self._handler = _H()
        self._logger = logging.getLogger("jax._src.interpreters.pxla")
        self._logger.addHandler(self._handler)
        # jax_log_compiles elevates trace/compile logs to WARNING — keep
        # them out of the user's terminal while we count
        self._silenced = [self._logger,
                          logging.getLogger("jax._src.dispatch")]
        self._propagate = [lg.propagate for lg in self._silenced]
        self._null = logging.NullHandler()      # defeats logging.lastResort
        for lg in self._silenced:
            lg.propagate = False
            lg.addHandler(self._null)
        self._prev = jax.config.jax_log_compiles
        jax.config.update("jax_log_compiles", True)
        return self

    def __exit__(self, *exc) -> None:
        jax.config.update("jax_log_compiles", self._prev)
        self._logger.removeHandler(self._handler)
        for lg, p in zip(self._silenced, self._propagate):
            lg.propagate = p
            lg.removeHandler(self._null)


# ------------------------------------------------------------ per-stage checks
def _iter_subjaxprs(params: Dict[str, Any]):
    import jax.extend.core as jcore
    for v in params.values():
        vals = v if isinstance(v, (list, tuple)) else (v,)
        for x in vals:
            if isinstance(x, jcore.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jcore.Jaxpr):
                yield x


def _scan_jaxpr(jaxpr, hits: Counter) -> None:
    for eqn in jaxpr.eqns:
        pname = eqn.primitive.name
        if any(t in pname for t in _CALLBACK_TAGS) \
                or pname in _TRANSFER_PRIMS:
            hits[pname] += 1
        for sub in _iter_subjaxprs(eqn.params):
            _scan_jaxpr(sub, hits)


def callback_findings(fn, avals: Sequence, name: str,
                      path: str = ENGINE_PATH) -> List[Finding]:
    """RL101 over one traceable function at the given avals."""
    try:
        jaxpr = jax.make_jaxpr(fn)(*avals)
    except Exception as e:      # tracing failed: surface, don't crash the CLI
        return [Finding("RL101", path, 0, name,
                        f"stage could not be traced for inspection: {e!r}")]
    hits: Counter = Counter()
    _scan_jaxpr(jaxpr.jaxpr, hits)
    return [
        Finding("RL101", path, 0, name,
                f"stage traces host primitive `{prim}` x{n} — jitted serve "
                f"stages must be pure device compute")
        for prim, n in sorted(hits.items())]


def _norm_donate(d) -> Tuple[int, ...]:
    if d is None:
        return ()
    return (d,) if isinstance(d, int) else tuple(d)


def donation_findings(jitted, avals: Sequence, declared: Tuple[int, ...],
                      contract: Tuple[int, ...], name: str,
                      path: str = ENGINE_PATH) -> List[Finding]:
    """RL102 over one jitted stage: contract match + true aliasing."""
    findings: List[Finding] = []
    if tuple(sorted(declared)) != tuple(sorted(contract)):
        findings.append(Finding(
            "RL102", path, 0, name,
            f"stage declares donate_argnums={tuple(sorted(declared))} but "
            f"the serve contract requires {tuple(sorted(contract))} — an "
            f"in-place stage without its donation pays a full copy per "
            f"step"))
        return findings
    if not declared:
        return findings
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        try:
            mlir = jitted.lower(*avals).as_text()
        except Exception as e:
            return [Finding("RL102", path, 0, name,
                            f"stage could not be lowered for donation "
                            f"analysis: {e!r}")]
    unused = [w for w in caught
              if "donated" in str(w.message).lower()]
    donated_leaves = sum(len(jtu.tree_leaves(avals[i])) for i in declared
                         if i < len(avals))
    aliased = len(re.findall(r"tf\.aliasing_output", mlir))
    if unused or aliased < donated_leaves:
        findings.append(Finding(
            "RL102", path, 0, name,
            f"donation does not fully alias: {aliased}/{donated_leaves} "
            f"donated leaves carry tf.aliasing_output"
            + (f" (XLA: {unused[0].message})" if unused else "")))
    return findings


def missed_donation_findings(rec: StageRecord, contract: Tuple[int, ...],
                             path: str = ENGINE_PATH) -> List[Finding]:
    """RL104 (advice): large un-donated inputs with identically-shaped
    outputs."""
    if rec.avals is None:
        return []
    try:
        out = jax.eval_shape(rec.fn, *rec.avals)
    except Exception:
        return []
    out_shapes = {(tuple(leaf.shape), jtu.tree_leaves(leaf)[0].dtype.name
                   if hasattr(leaf, "dtype") else None)
                  for leaf in jtu.tree_leaves(out)
                  if hasattr(leaf, "shape")}
    findings = []
    for i, arg in enumerate(rec.avals):
        if i in contract:
            continue
        for leaf in jtu.tree_leaves(arg):
            if not hasattr(leaf, "shape"):
                continue
            nbytes = int(np.prod(leaf.shape, dtype=np.int64)) \
                * leaf.dtype.itemsize
            if nbytes < _RL104_MIN_BYTES:
                continue
            if (tuple(leaf.shape), leaf.dtype.name) in out_shapes:
                findings.append(Finding(
                    "RL104", path, 0, rec.name,
                    f"arg {i} has an un-donated {leaf.dtype.name}"
                    f"{tuple(leaf.shape)} leaf matching an output shape — "
                    f"likely an in-place update paying a copy",
                    severity="advice"))
                break
    return findings


# ----------------------------------------------------------------- serve runs
def _tiny_setup():
    from repro.configs.base import AttnConfig, ModelConfig, RetroConfig
    from repro.models import model as M
    retro = RetroConfig(avg_cluster=8, cluster_cap=64, prefill_segment=64,
                        update_segment=32, sink=4, local=32,
                        retrieval_frac=1.0, estimation_frac=0.0,
                        kmeans_iters=3)
    cfg = ModelConfig(
        arch_id="retrolint-tiny", family="dense", n_layers=2, d_model=64,
        d_ff=128, vocab=256,
        attn=AttnConfig(n_heads=4, n_kv_heads=2, head_dim=16),
        dtype="float32", retro=retro)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _requests(lengths: Sequence[int], max_new: int):
    from repro.serving.engine import Request
    rng = np.random.RandomState(0)
    return [Request(prompt=rng.randint(1, 250, size=(n,)).astype(np.int32),
                    max_new_tokens=max_new) for n in lengths]


@dataclass
class RunReport:
    label: str
    recorder: StageRecorder
    compiles: Counter
    expected: Dict[str, int]
    findings: List[Finding] = field(default_factory=list)


def _serve_run(label: str, cfg, params, *, lengths: Sequence[int],
               max_new: int, exercised: Sequence[str],
               n_prompt_lens: int, n_buckets: int,
               **engine_kw) -> RunReport:
    from repro.serving.engine import SERVE_STAGES, ServeEngine
    with StageRecorder() as rec, CompileLog() as clog:
        engine = ServeEngine(cfg, params, gen_headroom=256, **engine_kw)
        engine.serve(_requests(lengths, max_new), batch_size=2, seed=0)
    expected: Dict[str, int] = {}
    for name, contract in SERVE_STAGES.items():
        if name not in exercised:
            expected[name] = 0
        elif contract["budget"] == "per_prompt_len":
            expected[name] = n_prompt_lens
        elif contract["budget"] == "per_prompt_bucket":
            expected[name] = n_buckets
        else:
            expected[name] = 1
    return RunReport(label, rec, clog.counts, expected)


# run plans: which contract stages each serve mode exercises
_OFFLOAD_STAGES = ("argmax_ids", "merge_tokens", "chunk", "fin",
                   "embed_tokens", "rank_fn", "attend_fn", "unembed_logits",
                   "cache_upd", "cache_stage", "offload_flush")
_BLOCKING_STAGES = ("graft", "categorical_ids", "merge_tokens", "prefill",
                    "decode", "flush")


def run_contract_checks(verbose=None) -> List[Finding]:
    """The full trace-time gate: a chunked+offload serve and a
    blocking+direct serve (tiny config), then every SERVE_STAGES contract
    verified against what was recorded. The offload run doubles as the
    retrosched (RL301-RL305) schedule recording: a ``ScheduleRecorder``
    captures the control-plane event stream and the happens-before checker
    runs over it — no third serve run."""
    from repro.analysis.schedule_check import schedule_findings
    from repro.analysis.schedule_model import ScheduleRecorder
    from repro.serving.engine import SERVE_STAGES
    log = verbose or (lambda *_: None)
    cfg, params = _tiny_setup()
    lengths = [48, 72, 96, 72]          # ragged mix, one duplicate length

    log("retrolint: serve run 1/2 (chunked admission, host-offload decode)")
    with ScheduleRecorder() as sched:
        run_a = _serve_run(
            "chunked+offload", cfg, params, lengths=lengths, max_new=40,
            exercised=_OFFLOAD_STAGES, n_prompt_lens=len(set(lengths)),
            n_buckets=len(set(lengths)),
            admission="chunked", offload=True, temperature=0.0)
    log("retrolint: serve run 2/2 (blocking admission, direct decode)")
    run_b = _serve_run(
        "blocking+direct", cfg, params, lengths=lengths, max_new=40,
        exercised=_BLOCKING_STAGES, n_prompt_lens=len(set(lengths)),
        n_buckets=len(set(lengths)),
        admission="blocking", offload=False, temperature=0.7)

    findings: List[Finding] = []
    log("retrolint: retrosched happens-before check over the offload "
        "schedule")
    findings += schedule_findings(sched.trace)
    checked: set = set()
    for run in (run_a, run_b):
        # RL103: per-stage compile budget over the run
        for name, exp in sorted(run.expected.items()):
            obs = run.compiles.get(name, 0)
            if obs != exp:
                findings.append(Finding(
                    "RL103", ENGINE_PATH, 0, name,
                    f"stage compiled {obs}x over the {run.label} run, "
                    f"budget is {exp}"))
        # RL101/RL102/RL104 on every recorded contract stage (once per name)
        for name, rec in sorted(run.recorder.records.items()):
            contract = SERVE_STAGES.get(name)
            if contract is None or name in checked:
                continue
            if rec.avals is None:
                continue            # built but never called in this run
            checked.add(name)
            log(f"retrolint: checking stage `{name}`")
            findings += callback_findings(rec.fn, rec.avals, name)
            findings += donation_findings(
                rec.jitted, rec.avals,
                _norm_donate(rec.jit_kwargs.get("donate_argnums")),
                tuple(contract["donate"]), name)
            findings += missed_donation_findings(
                rec, tuple(contract["donate"])
                + tuple(contract.get("copy_ok", ())))
            # retronum (RL401-RL405): the stage's declared numerics
            # contract, checked over the same recorded trace
            if contract.get("numerics") is not None:
                findings += numerics_check.stage_findings(
                    rec.fn, rec.avals, name, contract["numerics"],
                    ENGINE_PATH)
    # a contract stage that NO run exercised means the registry rotted
    for name in SERVE_STAGES:
        if name not in checked and all(r.expected.get(name, 0) == 0
                                       for r in (run_a, run_b)):
            continue        # contractually idle under both plans
        if name not in checked:
            findings.append(Finding(
                "RL103", ENGINE_PATH, 0, name,
                "stage is in SERVE_STAGES but was never built by either "
                "serve run — stale contract entry or renamed stage"))
    return findings
