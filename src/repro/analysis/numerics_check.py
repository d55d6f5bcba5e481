"""retronum — jaxpr precision-flow checker for the decode numerics contract.

The paper's accuracy claim (full-attention-level output from
accuracy-bounded estimation, Sec. 4.4/Fig. 18) rests on a mixed-precision
discipline the code states only in comments: payload stores may be bf16,
but every softmax/LSE chain, every dot accumulator and every LSE-merge
partial is f32, values are widened *per tile* (``preferred_element_type``
/ the kernel's VMEM casts) rather than via whole-store ``astype``, and the
single sanctioned narrowing is the stage-output ``astype(q.dtype)`` (plus
same-dtype storage writes). retronum makes that discipline machine-checked:

* an abstract interpreter flattens a stage jaxpr (inlining ``pjit`` and
  friends, recursing into ``cond``/``scan``/``while``/``shard_map`` bodies
  and — with ``pallas_check``'s kernel-inlining trick — into the Pallas
  kernel body under ``pallas_call``'s ``jaxpr`` param) into a dataflow
  graph over SSA values,
* propagates a precision lattice (storage dtype x accumulation dtype x
  rounding count, tracked via convert provenance) through it,
* and checks the per-stage contract declared as ``numerics=`` in
  ``serving.engine.SERVE_STAGES`` (schema: ``README.md``).

Rules: RL401 (sub-f32 softmax/exp/log chain), RL402 (dot accumulation:
missing ``preferred_element_type=f32`` or the hoisted whole-store upcast),
RL403 (f32->bf16->f32 double rounding), RL404 (narrowing consumed by
general compute), RL405 (LSE-merge partial/collective below f32), RL406
(advice: the certified VMEM cast-site inventory the quantization roadmap
item will hook dequant into).

Two drivers: :func:`stage_findings` runs inside
``jaxpr_check.run_contract_checks`` over every *recorded* serve stage;
:func:`run_numerics_checks` traces a curated set of real decode entry
points at bf16 payload dtypes (dense fallback, jnp + fused-emulation zone
walks, the paged Pallas kernel in both ``double_buffer`` flavors, the
``return_parts``/distributed LSE-merge path) so the contract is exercised
at the dtypes production serves, not just the f32 tiny setup.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.analysis.findings import Finding

# ------------------------------------------------------------ primitive sets
# softmax/LSE-chain transcendentals (RL401). rsqrt/erf are norm/gelu
# territory with their own error budget — not part of the softmax contract.
_TRANSCENDENTAL = {"exp", "exp2", "log", "log2", "log1p", "expm1",
                   "logistic", "tanh"}
# call-like primitives inlined into the caller's graph (one flat unit)
_INLINE = {"pjit", "closed_call", "core_call", "named_call", "remat",
           "remat2", "checkpoint", "custom_jvp_call", "custom_vjp_call",
           "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr"}
# shape-only ops a value flows through unchanged (provenance walks)
_PASSTHROUGH = {"reshape", "transpose", "broadcast_in_dim", "squeeze",
                "expand_dims", "slice", "dynamic_slice", "rev", "gather",
                "concatenate", "pad", "copy", "select_n", "convert_weak",
                "stop_gradient"}
# storage writes: a narrowing feeding one of these at matching dtype is the
# sanctioned store-write path (dense_cache_append, kernel o_ref/scratch)
_STORE_WRITE = {"scatter", "scatter-add", "dynamic_update_slice", "swap",
                "masked_swap", "addupdate"}
# cross-shard collectives on the LSE-merge path (RL405)
_COLLECTIVE = {"psum", "pmax", "pmin", "all_gather", "all_to_all",
               "ppermute", "reduce_scatter"}

# RL402(b): a widening convert at least this large feeding a dot is the
# hoisted-cast hazard (XLA converts the whole store every step). Per-tile /
# query-sized upcasts stay far below it; whole payload stores sit far above.
RL402_MIN_BYTES = 4 << 20


# ------------------------------------------------------------- the contract
@dataclass(frozen=True)
class NumericsContract:
    """Per-stage numerics contract (the ``numerics=`` SERVE_STAGES field).

    softmax: dtype floor for exp/log/LSE chains            (RL401)
    accum:   dtype floor for dot_general accumulation      (RL402)
    narrow:  "output-only" — only the stage output and same-dtype storage
             writes may consume a narrowed value (RL403/RL404); "free"
             disables the narrowing rules for the stage.
    """
    softmax: str = "float32"
    accum: str = "float32"
    narrow: str = "output-only"

    @classmethod
    def from_spec(cls, spec: Optional[Dict[str, str]]) -> "NumericsContract":
        return cls() if spec is None else cls(**spec)


def _floor_bytes(name: str) -> int:
    return np.dtype(name).itemsize


# --------------------------------------------------------------- graph build
def _is_float(dtype) -> bool:
    # np.issubdtype does not know the ml_dtypes extension floats (bf16,
    # fp8) — exactly the dtypes this checker exists for; jax's lattice does.
    import jax.numpy as jnp
    from jax import dtypes as jdt
    return jdt.issubdtype(dtype, jnp.floating)


def _aval_of(atom):
    aval = getattr(atom, "aval", None)
    # pallas kernel refs: the value of interest is the carried array
    return getattr(aval, "inner_aval", aval)


def _nbytes(aval) -> int:
    try:
        return int(np.prod(aval.shape, dtype=np.int64)) * aval.dtype.itemsize
    except Exception:
        return 0


def _site(eqn, default_path: str) -> Tuple[str, int]:
    """Repo-relative (path, line) of the user frame that traced ``eqn``."""
    try:
        from jax._src import source_info_util as siu
        fr = siu.user_frame(eqn.source_info.traceback)
        if fr is not None:
            path = fr.file_name.replace("\\", "/")
            i = path.rfind("/src/repro/")
            if i >= 0:
                path = path[i + 1:]
            return path, fr.start_line
    except Exception:
        pass
    return default_path, 0


class _Op:
    __slots__ = ("prim", "ins", "outs", "eqn")

    def __init__(self, prim, ins, outs, eqn):
        self.prim, self.ins, self.outs, self.eqn = prim, ins, outs, eqn


class _Graph:
    """One analysis unit: a flattened jaxpr body as an SSA dataflow graph."""

    def __init__(self, name: str, in_kernel: bool):
        self.name = name
        self.in_kernel = in_kernel
        self.ops: List[_Op] = []
        self.aval: Dict[int, Any] = {}          # key -> ShapedArray
        self.producer: Dict[int, _Op] = {}      # key -> defining op
        self.consumers: Dict[int, List[_Op]] = {}
        self.outvars: set = set()               # unit-output keys
        self._n = 0

    def fresh(self, aval) -> int:
        self._n += 1
        self.aval[self._n] = aval
        return self._n

    def add(self, prim, ins, outs, eqn):
        op = _Op(prim, ins, outs, eqn)
        self.ops.append(op)
        for k in ins:
            self.consumers.setdefault(k, []).append(op)
        for k in outs:
            self.producer[k] = op
        return op


def _subjaxprs(params):
    """Every Jaxpr reachable from an eqn's params (mirrors jaxpr_check)."""
    import jax.extend.core as jc
    for v in params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for x in vs:
            if isinstance(x, jc.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jc.Jaxpr):
                yield x


def _inline_target(eqn):
    """The single body of a call-like primitive (ClosedJaxpr or Jaxpr)."""
    for key in ("jaxpr", "call_jaxpr"):
        sub = eqn.params.get(key)
        if sub is not None:
            return sub
    return None


def _build_units(closed, name: str) -> List[_Graph]:
    """Flatten a ClosedJaxpr into analysis units: the top-level graph (with
    all call-like prims inlined) plus one unit per control-flow/kernel body,
    recursively. Pallas kernel bodies are marked ``in_kernel``."""
    import jax.extend.core as jc
    units: List[_Graph] = []
    pending: List[Tuple[Any, str, bool]] = [(closed.jaxpr, name, False)]
    while pending:
        jaxpr, uname, in_kernel = pending.pop(0)
        g = _Graph(uname, in_kernel)
        env: Dict[Any, int] = {}

        def key_of(atom, g=g, env=env):
            if isinstance(atom, jc.Literal):
                return g.fresh(_aval_of(atom))
            if atom not in env:
                env[atom] = g.fresh(_aval_of(atom))
            return env[atom]

        def emit(jx):
            for eqn in jx.eqns:
                prim = eqn.primitive.name
                sub = _inline_target(eqn) if prim in _INLINE else None
                if sub is not None:
                    sj = sub.jaxpr if isinstance(sub, jc.ClosedJaxpr) else sub
                    for cv in sj.constvars:
                        env[cv] = g.fresh(_aval_of(cv))
                    for iv, outer in zip(sj.invars, eqn.invars):
                        env[iv] = key_of(outer)
                    emit(sj)
                    for ov, outer in zip(sj.outvars, eqn.outvars):
                        env[outer] = key_of(ov)
                    continue
                ins = [key_of(a) for a in eqn.invars]
                outs = [key_of(v) for v in eqn.outvars]
                g.add(prim, ins, outs, eqn)
                for body in _subjaxprs(eqn.params):
                    pending.append(
                        (body, f"{uname}:{prim}",
                         in_kernel or prim == "pallas_call"))

        for v in list(jaxpr.invars) + list(jaxpr.constvars):
            env[v] = g.fresh(_aval_of(v))
        emit(jaxpr)
        g.outvars = {key_of(v) for v in jaxpr.outvars}
        units.append(g)
    return units


# ------------------------------------------------------------ rule machinery
def _walk_forward(g: _Graph, key: int):
    """Terminal (op, via_outvar) consumers of ``key`` through passthroughs."""
    seen, stack, terms, hits_out = set(), [key], [], False
    while stack:
        k = stack.pop()
        if k in seen:
            continue
        seen.add(k)
        if k in g.outvars:
            hits_out = True
        for op in g.consumers.get(k, ()):
            if op.prim in _PASSTHROUGH:
                stack.extend(op.outs)
            else:
                terms.append(op)
    return terms, hits_out


def _walk_back(g: _Graph, key: int) -> Optional[_Op]:
    """Producer of ``key`` skipping passthrough ops."""
    while True:
        op = g.producer.get(key)
        if op is None:
            return None
        if op.prim in _PASSTHROUGH and op.ins:
            key = op.ins[0]
            continue
        return op


def _store_dtype(op: _Op):
    """Destination dtype of a storage-write op (ref inner or output aval)."""
    av = _aval_of(op.eqn.invars[0]) if op.eqn.invars else None
    if av is not None and getattr(av, "dtype", None) is not None:
        return av.dtype
    return None


def _check_unit(g: _Graph, contract: NumericsContract, path: str,
                findings: List[Finding],
                inventory: Optional[List[Finding]]) -> None:
    soft_floor = _floor_bytes(contract.softmax)
    accum_floor = _floor_bytes(contract.accum)
    narrow_rules = contract.narrow == "output-only"
    for op in g.ops:
        eqn = op.eqn
        # ---- RL401: transcendental on a sub-floor float operand
        if op.prim in _TRANSCENDENTAL:
            for k in op.ins:
                av = g.aval.get(k)
                if (av is not None and _is_float(av.dtype)
                        and av.dtype.itemsize < soft_floor):
                    p, ln = _site(eqn, path)
                    findings.append(Finding(
                        "RL401", p, ln, g.name,
                        f"`{op.prim}` computes on {av.dtype.name} — the "
                        f"softmax/LSE chain must run in {contract.softmax} "
                        f"(upcast the operand row, not the store)"))
        # ---- RL402(a): dot accumulating below the floor
        elif op.prim == "dot_general":
            in_dts = [g.aval[k].dtype for k in op.ins
                      if k in g.aval and _is_float(g.aval[k].dtype)]
            out_av = g.aval.get(op.outs[0]) if op.outs else None
            if (in_dts and out_av is not None and _is_float(out_av.dtype)
                    and any(d.itemsize < accum_floor for d in in_dts)
                    and out_av.dtype.itemsize < accum_floor):
                p, ln = _site(eqn, path)
                findings.append(Finding(
                    "RL402", p, ln, g.name,
                    f"dot/einsum with {'/'.join(d.name for d in in_dts)} "
                    f"operands accumulates in {out_av.dtype.name} — pass "
                    f"preferred_element_type=jnp.{contract.accum}"))
        # ---- RL405: collective over sub-f32 partials
        elif op.prim in _COLLECTIVE:
            for k in op.ins:
                av = g.aval.get(k)
                if (av is not None and _is_float(av.dtype)
                        and av.dtype.itemsize < 4):
                    p, ln = _site(eqn, path)
                    findings.append(Finding(
                        "RL405", p, ln, g.name,
                        f"collective `{op.prim}` over {av.dtype.name} "
                        f"partials — the LSE merge rounds once per shard; "
                        f"keep (num, den, m) f32 until the final downcast"))
        elif op.prim != "convert_element_type":
            continue
        if op.prim != "convert_element_type":
            continue
        # ---------------- convert analysis (RL402b / RL403 / RL404 / RL406)
        src_av = g.aval.get(op.ins[0]) if op.ins else None
        dst_av = g.aval.get(op.outs[0]) if op.outs else None
        if (src_av is None or dst_av is None
                or not _is_float(src_av.dtype) or not _is_float(dst_av.dtype)
                or src_av.dtype == dst_av.dtype):
            continue
        widening = dst_av.dtype.itemsize > src_av.dtype.itemsize
        p, ln = _site(eqn, path)
        if g.in_kernel and inventory is not None:
            role = ("widen-to-accum (dequant hook)" if widening
                    else "output downcast")
            shape = "x".join(map(str, src_av.shape))
            inventory.append(Finding(
                "RL406", p, ln, g.name,
                f"VMEM cast site: {src_av.dtype.name}[{shape}] -> "
                f"{dst_av.dtype.name} — {role}", severity="advice"))
        if widening:
            # ---- RL403: narrow->widen round trip (two roundings)
            back = _walk_back(g, op.ins[0])
            if (back is not None and back.prim == "convert_element_type"
                    and back.ins):
                bav = g.aval.get(back.ins[0])
                if (bav is not None and _is_float(bav.dtype)
                        and bav.dtype.itemsize >= dst_av.dtype.itemsize
                        and narrow_rules):
                    findings.append(Finding(
                        "RL403", p, ln, g.name,
                        f"double rounding: value round-tripped "
                        f"{bav.dtype.name} -> {src_av.dtype.name} -> "
                        f"{dst_av.dtype.name} before accumulation"))
            # ---- RL402(b): whole-store upcast hoisted before a dot
            if (not g.in_kernel and _nbytes(src_av) >= RL402_MIN_BYTES):
                terms, _ = _walk_forward(g, op.outs[0])
                if any(t.prim == "dot_general" for t in terms):
                    findings.append(Finding(
                        "RL402", p, ln, g.name,
                        f"explicit astype({dst_av.dtype.name}) on a "
                        f"{_nbytes(src_av) >> 20} MiB {src_av.dtype.name} "
                        f"operand feeding a dot — XLA hoists the convert "
                        f"through the gather and rewrites the WHOLE store "
                        f"(2x bytes); keep storage dtype and pass "
                        f"preferred_element_type instead"))
        elif narrow_rules:
            # ---- RL404: narrowing must end at the output / a store write /
            # an f32-accumulating dot / another convert (RL403's business)
            terms, hits_out = _walk_forward(g, op.outs[0])
            bad = []
            for t in terms:
                if t.prim == "convert_element_type":
                    continue
                if t.prim in _STORE_WRITE:
                    sd = _store_dtype(t)
                    if sd is None or sd == dst_av.dtype:
                        continue
                if t.prim == "dot_general":
                    oav = g.aval.get(t.outs[0]) if t.outs else None
                    if (oav is not None
                            and oav.dtype.itemsize >= accum_floor):
                        continue
                bad.append(t.prim)
            if bad:
                findings.append(Finding(
                    "RL404", p, ln, g.name,
                    f"unsanctioned downcast {src_av.dtype.name} -> "
                    f"{dst_av.dtype.name} consumed by "
                    f"`{'`/`'.join(sorted(set(bad)))}` — only the stage "
                    f"output astype(q.dtype), same-dtype storage writes and "
                    f"f32-accumulating dots may consume a narrowed value"))
            del hits_out  # output-feeding narrows are sanctioned by absence


# ------------------------------------------------------------------ drivers
def check_closed_jaxpr(closed, *, name: str, path: str,
                       contract: Optional[NumericsContract] = None,
                       inventory: Optional[List[Finding]] = None
                       ) -> List[Finding]:
    """Run RL401-RL406 over one traced ClosedJaxpr."""
    contract = contract or NumericsContract()
    findings: List[Finding] = []
    for unit in _build_units(closed, name):
        _check_unit(unit, contract, path, findings, inventory)
    return findings


def _trace(fn, avals):
    import jax
    return jax.make_jaxpr(fn)(*avals)


def numerics_findings(fn, avals: Sequence, name: str, *, path: str,
                      contract: Optional[Dict[str, str]] = None,
                      inventory: Optional[List[Finding]] = None
                      ) -> List[Finding]:
    """Trace ``fn`` at ``avals`` and check the numerics contract."""
    try:
        closed = _trace(fn, avals)
    except Exception as e:  # a target that stops tracing breaks the gate
        return [Finding("RL401", path, 0, name,
                        f"target could not be traced for the numerics "
                        f"pass: {e!r}")]
    return check_closed_jaxpr(
        closed, name=name, path=path,
        contract=NumericsContract.from_spec(contract), inventory=inventory)


def stage_findings(fn, avals: Sequence, name: str, spec: Dict[str, str],
                   path: str) -> List[Finding]:
    """The per-recorded-stage hook ``jaxpr_check.run_contract_checks``
    calls for every SERVE_STAGES entry that declares ``numerics=``. The
    kernel cast inventory is NOT collected here (it belongs to the curated
    kernel traces in :func:`run_numerics_checks`)."""
    return numerics_findings(fn, avals, name, path=path, contract=spec,
                             inventory=None)


def parts_findings(fn, avals: Sequence, name: str, *, path: str
                   ) -> List[Finding]:
    """RL405 boundary check: the (num, den, m) LSE-merge partials a
    ``return_parts`` trace yields must all be f32."""
    try:
        closed = _trace(fn, avals)
    except Exception as e:
        return [Finding("RL405", path, 0, name,
                        f"parts target could not be traced: {e!r}")]
    findings = []
    labels = ("num", "den", "m")
    for label, v in zip(labels, closed.jaxpr.outvars):
        av = _aval_of(v)
        if (av is not None and _is_float(av.dtype)
                and av.dtype.itemsize < 4):
            findings.append(Finding(
                "RL405", path, 0, name,
                f"LSE-merge partial `{label}` leaves the stage as "
                f"{av.dtype.name} — partial accumulators must stay f32 "
                f"until the cross-shard merge's single downcast"))
    return findings


# --------------------------------------------------- the curated repo gate
_ATTN_PATH = "src/repro/core/attention.py"
_OPS_PATH = "src/repro/kernels/wave_attention/ops.py"
_DIST_PATH = "src/repro/core/distributed.py"


def _sds(tree):
    import jax
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype), tree)


def _bf16_wave_setup():
    """A real (tiny) wave-index build whose payload fields are recast to
    bf16 — shapes come from ``prefill_build`` so the trace geometry always
    matches what the decode entry points expect."""
    import jax.numpy as jnp
    from repro.configs.base import RetroConfig
    from repro.core.wave_index import prefill_build, max_clusters
    from repro.core.zones import plan_zones

    retro = RetroConfig(avg_cluster=64, cluster_cap=256,
                        prefill_segment=1024, update_segment=256,
                        sink=16, local=256, retrieval_frac=0.1,
                        estimation_frac=0.3, kmeans_iters=1)
    B, Hkv, hd, n = 2, 2, 64, 2048
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.standard_normal((B, n, Hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, n, Hkv, hd)), jnp.float32)
    M = max_clusters(n, retro)
    state = prefill_build(k, v, retro, M)
    bf16 = {"k_store", "v_store", "sink_k", "sink_v", "local_k", "local_v"}
    state = state._replace(**{
        f: getattr(state, f).astype(jnp.bfloat16) for f in bf16})
    plan = plan_zones(n, retro)
    q = jnp.zeros((B, 2 * Hkv, hd), jnp.bfloat16)
    return q, state, retro, plan


def _pallas_avals(double_buffer: bool):
    """ops.paged_wave_attention at bf16 stores, emulate=False — the trace
    contains the real ``pallas_call`` whose kernel body retronum inlines."""
    import functools
    import jax
    import jax.numpy as jnp
    from repro.kernels.wave_attention import ops

    B, H, G, hd, M, cap, r, E, Lb, S = 2, 2, 2, 64, 16, 128, 4, 128, 512, 16
    sd, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    a = jax.ShapeDtypeStruct
    avals = (a((B, H, G, hd), sd),                     # qg
             a((B, H, S, hd), sd), a((B, H, S, hd), sd),    # sink
             a((B, H, Lb, hd), sd), a((B, H, Lb, hd), sd),  # local
             a((B, H, Lb), i32),                            # local_pos
             a((B, H, M, cap, hd), sd), a((B, H, M, cap, hd), sd),
             a((B, H, M, cap), i32),                        # stores
             a((B, H, r), i32), a((B, H, r), i32),          # idx_r, live
             a((B, H, 2), i32),                             # rowb
             a((B, H, G, E), f32), a((B, H, G, E), f32),    # est_logit, cs
             a((B, H, E, hd), f32))                         # vs
    fn = functools.partial(ops.paged_wave_attention, softcap=None,
                           block_l=Lb, interpret=False, emulate=False,
                           double_buffer=double_buffer)
    return fn, avals


def run_numerics_checks(verbose=None) -> List[Finding]:
    """The full retronum repo gate: every curated decode entry point traced
    at bf16 payload dtypes and checked against the default f32 contract.
    Returns errors plus the RL406 cast-site inventory (advice)."""
    import functools
    import jax
    import jax.numpy as jnp
    from repro.core import attention as attn
    from repro.core.distributed import distributed_wave_attention

    log = verbose or (lambda *_: None)
    findings: List[Finding] = []
    inventory: List[Finding] = []

    # 1. dense-cache fallback decode + append, bf16 cache (the dense path
    # is full attention — a whole-cache upcast here is the RL402(b) catch)
    log("retronum: tracing dense-cache fallback (bf16 cache)")
    B, Hkv, S, hd = 2, 4, 8192, 128
    a = jax.ShapeDtypeStruct
    cache = attn.DenseCache(a((B, Hkv, S, hd), jnp.bfloat16),
                            a((B, Hkv, S, hd), jnp.bfloat16),
                            a((B,), jnp.int32))
    q = a((B, 2 * Hkv, hd), jnp.bfloat16)
    findings += numerics_findings(
        attn.full_attention_decode, (q, cache), "full_attention_decode",
        path=_ATTN_PATH)
    findings += numerics_findings(
        attn.dense_cache_append,
        (cache, a((B, Hkv, hd), jnp.float32), a((B, Hkv, hd), jnp.float32)),
        "dense_cache_append", path=_ATTN_PATH)

    # 2-4. the wave zone walk at bf16 stores: reference jnp path, the
    # fused path (resolves to the ref emulation on CPU — same zone walk the
    # serve hot path runs), and the return_parts LSE-merge boundary
    log("retronum: tracing wave decode (jnp + fused emulation, bf16 store)")
    qw, state, retro, plan = _bf16_wave_setup()
    st_avals = _sds(state)
    for impl in ("jnp", "fused"):
        fn = functools.partial(attn.wave_attention_decode, retro=retro,
                               plan=plan, impl=impl)
        findings += numerics_findings(
            fn, (_sds(qw), st_avals), f"wave_attention_decode[{impl}]",
            path=_ATTN_PATH)
    parts = functools.partial(
        attn.wave_attention_decode, retro=retro, plan=plan, impl="jnp",
        return_parts=True)
    findings += parts_findings(
        lambda q, s: parts(q, s)[:3], (_sds(qw), st_avals),
        "wave_attention_decode[parts]", path=_ATTN_PATH)

    # 5. the paged Pallas kernel, both cluster-walk flavors: in-kernel
    # precision rules + the RL406 VMEM cast-site inventory
    for db in (True, False):
        log(f"retronum: tracing paged kernel (double_buffer={db})")
        fn, avals = _pallas_avals(db)
        findings += numerics_findings(
            fn, avals, f"paged_wave_attention[db={int(db)}]",
            path=_OPS_PATH, inventory=inventory)

    # 6. the distributed LSE merge (shard_map body: psum/pmax collectives)
    log("retronum: tracing distributed LSE merge (1-device mesh)")
    try:
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("model",))
        fn = functools.partial(distributed_wave_attention, retro=retro,
                               plan=plan, mesh=mesh)
        findings += numerics_findings(
            fn, (_sds(qw.astype(jnp.float32)), st_avals),
            "distributed_wave_attention", path=_DIST_PATH)
    except Exception as e:
        findings.append(Finding(
            "RL405", _DIST_PATH, 0, "distributed_wave_attention",
            f"LSE-merge target could not be traced: {e!r}"))

    # de-duplicate inventory across the two kernel flavors (shared fold
    # helpers trace the same source site twice)
    seen, uniq = set(), []
    for f in inventory:
        key = (f.path, f.line, f.message)
        if key not in seen:
            seen.add(key)
            uniq.append(f)
    log(f"retronum: {len(uniq)} certified VMEM cast sites, "
        f"{len(findings)} findings")
    return findings + uniq


def kernel_cast_inventory() -> List[Finding]:
    """Just the RL406 advice inventory (used by the selftest)."""
    return [f for f in run_numerics_checks() if f.rule == "RL406"]
