"""The timed path broken underneath, and the control put in its place:
engine hooks (``run.run_cell``'s ``engine_hook``) that the CPU tests and
``bench/readings.py`` use to show that ``correct`` comes out false. None
of them is used by a benchmark run.

- ``token_altered``: greedy sampling returns the id after the best one;
- ``state_unchanged``: the decode step computes its logits but returns
  the wave-index state it was given, so no served token enters it;
- ``kernel_zeroed``: the paged wave-attention kernel's output is zeros;
- ``control(cfgd, seed, quant)``: the program serves as usual, then each
  request's served tokens are replaced by as many tokens decoded greedily
  from its prompt by the plain reference computed one precision step
  lower (``reference.control_decode``: a forward pass per token, so toy
  sizes only; at a cell's size ``readings.py`` reads the control at the
  program's positions instead).

``kernel_zeroed`` replaces a module attribute for the whole process;
``restore()`` puts it back.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def token_altered(engine):
    vocab = engine.cfg.vocab
    engine._argmax = jax.jit(
        lambda lg: ((jnp.argmax(lg, -1) + 1) % vocab).astype(jnp.int32))


def state_unchanged(engine):
    from repro.core.zones import plan_zones
    from repro.models import model as M
    real = engine._decode_fns

    def fns(batch, max_ctx):
        _, flush = real(batch, max_ctx)
        cfg, gh = engine.cfg, engine.gen_headroom
        plan = plan_zones(max_ctx, cfg.retro, gh)

        @jax.jit
        def decode(params, state, token, active):
            logits, _ = M.apply_decode(
                params, cfg, state, token, runtime=engine.runtime, plan=plan,
                seq_len=max_ctx, gen_headroom=gh, active=active,
                attn_impl=engine.attn_impl)
            return logits, state
        return decode, flush

    engine._decode_fns = fns


_REAL_KERNEL = {}


def kernel_zeroed(engine):
    from repro.kernels.wave_attention import ops
    _REAL_KERNEL.setdefault("paged_wave_attention", ops.paged_wave_attention)
    ops.paged_wave_attention = (
        lambda qg, *args, **kw: jnp.zeros(qg.shape, jnp.float32))


def restore():
    from repro.kernels.wave_attention import ops
    if "paged_wave_attention" in _REAL_KERNEL:
        ops.paged_wave_attention = _REAL_KERNEL.pop("paged_wave_attention")


def control(cfgd, seed: int, quant: str):
    """Hook that puts the ``quant`` control in the program's place."""
    from bench import reference

    def hook(engine):
        real = engine.serve

        def serve(reqs, batch_size, **kw):
            out = real(reqs, batch_size, **kw)
            for r in reqs:
                if r.out_tokens:
                    patches = (r.extra or {}).get("patch_embeds")
                    r.out_tokens = [int(t) for t in reference.control_decode(
                        cfgd, seed, r.prompt, len(r.out_tokens), patches,
                        quant)]
            return out
        engine.serve = serve
    return hook


FAULTS = {"token-altered": token_altered, "state-unchanged": state_unchanged,
          "kernel-zeroed": kernel_zeroed}
