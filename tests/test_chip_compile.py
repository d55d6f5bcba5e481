"""Compile the decode path's kernels for a described TPU v5e, without a chip.

The Pallas interpreter checks none of Mosaic's layout rules, so every
kernel on the served decode path is compiled here for a ``v5e:2x2``
topology that is described, not attached, at the widths of real configs;
each test asserts that the Mosaic kernel (``tpu_custom_call``) is in the
compiled program. The topology is described inside a module fixture:
only the one worker that runs this file loads the TPU compiler, and the
file's tests skip where it cannot be described.
"""
import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.core.zones import plan_zones

# paper-scale decode geometry: a 32k context under the default RetroConfig
CONTEXT = 32_768
GEN_HEADROOM = 4096
B = 2


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # programs compiled for a described chip are written to the persistent
    # cache but cannot be read back without one: keep the cache off here
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _paged_args(cfg, spec):
    """Shapes of ``ops.paged_wave_attention``'s operands at ``cfg``'s widths."""
    retro = cfg.retro
    plan = plan_zones(CONTEXT, retro, GEN_HEADROOM)
    H, hd = cfg.n_kv_heads, cfg.head_dim
    G = cfg.n_heads // H
    M, r, cap, E = plan.m_max, plan.r, retro.cluster_cap, plan.r + plan.e
    lb = retro.local + retro.update_segment
    st, f32, i32 = jnp.dtype(cfg.dtype), jnp.float32, jnp.int32
    return (spec((B, H, G, hd), st), spec((B, H, retro.sink, hd), st),
            spec((B, H, retro.sink, hd), st), spec((B, H, lb, hd), st),
            spec((B, H, lb, hd), st), spec((B, H, lb), i32),
            spec((B, H, M, cap, hd), st), spec((B, H, M, cap, hd), st),
            spec((B, H, M, cap), i32), spec((B, H, r), i32),
            spec((B, H, r), i32), spec((B, H, 2), i32),
            spec((B, H, G, E), f32), spec((B, H, G, E), f32),
            spec((B, H, E, hd), f32))


@pytest.mark.parametrize("double_buffer", [False, True],
                         ids=["blockspec-walk", "double-buffered-dma"])
@pytest.mark.parametrize("arch", ["gemma2_2b", "minitron_8b"])
def test_paged_kernel_compiles_for_v5e(one_chip, arch, double_buffer):
    """Both cluster-walk flavors at hd 256 (gemma2-2b: G 2, softcap) and
    hd 128 (minitron-8b: G 4), bf16 stores, cap 32."""
    from repro.kernels.wave_attention import ops
    cfg = get_config(arch)
    fn = jax.jit(partial(ops.paged_wave_attention, softcap=cfg.attn.softcap,
                         double_buffer=double_buffer, interpret=False))
    hlo = fn.lower(*_paged_args(cfg, _spec(one_chip))).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_fused_decode_attention_compiles_for_v5e(one_chip, monkeypatch):
    """The whole fused decode attention of one gemma2-2b layer — ranking,
    estimation zone, position gather and the kernel — as the chip runs it.
    ``ops.on_cpu`` sees this process's CPU, so the test steers it."""
    from repro.core.attention import wave_attention_decode
    from repro.core.wave_index import init_wave_state
    from repro.kernels.wave_attention import ops
    monkeypatch.setattr(ops, "on_cpu", lambda: False)
    cfg = get_config("gemma2_2b")
    plan = plan_zones(CONTEXT, cfg.retro, GEN_HEADROOM)
    state = jax.eval_shape(lambda: init_wave_state(
        B, cfg.n_kv_heads, cfg.head_dim, plan.m_max, cfg.retro,
        jnp.dtype(cfg.dtype)))
    state = jax.tree.map(lambda a: _spec(one_chip)(a.shape, a.dtype), state)
    q = _spec(one_chip)((B, cfg.n_heads, cfg.head_dim), jnp.dtype(cfg.dtype))
    fn = jax.jit(lambda q, st: wave_attention_decode(
        q, st, cfg.retro, plan, window=jnp.float32(cfg.attn.sliding_window),
        softcap=cfg.attn.softcap, impl="fused").out)
    assert "tpu_custom_call" in fn.lower(q, state).compile().as_text()


def test_gathered_buffer_kernel_compiles_for_v5e(one_chip):
    """The legacy gathered-buffer kernel (``impl="pallas"``) at gemma2-2b
    widths over a steady zone + 37 retrieved clusters."""
    from repro.kernels.wave_attention import ops
    cfg = get_config("gemma2_2b")
    spec = _spec(one_chip)
    H, hd, G = cfg.n_kv_heads, cfg.head_dim, cfg.n_heads // cfg.n_kv_heads
    T, E = 4 + 1088 + 37 * 32, 512
    st, f32 = jnp.dtype(cfg.dtype), jnp.float32
    fn = jax.jit(partial(ops.wave_attention_merge, softcap=cfg.attn.softcap,
                         interpret=False))
    hlo = fn.lower(spec((B, H, G, hd), st), spec((B, H, T, hd), st),
                   spec((B, H, T, hd), st), spec((B, H, T), jnp.bool_),
                   spec((B, H, G, E), f32), spec((B, H, G, E), f32),
                   spec((B, H, E, hd), f32)).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_dense_cache_append_in_place_for_v5e(one_chip):
    """The full-attention runtime's masked append updates the donated cache
    in place on the chip: the whole cache aliases the output, no temp holds
    a copy, and masking adds no cache-sized traffic to the unmasked
    append."""
    from repro.core.attention import DenseCache, dense_cache_append
    spec = _spec(one_chip)
    H, S_max, hd = 2, 4096, 64
    cache = DenseCache(spec((B, H, S_max, hd), jnp.float32),
                       spec((B, H, S_max, hd), jnp.float32),
                       spec((B,), jnp.int32))
    k_new = spec((B, H, hd), jnp.float32)
    cache_bytes = 2 * B * H * S_max * hd * 4

    def compiled(fn, *args):
        return jax.jit(fn, donate_argnums=(0,)).lower(cache, *args).compile()

    def bytes_accessed(c):
        ca = c.cost_analysis()
        return float((ca[0] if isinstance(ca, list) else ca)["bytes accessed"])

    plain = compiled(lambda c, k: dense_cache_append(c, k, k), k_new)
    masked = compiled(lambda c, k, a: dense_cache_append(c, k, k, active=a),
                      k_new, spec((B,), jnp.bool_))
    mem = masked.memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes, mem
    assert mem.temp_size_in_bytes < 0.1 * cache_bytes, mem
    assert bytes_accessed(masked) < bytes_accessed(plain) + 0.1 * cache_bytes
