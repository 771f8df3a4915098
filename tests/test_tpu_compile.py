"""Compiles for a described TPU v5e, no chip attached.

Interpret mode on the CPU does not check Mosaic's tiling rules, so every
kernel test can pass while the chip refuses the kernel.  These tests hand
the serving path's kernels and the full-width decode step to the TPU
compiler at qwen3-1.7b widths; a refusal fails here, at no chip time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and test workers that
collected different tests would run none.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_arch
from repro.kernels import ops
from repro.models import transformer as T
from repro.models.attention import decode_attn_impl

CFG = get_arch("qwen3-1.7b")
B = 4


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with JAX's persistent cache off: a TPU
    executable written to it could not be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()
    # traces made for the chip must not serve a later CPU call
    jax.clear_caches()


def _struct(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("C", [96, 256, 2048])
@pytest.mark.parametrize("form", ["plain", "merged", "slot_masked"])
def test_decode_kernel_compiles_for_v5e(one_chip, form, C):
    Hq, Hkv, d = CFG.n_heads, CFG.n_kv_heads, CFG.resolved_head_dim
    bf16 = jnp.bfloat16
    q = _struct((B, 1, Hq, d), bf16, one_chip)
    kv = _struct((B, C, Hkv, d), bf16, one_chip)
    lens = _struct((B,), jnp.int32, one_chip)
    new = _struct((B, 1, Hkv, d), bf16, one_chip)
    mask = _struct((B, C), jnp.bool_, one_chip)
    if form == "plain":
        hlo = _compiled_text(lambda q, k, v, n: ops.decode_attention(
            q, k, v, n, interpret=False), q, kv, kv, lens)
    elif form == "merged":
        hlo = _compiled_text(lambda q, k, v, n, kn, vn: ops.decode_attention(
            q, k, v, n, k_new=kn, v_new=vn, interpret=False),
            q, kv, kv, lens, new, new)
    else:
        hlo = _compiled_text(
            lambda q, k, v, n, kn, vn, m: ops.decode_attention(
                q, k, v, n, k_new=kn, v_new=vn, slot_mask=m,
                interpret=False),
            q, kv, kv, lens, new, new, mask)
    assert "tpu_custom_call" in hlo


def test_lora_merge_compiles_for_v5e(one_chip):
    L, D, r = CFG.n_layers, CFG.d_model, 4
    bf16 = jnp.bfloat16
    hlo = _compiled_text(
        lambda W, A, Bm: ops.lora_merge(W, A, Bm, 0.5, interpret=False),
        _struct((L, D, D), bf16, one_chip), _struct((L, D, r), bf16, one_chip),
        _struct((L, r, D), bf16, one_chip))
    assert "tpu_custom_call" in hlo


def test_full_width_decode_step_compiles_with_mosaic_kernel(one_chip,
                                                           monkeypatch):
    """The serving decode step at published width, decode attention on
    the Mosaic kernel (the CPU backend would pick interpret mode)."""
    monkeypatch.setattr(ops, "_interpret", lambda override: False)

    def placed(tree):
        return jax.tree.map(lambda a: _struct(a.shape, a.dtype, one_chip),
                            tree)

    params = placed(jax.eval_shape(
        lambda: T.init_params(CFG, jax.random.PRNGKey(0))))
    cache = jax.eval_shape(lambda: T.init_cache(CFG, B, 96))
    cache["pos"] = jax.ShapeDtypeStruct((B,), jnp.int32)
    cache = placed(cache)
    toks = _struct((B,), jnp.int32, one_chip)
    with decode_attn_impl("pallas"):
        compiled = jax.jit(lambda p, t, c: T.decode_step(
            CFG, p, {"tokens": t}, c)).lower(params, toks, cache).compile()
    assert "tpu_custom_call" in compiled.as_text()
    arg_bytes = compiled.memory_analysis().argument_size_in_bytes
    assert arg_bytes > 3e9          # the whole bf16 model is an argument
