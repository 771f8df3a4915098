"""Compiles for a described TPU v5e, no chip attached.

Interpret mode on the CPU does not check Mosaic's tiling rules, so every
kernel test can pass while the chip refuses the kernel.  These tests hand
the serving path's kernels and the full-width decode step to the TPU
compiler at qwen3-1.7b widths, and the decode kernel at qwen2.5-14b's
too (one pipeline stage's slots and cache), and Trinity-Mini's windowed
decode kernel and grouped expert FFN, alone and in Trinity's decode step
and prefill; a refusal fails here, at no chip time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and test workers that
collected different tests would run none.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_arch
from repro.kernels import ops
from repro.models import moe
from repro.models import transformer as T
from repro.models.attention import decode_attn_impl

CFG = get_arch("qwen3-1.7b")
B = 4
# (slots, Hq, Hkv, d) of each decode the benchmark serves
QWEN3 = (B, CFG.n_heads, CFG.n_kv_heads, CFG.resolved_head_dim)
PP4 = (4, 40, 8, 128)           # qwen2.5-14b, G = 5


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


@pytest.mark.parametrize("C,widths", [
    *(pytest.param(C, QWEN3, id=str(C)) for C in (96, 256, 2048)),
    pytest.param(3200, PP4, id="3200-qwen2.5-14b"),   # ragged: 3200 % 512
])
@pytest.mark.parametrize("form", ["plain", "merged", "slot_masked"])
def test_decode_kernel_compiles_for_v5e(one_chip, form, C, widths):
    B, Hq, Hkv, d = widths
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
    # the trace's reduction finds the kernel by this name, and the cache
    # reaches it unpadded (a ragged C is a partial last block)
    assert re.search(r"%decode_attention\.\d+ = .*custom-call\(", hlo)
    assert "pad(" not in hlo


def test_windowed_decode_kernel_compiles_for_v5e(one_chip):
    """Trinity-Mini's decode: G = 8, a 4096-row cache, each slot's lower
    bound prefetched beside its length."""
    B, C, Hq, Hkv, d = 8, 4096, 32, 4, 128
    bf16 = jnp.bfloat16
    hlo = _compiled_text(
        lambda q, k, v, n, kn, vn, lo: ops.decode_attention(
            q, k, v, n, k_new=kn, v_new=vn, lo=lo, interpret=False),
        _struct((B, 1, Hq, d), bf16, one_chip),
        _struct((B, C, Hkv, d), bf16, one_chip),
        _struct((B, C, Hkv, d), bf16, one_chip),
        _struct((B,), jnp.int32, one_chip),
        _struct((B, 1, Hkv, d), bf16, one_chip),
        _struct((B, 1, Hkv, d), bf16, one_chip),
        _struct((B,), jnp.int32, one_chip))
    assert re.search(r"%decode_attention\.\d+ = .*custom-call\(", hlo)


@pytest.mark.parametrize("M", [64, 32768])    # a decode step's, a prefill's
def test_moe_gmm_compiles_for_v5e(one_chip, M):
    """The grouped expert FFN at Trinity-Mini's widths (16 held experts of
    1024 over d 2048), reading one layer's experts in place from the
    stacks of 30 MoE layers, under the name the trace reduction finds."""
    L, G, D, F = 30, 16, 2048, 1024
    bf16 = jnp.bfloat16
    hlo = _compiled_text(
        lambda x, g, u, w, n, l: ops.moe_gmm(x, g, u, w, n, l,
                                             interpret=False),
        _struct((M, D), bf16, one_chip),
        _struct((L, G, D, F), bf16, one_chip),
        _struct((L, G, D, F), bf16, one_chip),
        _struct((L, G, F, D), bf16, one_chip),
        _struct((G,), jnp.int32, one_chip), _struct((), jnp.int32, one_chip))
    assert re.search(r"%moe_gmm\.\d+ = .*custom-call\(", hlo)
    assert not _ONE_LAYERS_EXPERTS.search(hlo)


# a buffer holding one layer's held experts (w_gate / w_up, w_down)
_ONE_LAYERS_EXPERTS = re.compile(r"bf16\[(1,)?16,(2048,1024|1024,2048)\]")


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_trinity_steps_read_the_stacked_experts_in_place(one_chip,
                                                        monkeypatch, step):
    """Trinity-Mini at one chip's 16 experts, 8 slots and a 4096-row
    cache: the serving decode step and a bucketed prefill (512 tokens a
    row) hand ``moe_gmm`` the whole (30, 16, ...) expert stacks, and no
    buffer of one layer's experts is made for it."""
    monkeypatch.setattr(ops, "_interpret", lambda override: False)
    monkeypatch.setattr(moe, "GMM_IMPL", ["pallas"])
    cfg = dataclasses.replace(get_arch("trinity-mini"), n_experts=16)
    B, C, S = 8, 4096, 512

    def placed(tree):
        return jax.tree.map(lambda a: _struct(a.shape, a.dtype, one_chip),
                            tree)

    params = placed(jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.PRNGKey(0))))
    if step == "decode":
        cache = jax.eval_shape(lambda: T.init_cache(cfg, B, C))
        cache["pos"] = jax.ShapeDtypeStruct((B,), jnp.int32)
        hlo = _compiled_text(
            lambda p, t, c, m: T.decode_step(
                cfg, p, {"tokens": t}, c, token_mask=m, return_stats=True),
            params, _struct((B,), jnp.int32, one_chip), placed(cache),
            _struct((B,), jnp.bool_, one_chip))
    else:
        hlo = _compiled_text(
            lambda p, t, li, m: T.forward(
                cfg, p, {"tokens": t}, mode="prefill", max_len=C,
                last_index=li, token_mask=m, return_stats=True),
            params, _struct((B, S), jnp.int32, one_chip),
            _struct((B,), jnp.int32, one_chip),
            _struct((B, S), jnp.bool_, one_chip))
    calls = [ln for ln in hlo.splitlines()
             if "tpu_custom_call" in ln and "moe_gmm/pallas_call" in ln]
    assert calls
    stacks = ["bf16[30,16,2048,1024]"] * 2 + ["bf16[30,16,1024,2048]"]
    for ln in calls:
        # the trace reduction finds the kernel by this name
        assert re.search(r"%moe_gmm\.\d+ = .*custom-call\(", ln), ln
        assert re.findall(r"bf16\[30,16,\d+,\d+\]", ln) == stacks, ln
    assert not _ONE_LAYERS_EXPERTS.search(hlo)


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
