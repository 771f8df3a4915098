"""Dropless MoE at one chip's expert share, and a stack of dense and MoE
layers with sliding and full attention (Trinity-Mini, reduced) served
through the bucketed batcher."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.configs.base import get_arch
from repro.models import moe
from repro.models import transformer as T
from repro.models.layers import _ACTS
from repro.serving.engine import (ContinuousBatcher, ServeRequest,
                                  ServingEngine, bucket_sizes,
                                  quantized_greedy)

KEY = jax.random.PRNGKey(15)


@pytest.fixture(scope="module")
def trinity():
    cfg = get_arch("trinity-mini").reduced()
    return cfg, T.init_params(cfg, KEY)


def test_trinity_stack_and_param_count():
    cfg = get_arch("trinity-mini")
    kinds = cfg.layer_kinds()
    assert kinds == ["attn"] * 2 + ["moe"] * 30
    assert [cfg.layer_window(i) for i in range(8)] == [2048] * 3 + [0] \
        + [2048] * 3 + [0]
    assert [cfg.layer_uses_rope(i) for i in range(4)] == [True] * 3 + [False]
    assert abs(cfg.param_count() / 1e9 - 26.1) / 26.1 < 0.02
    # one chip of an 8-way expert-parallel deployment holds 16 experts
    share = cfg.reduced(n_layers=32, n_dense_layers=2, d_model=2048,
                        n_heads=32, n_kv_heads=4, head_dim=128, d_ff=6144,
                        vocab_size=200192, n_experts=16, router_experts=128,
                        top_k=8, moe_d_ff=1024, dtype="bfloat16",
                        layer_windows=cfg.layer_windows)
    assert abs(share.param_count() / 1e9 - 4.98) < 0.02


def test_config_lists_become_tuples():
    cfg = get_arch("trinity-mini").reduced(layer_windows=[8, 0],
                                           layer_rope=[True, False])
    assert cfg.layer_windows == (8, 0) and cfg.layer_rope == (True, False)
    hash(cfg)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_pairs_and_hits_count_only_held_experts_of_routed_tokens(impl):
    """``pairs`` counts (token, expert) pairs of tokens in the mask whose
    expert is held here; ``experts_hit`` the held experts among them.
    Tokens outside the mask change nothing for the others."""
    cfg = get_arch("trinity-mini").reduced(
        n_experts=3, router_experts=12, expert_offset=6, top_k=4)
    p = T.init_params(cfg, KEY)["blocks"]["moe"]
    p = jax.tree.map(lambda a: a[0], p)["mlp"]
    p["expert_bias"] = jax.random.normal(KEY, p["expert_bias"].shape) * 0.1
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (3, 10, cfg.d_model))
    mask = np.ones((3, 10), bool)
    mask[1, 6:] = False
    mask[2] = False
    moe.GMM_IMPL[0] = impl
    try:
        y, _, counts = moe.moe_mlp(cfg, p, x, _ACTS[cfg.act],
                                   jnp.asarray(mask))
        y_all, _, _ = moe.moe_mlp(cfg, p, x, _ACTS[cfg.act])
    finally:
        moe.GMM_IMPL[0] = "auto"
    sel, _, _ = moe.route(cfg, p, x.reshape(30, -1))
    held = (np.asarray(sel) >= 6) & (np.asarray(sel) < 9)
    held &= mask.reshape(30, 1)
    assert int(counts["pairs"]) == held.sum()
    assert int(counts["experts_hit"]) == len(set(np.asarray(sel)[held]))
    np.testing.assert_allclose(np.asarray(y)[mask], np.asarray(y_all)[mask],
                               atol=1e-5, rtol=1e-5)


def test_trinity_serves_bucketed_and_batching_changes_no_token(trinity):
    """The stack of dense and MoE layers pads its prompts to buckets and
    shares each step among slots of different lengths, windows binding;
    every request's tokens equal its solo run's, and the traced spans
    carry the MoE and window counts."""
    cfg, params = trinity
    srv = ServingEngine(cfg, params, n_slots=3, max_len=64)
    srv.batcher.sampler = quantized_greedy
    assert srv.batcher._can_bucket
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 250, size=n) for n in (23, 5, 12, 30)]
    for i, pr in enumerate(prompts):
        srv.submit(ServeRequest(i, pr, max_new_tokens=9, arrival=0.0))
    tracing.enable()
    srv.run()
    tracing.disable()
    done = {r.rid: r.generated for r in srv.completed}
    for i, pr in enumerate(prompts):
        solo = ContinuousBatcher(cfg, params, n_slots=1, max_len=64,
                                 sampler=quantized_greedy)
        r = ServeRequest(i, pr, max_new_tokens=9)
        solo.admit(r)
        while solo.n_active:
            solo.step()
        assert done[i] == r.generated, i
    stats = srv.batcher.compile_stats()
    assert stats["decode_compiles"] == 1
    assert stats["prefill_compiles"] <= len(bucket_sizes(64))
    n_moe = sum(k == "moe" for k in cfg.layer_kinds())
    pre = [r.meta for r in tracing.spans() if r.name == "pb.prefill"]
    assert pre and all(0 < m["moe_pairs"] <= m["real_tokens"] * cfg.top_k
                       * n_moe for m in pre)
    dec = [r.meta for r in tracing.spans() if r.name == "pb.decode"]
    for m in dec:
        assert m["moe_experts_held"] == n_moe * cfg.n_experts
        assert 0 < m["moe_experts_hit"] <= m["moe_experts_held"]
        assert m["moe_pairs"] <= m["n_active"] * cfg.top_k * n_moe
        assert m["kv_blocks_window"] <= m["kv_blocks_nowindow"]
