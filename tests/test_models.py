"""Per-architecture smoke tests (reduced configs, CPU): one forward/train
step per assigned arch asserting output shapes + no NaNs, plus
decode-vs-full-forward consistency (the KV-cache correctness invariant)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ARCH_IDS, get_arch
from repro.models import transformer as T

KEY = jax.random.PRNGKey(0)


def make_batch(cfg, B=2, S=24):
    if cfg.family in ("audio", "vlm"):
        batch = {"embeds": jax.random.normal(KEY, (B, S, cfg.d_model),
                                             jnp.float32)}
        if cfg.mrope:
            batch["positions"] = jnp.broadcast_to(
                jnp.arange(S)[None, :, None], (B, S, 3)).astype(jnp.int32)
    else:
        batch = {"tokens": jax.random.randint(KEY, (B, S), 0,
                                              cfg.vocab_size)}
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_forward(arch):
    cfg = get_arch(arch).reduced()
    params = T.init_params(cfg, KEY)
    batch = make_batch(cfg)
    logits, aux = T.forward(cfg, params, batch, mode="train")
    B, S = 2, 24
    assert logits.shape == (B, S, cfg.padded_vocab)
    assert not np.any(np.isnan(np.asarray(logits, np.float32)))
    assert np.isfinite(float(aux))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_train_step(arch):
    from repro.training.optimizer import AdamWConfig
    from repro.training.train import init_train_state, make_train_step
    cfg = get_arch(arch).reduced()
    state = init_train_state(cfg, KEY, jnp.float32)
    step = make_train_step(cfg, AdamWConfig(warmup_steps=1, total_steps=10),
                           remat=True)
    batch = make_batch(cfg)
    batch["labels"] = jax.random.randint(jax.random.fold_in(KEY, 9),
                                         (2, 24), 0, cfg.vocab_size)
    new_state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    # params actually changed
    a0 = jax.tree.leaves(state.params)[1]
    a1 = jax.tree.leaves(new_state.params)[1]
    assert not np.allclose(np.asarray(a0), np.asarray(a1))


@pytest.mark.parametrize("arch", [a for a in ARCH_IDS
                                  if get_arch(a).has_decode])
def test_arch_decode_matches_full_forward(arch):
    cfg = get_arch(arch).reduced()
    params = T.init_params(cfg, KEY)
    B, S = 2, 24
    batch = make_batch(cfg, B, S)
    lg, cache = T.forward(cfg, params, batch, mode="prefill", max_len=S + 8)
    assert lg.shape == (B, cfg.padded_vocab)

    if cfg.family == "vlm":
        e1 = jax.random.normal(jax.random.fold_in(KEY, 3),
                               (B, 1, cfg.d_model), jnp.float32)
        p1 = jnp.full((B, 1, 3), S, jnp.int32)
        lg2, _ = T.decode_step(cfg, params, {"embeds": e1, "positions": p1},
                               cache)
        full_batch = {"embeds": jnp.concatenate([batch["embeds"], e1], 1),
                      "positions": jnp.concatenate([batch["positions"], p1],
                                                   1)}
    else:
        nxt = jnp.argmax(lg, -1).astype(jnp.int32)
        lg2, _ = T.decode_step(cfg, params, {"tokens": nxt}, cache)
        full_batch = {"tokens": jnp.concatenate(
            [batch["tokens"], nxt[:, None]], 1)}
    full, _ = T.forward(cfg, params, full_batch, mode="train")
    np.testing.assert_allclose(np.asarray(lg2),
                               np.asarray(full[:, -1, :]),
                               atol=2e-2, rtol=2e-2)


def test_encoder_has_no_decode():
    cfg = get_arch("hubert-xlarge").reduced()
    assert not cfg.has_decode
    with pytest.raises(AssertionError):
        T.decode_step(cfg, T.init_params(cfg, KEY), {"tokens": jnp.zeros(
            (1,), jnp.int32)}, {"pos": jnp.zeros((1,), jnp.int32)})


def test_local_window_ring_buffer_long_decode():
    """Windowed arch decoding past the window: ring must hold exactly the
    last `window` keys (long_500k-style bounded cache)."""
    cfg = get_arch("recurrentgemma-2b").reduced(n_layers=3, attn_window=8)
    params = T.init_params(cfg, KEY)
    B, S = 1, 20
    batch = make_batch(cfg, B, S)
    lg, cache = T.forward(cfg, params, batch, mode="prefill", max_len=64)
    assert cache["attn"]["k"].shape[2] == 8  # ring capacity == window
    tok = jnp.argmax(lg, -1).astype(jnp.int32)
    toks = [batch["tokens"], tok[:, None]]
    for i in range(12):
        lg, cache = T.decode_step(cfg, params, {"tokens": tok}, cache)
        tok = jnp.argmax(lg, -1).astype(jnp.int32)
        toks.append(tok[:, None])
    # reference: full forward over the whole history
    hist = jnp.concatenate(toks, axis=1)
    full, _ = T.forward(cfg, params, {"tokens": hist[:, :-1]}, mode="train")
    np.testing.assert_allclose(np.asarray(lg),
                               np.asarray(full[:, -1, :]),
                               atol=2e-2, rtol=2e-2)


def test_param_counts_match_published():
    expect = {
        "mamba2-780m": 0.780, "qwen3-1.7b": 1.72, "deepseek-coder-33b": 33.3,
        "granite-3-8b": 8.17, "qwen2.5-14b": 14.8, "hubert-xlarge": 0.95,
        "qwen2-vl-72b": 72.7, "recurrentgemma-2b": 2.67,
    }
    for a, v in expect.items():
        got = get_arch(a).param_count() / 1e9
        assert abs(got - v) / v < 0.02, (a, got, v)
    # MoE active counts
    assert abs(get_arch("qwen2-moe-a2.7b").active_param_count() / 1e9
               - 2.7) < 0.15
    assert abs(get_arch("phi3.5-moe-42b-a6.6b").active_param_count() / 1e9
               - 6.6) < 0.25


def test_cells_matrix():
    from repro.configs.base import cells
    cs = cells(include_skipped=True)
    assert len(cs) == 44
    runnable = [c for c in cs if c[2]]
    assert len(runnable) == 34
