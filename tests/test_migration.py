"""KV-snapshot migration: export -> import resumes decode with zero
re-prefill, token-for-token identical to an uninterrupted run (the
cluster-level §4.4 claim: surviving hardware keeps serving without
redoing prefill)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch
from repro.models import transformer as T
from repro.serving.engine import (ContinuousBatcher, ServeRequest,
                                  ServingEngine, quantized_greedy)

KEY = jax.random.PRNGKey(11)


def _solo(cfg, params, prompt, n, max_len=96):
    lg, cache = T.forward(cfg, params, {"tokens": jnp.asarray(prompt)[None]},
                          mode="prefill", max_len=max_len)
    toks = [int(quantized_greedy(lg)[0])]
    for _ in range(n - 1):
        lg, cache = T.decode_step(
            cfg, params, {"tokens": jnp.asarray([toks[-1]], jnp.int32)},
            cache)
        toks.append(int(quantized_greedy(lg)[0]))
    return toks


def _engine(cfg, params, n_slots=2, max_len=96):
    e = ServingEngine(cfg, params, n_slots=n_slots, max_len=max_len)
    e.batcher.sampler = quantized_greedy
    return e


@pytest.mark.parametrize("arch,kw", [
    ("qwen3-1.7b", {}),                          # dense, full-length cache
    ("qwen3-1.7b", {"attn_window": 8}),          # pure-attn ring buffer
    ("recurrentgemma-2b", {"attn_window": 8}),   # hybrid rec + ring
    ("mamba2-780m", {}),                         # SSM state only
])
def test_migration_roundtrip_matches_solo(arch, kw):
    """Drain mid-decode -> import on a fresh engine -> identical greedy
    tokens, with ZERO prefill work on the survivor.  The ring cases use a
    prompt longer than the window, so the tail-keep prefill branch and the
    wrapped-ring slot layout both ride through the snapshot."""
    cfg = get_arch(arch).reduced(n_layers=4, **kw)
    params = T.init_params(cfg, KEY)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 250, size=20)       # > window in ring cases
    a = _engine(cfg, params)
    req = ServeRequest(0, prompt, max_new_tokens=10)
    a.submit(req)
    for _ in range(4):
        a.step()
    drained = a.drain_inflight()
    assert drained == [req]
    assert req.snapshot is not None
    assert 1 < len(req.generated) < 10
    # snapshot pos == tokens whose state travelled (prompt + prefix - 1)
    assert req.snapshot.pos == len(prompt) + len(req.generated) - 1

    b = _engine(cfg, params)
    assert b.admit_with_state(req)
    assert req.snapshot is None                  # consumed
    assert b.batcher.n_prefill_reqs == 0
    assert b.batcher.n_migrated_in == 1
    while b.batcher.n_active:
        b.step()
    assert req.done
    assert req.generated == _solo(cfg, params, prompt, 10)
    assert b.batcher.n_prefill_reqs == 0         # never prefetched a token


def test_migration_into_busy_batch_exact():
    """Import lands in a free slot of a batch that is mid-decode on other
    requests; neither the import nor the residents diverge."""
    cfg = get_arch("qwen3-1.7b").reduced(n_layers=4)
    params = T.init_params(cfg, KEY)
    rng = np.random.default_rng(1)
    p_res = rng.integers(0, 250, size=9)
    p_mig = rng.integers(0, 250, size=14)

    a = _engine(cfg, params)
    mig = ServeRequest(7, p_mig, max_new_tokens=8)
    a.submit(mig)
    for _ in range(3):
        a.step()
    a.drain_inflight()

    b = _engine(cfg, params, n_slots=3)
    res = ServeRequest(1, p_res, max_new_tokens=9)
    b.submit(res)
    b.step()
    b.step()
    assert b.admit_with_state(mig)
    while b.batcher.n_active:
        b.step()
    assert mig.generated == _solo(cfg, params, p_mig, 8)
    assert res.generated == _solo(cfg, params, p_res, 9)


def test_import_refuses_incompatible_snapshot():
    """Shape/identity mismatches must refuse (return False) so the caller
    falls back to re-prefill instead of corrupting a cache."""
    cfg = get_arch("qwen3-1.7b").reduced(n_layers=4)
    params = T.init_params(cfg, KEY)
    rng = np.random.default_rng(2)
    a = _engine(cfg, params, max_len=96)
    req = ServeRequest(0, rng.integers(0, 250, size=8), max_new_tokens=6)
    a.submit(req)
    a.step()
    a.step()
    [req] = a.drain_inflight()

    # different max_len -> different cache capacity -> refuse
    b = _engine(cfg, params, max_len=64)
    assert not b.admit_with_state(req)
    assert req.snapshot is not None              # kept for the fallback
    # different arch -> refuse
    cfg2 = get_arch("qwen3-1.7b").reduced(n_layers=2)
    c = _engine(cfg2, T.init_params(cfg2, KEY), max_len=96)
    assert not c.admit_with_state(req)
    # the fallback path still finishes it exactly
    d = _engine(cfg, params, max_len=96)
    d.submit(req)
    d.run()
    assert req.generated == _solo(cfg, params, req.tokens, 6)


def test_admit_with_state_respects_epoch_barrier():
    """A batch mid-epoch on a different adapter must refuse the import
    (merged-LoRA weights apply to every slot)."""
    from repro.lora.adapters import init_lora, merge_lora, randomize_lora
    cfg = get_arch("qwen3-1.7b").reduced(n_layers=4)
    params = T.init_params(cfg, KEY)
    merged = merge_lora(params, randomize_lora(
        jax.random.fold_in(KEY, 3), init_lora(KEY, cfg, rank=4)))
    rng = np.random.default_rng(3)

    a = ServingEngine(cfg, params, n_slots=2, max_len=96,
                      adapter_params={"a": merged})
    a.batcher.sampler = quantized_greedy
    mig = ServeRequest(0, rng.integers(0, 250, size=8), max_new_tokens=6,
                       adapter="a")
    a.submit(mig)
    a.step()
    a.step()
    [mig] = a.drain_inflight()

    # survivor busy on BASE weights -> refuse the adapter-tagged import
    b = ServingEngine(cfg, params, n_slots=2, max_len=96,
                      adapter_params={"a": merged})
    b.batcher.sampler = quantized_greedy
    b.submit(ServeRequest(1, rng.integers(0, 250, size=8),
                          max_new_tokens=12))
    b.step()
    assert not b.admit_with_state(mig)
    # survivor without the adapter at all -> refuse
    c = _engine(cfg, params)
    assert not c.admit_with_state(mig)
    # idle survivor WITH the adapter -> switches and resumes exactly
    d = ServingEngine(cfg, params, n_slots=2, max_len=96,
                      adapter_params={"a": merged})
    d.batcher.sampler = quantized_greedy
    assert d.admit_with_state(mig)
    while d.batcher.n_active:
        d.step()
    assert mig.generated == _solo(cfg, merged, mig.tokens, 6)


def test_ring_zero_copy_step_matches_write_path():
    """The windowed decode step's zero-copy form (merged partial + evicted
    slot masked) must equal the legacy write-then-attend ring path."""
    from repro.models.transformer import attn_layer_step
    cfg = get_arch("qwen3-1.7b").reduced(n_layers=1, attn_window=8)
    params = T.init_params(cfg, KEY)
    p_l = jax.tree.map(lambda a: a[0], params["blocks"]["attn"])
    B, C, hd = 3, 8, cfg.resolved_head_dim
    rng = jax.random.PRNGKey(4)
    x = jax.random.normal(rng, (B, 1, cfg.d_model), jnp.float32)
    kc = jax.random.normal(jax.random.fold_in(rng, 1),
                           (B, C, cfg.n_kv_heads, hd), jnp.float32)
    vc = jax.random.normal(jax.random.fold_in(rng, 2),
                           (B, C, cfg.n_kv_heads, hd), jnp.float32)
    # per-slot positions: unwrapped, exactly-at-capacity, wrapped
    for pos_vals in ([3, 8, 13], [1, 7, 20]):
        pos = jnp.asarray(pos_vals, jnp.int32)
        x0, k0, v0, _ = attn_layer_step(cfg, p_l, x, pos[:, None], kc, vc,
                                        pos, zero_copy=False)
        x1, k1, v1, _ = attn_layer_step(cfg, p_l, x, pos[:, None], kc, vc,
                                        pos, zero_copy=True)
        np.testing.assert_allclose(np.asarray(x0), np.asarray(x1),
                                   atol=2e-5, rtol=2e-5)
        # write path returns the full cache; zero-copy returns the row the
        # caller scatters at pos % C — they must agree there
        slot = np.mod(pos_vals, C)
        bidx = np.arange(B)
        np.testing.assert_allclose(np.asarray(k0)[bidx, slot],
                                   np.asarray(k1), atol=1e-6)
        np.testing.assert_allclose(np.asarray(v0)[bidx, slot],
                                   np.asarray(v1), atol=1e-6)


def test_reconstruct_inflight_partial_layers():
    """Batcher-level §4.4.2: wipe some layers' state under live requests,
    rebuild only those, decode continues token-exact."""
    cfg = get_arch("qwen3-1.7b").reduced(n_layers=4)
    params = T.init_params(cfg, KEY)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 250, size=L) for L in (12, 7)]
    srv = _engine(cfg, params, n_slots=2)
    reqs = [ServeRequest(i, p, max_new_tokens=8)
            for i, p in enumerate(prompts)]
    for r in reqs:
        srv.submit(r)
    for _ in range(3):
        srv.step()
    cache = srv.batcher.cache
    for leaf in ("k", "v"):
        z = cache["attn"][leaf]
        cache["attn"][leaf] = z.at[1:3].set(jnp.zeros_like(z[1:3]))
    stats = srv.reconstruct_inflight([True, False, False, True])
    assert stats["reconstructed_reqs"] == 2
    assert stats["kv_reused"] == 2       # layer 0, per request
    assert stats["full_prefill"] == 4    # layers 1-2, per request
    assert stats["layers_skipped"] == 2  # layer 3 untouched
    assert stats["q_only_tokens"] > 0 and stats["prefill_tokens"] > 0
    while srv.batcher.n_active:
        srv.step()
    for i, p in enumerate(prompts):
        assert reqs[i].generated == _solo(cfg, params, p, 8), i


@pytest.mark.parametrize("arch,kw", [
    ("qwen3-1.7b", {}),                          # dense, full-length cache
    ("qwen3-1.7b", {"attn_window": 8}),          # pure-attn ring buffer
    ("mamba2-780m", {}),                         # SSM state only
])
def test_batched_import_matches_sequential(arch, kw):
    """A survivor absorbing several victims imports their snapshots in ONE
    donated scatter (import_snapshots) with the same continuations as N
    sequential import_snapshot calls — and one batched-import dispatch."""
    cfg = get_arch(arch).reduced(n_layers=4, **kw)
    params = T.init_params(cfg, KEY)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 250, size=L) for L in (20, 11, 15)]

    def drained_victims():
        a = _engine(cfg, params, n_slots=4)
        reqs = [ServeRequest(i, p, max_new_tokens=10)
                for i, p in enumerate(prompts)]
        for r in reqs:
            a.submit(r)
        for _ in range(4):
            a.step()
        return a.drain_inflight()

    b = _engine(cfg, params, n_slots=4)
    batch = drained_victims()
    accepted = b.admit_with_state_batch(batch)
    assert sorted(r.rid for r in accepted) == [0, 1, 2]
    assert b.batcher.n_batched_imports == 1      # ONE scatter dispatch
    assert b.batcher.n_migrated_in == 3
    assert b.batcher.n_prefill_reqs == 0         # zero re-prefill
    while b.batcher.n_active:
        b.step()

    c = _engine(cfg, params, n_slots=4)
    seq = drained_victims()
    for r in seq:
        assert c.admit_with_state(r)
    while c.batcher.n_active:
        c.step()
    for x, y in zip(sorted(accepted, key=lambda r: r.rid),
                    sorted(seq, key=lambda r: r.rid)):
        assert x.generated == y.generated, (x.rid, x.generated, y.generated)
        assert x.generated == _solo(cfg, params, prompts[x.rid], 10)


def test_batched_import_partial_capacity():
    """With fewer free slots than victims, import_snapshots takes what
    fits and hands the rest back for the re-prefill fallback."""
    cfg = get_arch("qwen3-1.7b").reduced(n_layers=2)
    params = T.init_params(cfg, KEY)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 250, size=10 + i) for i in range(3)]
    a = _engine(cfg, params, n_slots=4)
    reqs = [ServeRequest(i, p, max_new_tokens=8)
            for i, p in enumerate(prompts)]
    for r in reqs:
        a.submit(r)
    for _ in range(3):
        a.step()
    drained = a.drain_inflight()

    b = _engine(cfg, params, n_slots=3)
    resident = ServeRequest(9, rng.integers(0, 250, size=8),
                            max_new_tokens=12)
    b.submit(resident)
    b.step()                                     # 2 free slots remain
    accepted = b.admit_with_state_batch(drained)
    assert len(accepted) == 2
    left = [r for r in drained if r.rid not in {x.rid for x in accepted}]
    assert len(left) == 1 and left[0].snapshot is not None
    while b.batcher.n_active:
        b.step()
    for r in accepted:
        assert r.generated == _solo(cfg, params, prompts[r.rid], 8)
    assert resident.generated == _solo(cfg, params, resident.tokens, 12)


def test_crash_of_migration_target_mid_import():
    """Overlapping faults: the server that absorbed a migrated request
    crashes too, mid-decode.  The snapshot chain (A -> B -> C) survives a
    second hop and the final tokens still equal the uninterrupted run —
    snapshots compose."""
    cfg = get_arch("qwen3-1.7b").reduced(n_layers=4)
    params = T.init_params(cfg, KEY)
    rng = np.random.default_rng(6)
    prompt = rng.integers(0, 250, size=15)

    a = _engine(cfg, params)
    req = ServeRequest(0, prompt, max_new_tokens=12)
    a.submit(req)
    for _ in range(4):
        a.step()
    [req] = a.drain_inflight()               # first crash: A dies
    pos_a = req.snapshot.pos

    b = _engine(cfg, params)
    assert b.admit_with_state(req)
    for _ in range(3):
        b.step()                             # the import decodes a while
    assert not req.done
    [req] = b.drain_inflight()               # second crash: the TARGET dies
    assert req.snapshot is not None
    assert req.snapshot.pos > pos_a          # B's progress rode along

    c = _engine(cfg, params)
    assert c.admit_with_state(req)
    assert c.batcher.n_prefill_reqs == 0     # still zero re-prefill
    while c.batcher.n_active:
        c.step()
    assert req.done
    assert req.generated == _solo(cfg, params, prompt, 12)
