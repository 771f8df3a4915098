"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import decode_attention as dec
from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)


def rnd(shape, dtype, salt):
    x = jax.random.normal(jax.random.fold_in(KEY, salt), shape, jnp.float32)
    return x.astype(dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def poison_past(x, lens):
    """NaN in every cache row at or past each slot's length (model layout
    (B, C, Hkv, d)): rows the decode kernel skips or masks must not leak
    into its output, and 0 x NaN is NaN."""
    C = x.shape[1]
    dead = np.arange(C)[None, :] >= np.asarray(lens)[:, None]
    return jnp.where(jnp.asarray(dead)[:, :, None, None],
                     jnp.asarray(np.nan, x.dtype), x)


@pytest.mark.parametrize("B,Sq,Sk,Hq,Hkv,d", [
    (1, 128, 128, 4, 4, 64),     # MHA square
    (2, 200, 200, 8, 2, 64),     # GQA, ragged block edge
    (1, 64, 256, 4, 1, 128),     # MQA, cross attention lengths
    (2, 33, 130, 2, 2, 32),      # non-aligned everything
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64), (False, 0)])
def test_flash_attention_sweep(B, Sq, Sk, Hq, Hkv, d, dtype, causal, window):
    if causal and Sq != Sk:
        pytest.skip("causal assumes aligned q/k starts here")
    q = rnd((B, Sq, Hq, d), dtype, 1)
    k = rnd((B, Sk, Hkv, d), dtype, 2)
    v = rnd((B, Sk, Hkv, d), dtype, 3)
    o = ops.flash_attention(q, k, v, causal=causal, window=window,
                            block_q=64, block_k=64)
    r = ref.flash_attention_ref(jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2),
                                jnp.moveaxis(v, 1, 2), causal=causal,
                                window=window)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(jnp.moveaxis(r, 1, 2), np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("B,C,Hq,Hkv,d,block_k", [
    (2, 256, 8, 8, 64, 128),
    (3, 300, 8, 2, 64, 128),     # GQA + ragged tail
    (1, 1024, 4, 1, 128, 512),   # MQA long cache
    (6, 320, 4, 4, 64, 128),     # G 1, ragged tail (320 = 2.5 blocks)
    (6, 320, 10, 2, 128, 128),   # G 5, ragged tail
    (5, 384, 2, 1, 128, 128),    # MQA, G 2
    (3, 1536, 16, 8, 128, None),  # qwen3-1.7b heads, block from the shapes
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(B, C, Hq, Hkv, d, block_k, dtype):
    q = rnd((B, 1, Hq, d), dtype, 4)
    k = rnd((B, C, Hkv, d), dtype, 5)
    v = rnd((B, C, Hkv, d), dtype, 6)
    lens = np.random.default_rng(0).integers(1, C + 1, size=B)
    # edge lengths in all but the last slot: empty, one row, a whole
    # block, two blocks, the whole cache
    bk = block_k or dec.block_k_for(C, Hkv, d, jnp.dtype(dtype).itemsize)
    edges = [n for n in (0, 1, bk, 2 * bk, C) if n <= C][:B - 1]
    lens[:len(edges)] = edges
    lens = jnp.asarray(lens, jnp.int32)
    o = ops.decode_attention(q, poison_past(k, lens), poison_past(v, lens),
                             lens, block_k=block_k)
    assert np.isfinite(np.asarray(o, np.float32)).all()
    r = ref.decode_attention_ref(q[:, 0], jnp.moveaxis(k, 1, 2),
                                 jnp.moveaxis(v, 1, 2), lens)
    np.testing.assert_allclose(np.asarray(o[:, 0], np.float32),
                               np.asarray(r, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("B,C,Hq,Hkv,d", [
    (3, 256, 8, 2, 64),
    (2, 300, 4, 4, 32),              # ragged tail
    (5, 320, 10, 2, 128),            # G 5, ragged tail
    (4, 256, 4, 1, 64),              # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_merged_new_token(B, C, Hq, Hkv, d, dtype):
    """Zero-copy serving mode: the current token's K/V merged in-kernel
    must equal writing it at position ``lens`` and attending over lens+1
    entries — for ragged per-slot lens including the 0 and C-1 extremes."""
    q = rnd((B, 1, Hq, d), dtype, 30)
    k = rnd((B, C, Hkv, d), dtype, 31)
    v = rnd((B, C, Hkv, d), dtype, 32)
    kn = rnd((B, 1, Hkv, d), dtype, 33)
    vn = rnd((B, 1, Hkv, d), dtype, 34)
    lens = np.random.default_rng(1).integers(1, C - 1, size=B)
    lens[0] = 0                       # slot fresh out of (empty) prefill
    lens[-1] = C - 1                  # slot about to fill its cache
    if B >= 4:
        lens[1:3] = (1, 128)          # one row; exactly one whole block
    lens = jnp.asarray(lens, jnp.int32)
    o = ops.decode_attention(q, poison_past(k, lens), poison_past(v, lens),
                             lens, k_new=kn, v_new=vn, block_k=128)
    # oracle: write the new token into the cache, then plain ragged decode
    bidx = jnp.arange(B)
    kw = k.at[bidx, lens].set(kn[:, 0])
    vw = v.at[bidx, lens].set(vn[:, 0])
    r = ref.decode_attention_ref(q[:, 0], jnp.moveaxis(kw, 1, 2),
                                 jnp.moveaxis(vw, 1, 2), lens + 1)
    np.testing.assert_allclose(np.asarray(o[:, 0], np.float32),
                               np.asarray(r, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("B,C,Hq,Hkv,d,block_k", [
    (3, 40, 8, 2, 64, 16),           # GQA, mask straddles block edges
    (2, 300, 4, 4, 32, 128),         # ragged tail
    (4, 320, 10, 2, 128, 128),       # G 5, ragged tail
    (3, 256, 4, 1, 64, 128),         # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("merge_new", [False, True])
def test_decode_attention_slot_mask(B, C, Hq, Hkv, d, block_k, dtype,
                                    merge_new):
    """Ring-buffer mode: per-slot validity mask (eviction) must match the
    oracle — with and without the zero-copy in-kernel new-token merge."""
    q = rnd((B, 1, Hq, d), dtype, 50)
    k = rnd((B, C, Hkv, d), dtype, 51)
    v = rnd((B, C, Hkv, d), dtype, 52)
    rng = np.random.default_rng(2)
    lens = jnp.asarray(rng.integers(0, C + 1, size=B), jnp.int32)
    sm = rng.integers(0, 2, size=(B, C)).astype(bool)
    sm[0, :] = True                   # one fully-valid row
    kwargs = {}
    if merge_new:
        kwargs["k_new"] = rnd((B, 1, Hkv, d), dtype, 53)
        kwargs["v_new"] = rnd((B, 1, Hkv, d), dtype, 54)
    o = ops.decode_attention(q, poison_past(k, lens), poison_past(v, lens),
                             lens, slot_mask=jnp.asarray(sm),
                             block_k=block_k, **kwargs)
    if merge_new:
        # oracle: write the new token at the ring slot (pos % C), mark the
        # slot valid, and attend over min(lens+1, C) entries
        bidx = jnp.arange(B)
        slot = jnp.mod(lens, C)
        kw = k.at[bidx, slot].set(kwargs["k_new"][:, 0])
        vw = v.at[bidx, slot].set(kwargs["v_new"][:, 0])
        smw = jnp.asarray(sm).at[bidx, slot].set(True)
        r = ref.decode_attention_ref(q[:, 0], jnp.moveaxis(kw, 1, 2),
                                     jnp.moveaxis(vw, 1, 2),
                                     jnp.minimum(lens + 1, C), slot_mask=smw)
    else:
        r = ref.decode_attention_ref(q[:, 0], jnp.moveaxis(k, 1, 2),
                                     jnp.moveaxis(v, 1, 2), lens,
                                     slot_mask=jnp.asarray(sm))
    np.testing.assert_allclose(np.asarray(o[:, 0], np.float32),
                               np.asarray(r, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("C,Hkv,d,itemsize,want", [
    (1536, 8, 128, 2, 256),      # qwen3-1.7b: six 512 KiB blocks
    (3200, 8, 128, 2, 256),      # qwen2.5-14b: a ragged thirteenth block
    (96, 8, 128, 2, 96),         # short cache: one whole block
    (4096, 4, 128, 2, 512),      # fewer KV heads, more rows
    (4096, 4, 128, 4, 256),      # f32 halves the rows
    (4096, 64, 256, 2, 128),     # wide heads: never under 128 rows
])
def test_decode_block_k_follows_the_shapes(C, Hkv, d, itemsize, want):
    bk = dec.block_k_for(C, Hkv, d, itemsize)
    assert bk == want
    assert bk == C or bk % 128 == 0          # Mosaic's (8, 128) rule


def test_decode_kv_blocks_counts_the_valid_blocks():
    # 0 and 1 rows still fetch the first block; lengths past C are C
    assert dec.kv_blocks([0, 1, 512, 513, 1536, 2000], 1536, 512) == \
        1 + 1 + 1 + 2 + 3 + 3
    assert dec.kv_blocks(np.array([3200, 3073, 3072]), 3200, 512) == 20


def test_windowed_decode_step_pallas_matches_xla():
    """Ring-buffer (windowed) decode under eviction: the slot-masked Pallas
    flash-decode must produce the same logits/cache as the XLA lowering —
    the windowed zero-copy path no longer pins to XLA (ROADMAP item)."""
    from repro.configs.base import get_arch
    from repro.models import attention as A
    from repro.models import transformer as T
    cfg = get_arch("qwen3-1.7b").reduced(n_layers=2, attn_window=8)
    params = T.init_params(cfg, KEY)
    # prompt longer than the window: the ring is full and every further
    # decode step evicts (the slot mask is live, not vacuous)
    prompt = jax.random.randint(jax.random.fold_in(KEY, 60), (2, 12), 0, 250)
    lg, cache0 = T.forward(cfg, params, {"tokens": prompt}, mode="prefill",
                           max_len=32)
    tok0 = jnp.argmax(lg, -1).astype(jnp.int32)
    outs = {}
    for impl in ("xla", "pallas"):
        cache = jax.tree.map(lambda a: a, cache0)
        tok = tok0
        toks = []
        with A.decode_attn_impl(impl):
            for _ in range(6):
                lg, cache = T.decode_step(cfg, params, {"tokens": tok}, cache)
                tok = jnp.argmax(lg, -1).astype(jnp.int32)
                toks.append(np.asarray(tok))
        outs[impl] = (np.stack(toks), cache)
    np.testing.assert_array_equal(outs["xla"][0], outs["pallas"][0])
    for leaf in outs["xla"][1]["attn"]:
        np.testing.assert_allclose(
            np.asarray(outs["xla"][1]["attn"][leaf]),
            np.asarray(outs["pallas"][1]["attn"][leaf]), atol=1e-5, rtol=1e-5)


def test_decode_step_pallas_matches_xla():
    """transformer.decode_step behind the backend dispatch: the Pallas
    flash-decode path (interpret mode here, Mosaic on TPU) must match the
    XLA online-softmax path on ragged per-slot cache lengths."""
    from repro.configs.base import get_arch
    from repro.models import attention as A
    from repro.models import transformer as T
    cfg = get_arch("qwen3-1.7b").reduced(n_layers=2)
    params = T.init_params(cfg, KEY)
    prompt = jax.random.randint(jax.random.fold_in(KEY, 40), (2, 12), 0, 250)
    lg, cache = T.forward(cfg, params, {"tokens": prompt}, mode="prefill",
                          max_len=32)
    cache["pos"] = jnp.asarray([12, 7], jnp.int32)    # ragged slot lens
    tok = jnp.argmax(lg, -1).astype(jnp.int32)
    with A.decode_attn_impl("xla"):
        lx, cx = T.decode_step(cfg, params, {"tokens": tok}, cache)
    with A.decode_attn_impl("pallas"):
        lp, cp = T.decode_step(cfg, params, {"tokens": tok}, cache)
    np.testing.assert_allclose(np.asarray(lx), np.asarray(lp),
                               atol=1e-4, rtol=1e-4)
    for grp in ("attn",):
        for leaf in cx[grp]:
            np.testing.assert_allclose(np.asarray(cx[grp][leaf]),
                                       np.asarray(cp[grp][leaf]),
                                       atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (1, 64, 2, 16, 8, 16),
    (2, 130, 4, 32, 16, 32),     # pad path
    (1, 256, 8, 64, 128, 64),    # mamba2-780m-like dims
])
def test_ssd_scan_sweep(B, S, H, P, N, chunk):
    x = rnd((B, S, H, P), jnp.float32, 7)
    dt = jax.nn.softplus(rnd((B, S, H), jnp.float32, 8))
    A = -jnp.exp(rnd((H,), jnp.float32, 9) * 0.3)
    Bm = rnd((B, S, N), jnp.float32, 10)
    Cm = rnd((B, S, N), jnp.float32, 11)
    y, fs = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
    yr, fsr = ref.ssd_scan_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(np.asarray(fs), np.asarray(fsr),
                               atol=5e-4, rtol=5e-4)


def test_ssd_scan_matches_model_chunked_form():
    """Kernel vs the model's associative-scan SSD (two independent paths)."""
    from repro.models.mamba2 import ssd_chunked
    B, S, H, P, N = 2, 96, 4, 16, 8
    x = rnd((B, S, H, P), jnp.float32, 12)
    dt = jax.nn.softplus(rnd((B, S, H), jnp.float32, 13))
    A = -jnp.exp(rnd((H,), jnp.float32, 14) * 0.3)
    Bm = rnd((B, S, N), jnp.float32, 15)
    Cm = rnd((B, S, N), jnp.float32, 16)
    y1, fs1 = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=32)
    y2, fs2 = ssd_chunked(x, dt, A, Bm, Cm, chunk=16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(np.asarray(fs1), np.asarray(fs2),
                               atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("B,S,W,bt,bw", [
    (1, 64, 32, 32, 32),
    (2, 100, 48, 32, 16),        # pad both dims
    (1, 256, 128, 128, 128),
])
def test_rglru_scan_sweep(B, S, W, bt, bw):
    la = -jax.nn.softplus(rnd((B, S, W), jnp.float32, 17))
    bx = rnd((B, S, W), jnp.float32, 18)
    h0 = rnd((B, W), jnp.float32, 19)
    y, hT = ops.rglru_scan(la, bx, h0, block_t=bt, block_w=bw)
    yr, hTr = ref.rglru_scan_ref(la, bx, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hTr),
                               atol=1e-4, rtol=1e-4)


def test_rglru_matches_model_scan():
    from repro.models.rglru import rglru_scan as model_scan
    B, S, W = 2, 80, 32
    la = -jax.nn.softplus(rnd((B, S, W), jnp.float32, 20))
    bx = rnd((B, S, W), jnp.float32, 21)
    h0 = rnd((B, W), jnp.float32, 22)
    y1, h1 = ops.rglru_scan(la, bx, h0, block_t=16, block_w=16)
    y2, h2 = model_scan(la, bx, h0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("L,Din,Dout,r,bi,bj", [
    (1, 64, 64, 4, 32, 32),
    (3, 96, 160, 8, 32, 64),
    (2, 100, 100, 16, 64, 64),   # pad path
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lora_merge_sweep(L, Din, Dout, r, bi, bj, dtype):
    W = rnd((L, Din, Dout), dtype, 23)
    A = rnd((L, Din, r), dtype, 24)
    B = rnd((L, r, Dout), dtype, 25)
    o = ops.lora_merge(W, A, B, 0.25, block_i=bi, block_j=bj)
    r_ = ref.lora_merge_ref(W, A, B, 0.25)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r_, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_lora_merge_unmerge_roundtrip():
    W = rnd((2, 64, 64), jnp.float32, 26)
    A = rnd((2, 64, 8), jnp.float32, 27)
    B = rnd((2, 8, 64), jnp.float32, 28)
    merged = ops.lora_merge(W, A, B, 0.5)
    back = ops.lora_merge(merged, A, B, -0.5)
    np.testing.assert_allclose(np.asarray(back), np.asarray(W), atol=1e-5)


@pytest.mark.parametrize("B,C,Hq,Hkv,d,window", [
    (4, 512, 8, 1, 128, 200),        # G 8 (Trinity's ratio), window in a block
    (5, 320, 4, 2, 64, 130),         # ragged tail, window past a block edge
    (3, 256, 8, 8, 64, 1000),        # window longer than every slot
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_lower_bound(B, C, Hq, Hkv, d, window, dtype):
    """A per-slot lower bound (a sliding window over a full-length cache)
    equals the oracle with rows under it masked; rows under the bound and
    at or past ``lens`` hold NaN, and blocks wholly under it are counted
    as not fetched."""
    q = rnd((B, 1, Hq, d), dtype, 40)
    k = rnd((B, C, Hkv, d), dtype, 41)
    v = rnd((B, C, Hkv, d), dtype, 42)
    lens = np.random.default_rng(3).integers(1, C + 1, size=B)
    lens[0] = 0
    lens[-1] = C
    lo = np.maximum(lens - window + 1, 0)
    under = np.arange(C)[None, :] < lo[:, None]

    def poison(x):
        x = poison_past(x, lens)
        return jnp.where(jnp.asarray(under)[:, :, None, None],
                         jnp.asarray(np.nan, x.dtype), x)
    o = ops.decode_attention(q, poison(k), poison(v), jnp.asarray(lens),
                             lo=jnp.asarray(lo, jnp.int32), block_k=128)
    assert np.isfinite(np.asarray(o, np.float32)).all()
    r = ref.decode_attention_ref(q[:, 0], jnp.moveaxis(k, 1, 2),
                                 jnp.moveaxis(v, 1, 2), jnp.asarray(lens),
                                 slot_mask=~under)
    np.testing.assert_allclose(np.asarray(o[:, 0], np.float32),
                               np.asarray(r, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])
    nblk = -(-np.minimum(lens, C) // 128)
    assert dec.kv_blocks(lens, C, 128, lo=lo) == int(
        np.maximum(nblk - lo // 128, 1).sum())
    assert dec.kv_blocks(lens, C, 128, lo=lo) <= dec.kv_blocks(lens, C, 128)


@pytest.mark.parametrize("sizes,tm,tf", [
    ((5, 0, 40, 3), 16, 32),         # an empty expert between two others
    ((0, 0, 7, 0), 8, 64),           # one expert hit, rows well past it
    ((0, 0, 0, 0), 16, 32),          # no pair held here at all
    ((16, 16, 16, 16), 16, 16),      # groups on tile edges, every F tile
])
def test_moe_gmm_matches_einsum(sizes, tm, tf):
    """The grouped expert FFN in interpret mode against a plain einsum per
    expert; rows past the groups are left to the caller."""
    G, D, F, M = 4, 32, 64, 70
    x = rnd((M, D), jnp.float32, 50)
    wg = rnd((G, D, F), jnp.float32, 51) / 6
    wu = rnd((G, D, F), jnp.float32, 52) / 6
    wd = rnd((G, F, D), jnp.float32, 53) / 8
    gs = np.asarray(sizes, np.int32)
    out = np.asarray(ops.moe_gmm(x, wg, wu, wd, jnp.asarray(gs), tm=tm,
                                 tf=tf))
    ends = np.cumsum(gs)
    for g in range(G):
        r = slice(ends[g] - gs[g], ends[g])
        want = (jax.nn.silu(x[r] @ wg[g]) * (x[r] @ wu[g])) @ wd[g]
        np.testing.assert_allclose(out[r], np.asarray(want), atol=2e-5,
                                   rtol=2e-5)
