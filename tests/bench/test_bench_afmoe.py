"""Trinity-Mini (AFMoE) at one chip's expert share: the plain float32
reference against the program, the expert share against the uncut layer,
and the readers of the MoE and window counters."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import harness, spans, weights, work_moe  # noqa: E402
from repro.models import attention as attn_lib  # noqa: E402
from repro.models import moe, transformer  # noqa: E402
from repro.models.layers import _ACTS  # noqa: E402
from repro.tracing import Record  # noqa: E402

REF = harness.load_module(ROOT / "bench" / "refs" / "afmoe.py")
FIX = Path(__file__).parent / "fixtures"
# Program and reference both compute in float32 (the reference at highest
# precision, the program at the CPU's float32); they sum in other orders
# (blocked online softmax and a grouped FFN against a full softmax and
# dense einsums), which over five layers and logits of magnitude ~5 moves
# the last few bits: 2e-4, as the dense reference's test allows.
TOL = 2e-4


def small(held=4, offset=2, published=8, top_k=2):
    """The configuration file at a small size: 1 dense and 4 MoE layers
    (sliding, sliding, full, sliding after the dense one), window 8."""
    cfg = json.load(open(ROOT / "bench" / "configs" / "trinity-mini-ep8.json"))
    cfg.update(hidden_size=64, intermediate_size=96, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, num_hidden_layers=5,
               num_dense_layers=1, moe_intermediate_size=32, vocab_size=300,
               num_experts=held, num_experts_published=published,
               expert_offset=offset, num_experts_per_tok=top_k,
               sliding_window=8, torch_dtype="float32",
               layer_types=cfg["layer_types"][:5])
    cfg["program"] = dict(cfg["program"], n_dense_layers=1, n_experts=held,
                          router_experts=published, expert_offset=offset,
                          top_k=top_k, moe_d_ff=32,
                          layer_windows=[8, 8, 8, 0])
    return cfg


def _params(cfg, seed):
    arch = harness.program_config("t", cfg)
    shapes = jax.eval_shape(lambda k: transformer.init_params(arch, k),
                            jax.random.PRNGKey(0))
    return arch, weights.make_params(seed, shapes, arch.vocab_size)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_prefill_then_decode_matches_the_reference(impl):
    """Three prompts of 20, 13 and 5 tokens and a pad row in one bucketed
    prefill, then decode through the cache to 37 tokens each (window 8),
    against the reference's forward of the whole sequences, on logits."""
    cfg = small()
    seed = 2**31 + 7
    arch, params = _params(cfg, seed)
    rng = np.random.default_rng(1)
    seqs = [rng.integers(0, 300, size=37) for _ in range(3)]
    plen = [20, 13, 5]
    toks = np.zeros((4, 32), np.int32)
    for i, n in enumerate(plen):
        toks[i, :n] = seqs[i][:n]
    last = np.asarray(plen + [1]) - 1
    valid = np.asarray([True] * 3 + [False])
    real = (np.arange(32)[None] <= last[:, None]) & valid[:, None]
    got = {i: [] for i in range(3)}
    moe.GMM_IMPL[0] = impl
    try:
        with attn_lib.decode_attn_impl(impl), \
                jax.default_matmul_precision("highest"):
            lg, cache, st = transformer.forward(
                arch, params, {"tokens": jnp.asarray(toks)}, mode="prefill",
                max_len=64, last_index=jnp.asarray(last),
                token_mask=jnp.asarray(real), return_stats=True)
            for i in range(3):
                got[i].append(np.asarray(lg[i, :300]))
            step = jax.jit(lambda p, t, c, m: transformer.decode_step(
                arch, p, {"tokens": t}, c, token_mask=m))
            for t in range(37 - min(plen)):
                pos = [n + t for n in plen]
                act = np.asarray([p < 37 for p in pos] + [False])
                tok = np.asarray([seqs[i][p] if act[i] else 0
                                  for i, p in enumerate(pos)] + [0], np.int32)
                lg, cache = step(params, jnp.asarray(tok), cache,
                                 jnp.asarray(act))
                for i in range(3):
                    if act[i]:
                        got[i].append(np.asarray(lg[i, :300]))
    finally:
        moe.GMM_IMPL[0] = "auto"
    h = REF.final_hidden(cfg, seed, seqs, [np.arange(n - 1, 37)
                                           for n in plen])
    want = np.asarray(h @ REF.head(cfg, seed))
    np.testing.assert_allclose(np.concatenate([np.stack(got[i])
                                               for i in range(3)]),
                               want, rtol=TOL, atol=TOL)
    # the prefill counted the real tokens' held pairs only
    assert 0 < int(st["moe_pairs"]) <= real.sum() * 2 * 4


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips' shares of an 8-expert layer (2 experts each, routed over
    all 8): their outputs, with the shared expert counted once, add up to
    the reference's uncut layer."""
    full = small(held=8, offset=0)
    seed = 2**31 + 11
    arch, params = _params(full, seed)
    p = jax.tree.map(lambda a: a[0], params["blocks"]["moe"])["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 9, 64), jnp.float32)
    parts = []
    with jax.default_matmul_precision("highest"):
        for s in range(4):
            cfg_s = harness.program_config("t", small(held=2, offset=2 * s))
            p_s = dict(p, **{k: p[k][2 * s:2 * s + 2]
                             for k in ("w_gate", "w_up", "w_down")})
            parts.append(moe.moe_mlp(cfg_s, p_s, x, _ACTS["silu"])[0])
        shared = moe.moe_mlp(harness.program_config(
            "t", small(held=8, offset=0, top_k=0)), p, x,
            _ACTS["silu"])[0]
    W = {"mlp/" + k: v for k, v in p.items() if k != "shared"}
    W.update({"mlp/shared/" + k: v for k, v in p["shared"].items()})
    c = dict(REF._cfgkey(full))
    want = REF._moe(x.reshape(18, 64), W, c, None).reshape(2, 9, 64)
    got = sum(parts) - 3 * shared
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_check_reads_the_widest_block_mean_of_the_gaps(monkeypatch):
    """The check's gaps are means over blocks of consecutive served
    tokens: one wide token is diluted by its block, a wrong stretch is
    not, and every row is kept."""
    g = np.zeros((300,), np.float32)
    g[10] = 1.0
    monkeypatch.setattr(REF.dense, "gaps", lambda *a, **k: g.copy())
    out = REF.gaps({}, 0, None, None)
    assert out.shape == (300,)                    # 2 blocks of 150 rows
    assert np.max(out) == pytest.approx(1.0 / 150)
    g[150:] = 0.3
    out = REF.gaps({}, 0, None, None)
    assert np.max(out) == pytest.approx(0.3)
    assert out[:150] == pytest.approx(np.full(150, 1.0 / 150))
    short = np.asarray([0.0, 0.6, 0.0], np.float32)
    monkeypatch.setattr(REF.dense, "gaps", lambda *a, **k: short)
    assert REF.gaps({}, 0, None, None) == pytest.approx([0.2] * 3)


def _run(config=None, trace_window=(10.0, 20.0), peak=None):
    cell = harness.Cell(name="x.y", entry={"chips": 1}, config_name="x",
                        config=config or {}, mix={}, spec={}, end_to_end=[],
                        per_layer=[])
    return harness.Run(cell=cell, dims=None, peak=peak, spans=[],
                       window=(0.0, 30.0), trace_window=trace_window,
                       trace=None)


def _reader(name):
    return harness.load_module(ROOT / "bench" / "layers" / f"{name}.py")


def _recs():
    """A traced window with two decode steps, an admission on a single
    chip, two belt admissions (no MoE counts), and a step before the
    window (left out)."""
    R = Record
    dec = {"n_active": 12, "kv_blocks": 40, "kv_blocks_all": 96,
           "kv_blocks_window": 600, "kv_blocks_nowindow": 720,
           "moe_pairs": 360, "moe_experts_hit": 255,
           "moe_experts_held": 480}
    return [
        R(1, None, "pb.decode", 9.5, 10.5, dict(dec, moe_pairs=1)),
        R(2, None, "pb.decode", 11.0, 11.02, dec),
        R(3, None, "pb.decode", 12.0, 12.02,
          dict(dec, kv_blocks_window=660, moe_experts_hit=270)),
        R(4, None, "pb.prefill", 13.0, 13.3,
          {"backend": "single", "rows": 12, "bucket": 256,
           "real_tokens": 300, "moe_pairs": 9000, "moe_experts_hit": 480}),
        R(5, None, "pb.prefill", 14.0, 16.0,
          {"backend": "pipeline", "rows": 16, "bucket": 128,
           "real_tokens": 90}),
        R(6, None, "pb.prefill", 17.0, 19.0,
          {"backend": "pipeline", "rows": 16, "bucket": 64,
           "real_tokens": 40}),
    ]


def test_window_share_reads_the_spans(monkeypatch):
    monkeypatch.setattr(spans, "records", _recs)
    assert _reader("window_kv_read_share").read(_run()) == pytest.approx(
        100.0 * (600 + 660) / (720 + 720))


def test_moe_gmm_roofline_reads_counts_over_the_kernels_time(monkeypatch):
    """The need of each traced span's MoE layers (30 calls at the mean
    counts) over the ``moe_gmm`` ops' device time of a recorded trace."""
    cfg = json.load(open(ROOT / "bench" / "configs" / "trinity-mini-ep8.json"))
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ops = json.load(open(FIX / "trace_moe_ops.json"))
    monkeypatch.setattr(spans, "records", _recs)
    monkeypatch.setattr(spans, "trace_file", lambda run: "trace.xplane.pb")
    monkeypatch.setattr(work_moe, "extract_ops", lambda path, chips: ops)
    dev = work_moe.kernel_s(ops)
    assert dev == pytest.approx((12_100_000 + 8_300_000 + 900_000) * 1e-9)
    DF = 2048 * 1024

    def need(pairs, hit):
        return 30 * max(6 * DF * pairs / 30 / 197e12,
                        3 * DF * 2 * hit / 30 / 819e9)
    want = need(360, 255) + need(360, 270) + need(9000, 480)
    got = _reader("moe_gmm_roofline").read(_run(cfg, peak=peak))
    assert got == pytest.approx(100.0 * want / dev)
    assert 0 < got < 100


@pytest.mark.parametrize("name", ["moe_gmm_roofline", "window_kv_read_share"])
def test_a_program_without_the_counts_reads_nothing(monkeypatch, name):
    plain = [Record(1, None, "pb.decode", 11.0, 11.02,
                    {"n_active": 3, "kv_blocks": 4, "kv_blocks_all": 8}),
             Record(2, None, "pb.prefill", 12.0, 14.0,
                    {"backend": "pipeline", "rows": 4, "bucket": 16,
                     "real_tokens": 9})]
    monkeypatch.setattr(spans, "records", lambda: plain)
    monkeypatch.setattr(spans, "trace_file", lambda run: None)
    assert _reader(name).read(_run({}, peak={})) is None
