"""Plain float32 reference of the Qwen2/Qwen3 dense decoder.

Follows the published Hugging Face ``Qwen2ForCausalLM`` /
``Qwen3ForCausalLM`` forward pass: RMSNorm before attention and MLP,
grouped-query attention with rotary embeddings (``rotate_half`` form),
optional QKV bias (Qwen2) or per-head RMSNorm of q and k (Qwen3), a SwiGLU
MLP, a final RMSNorm and a tied or untied output head over the published
vocabulary.  Everything is float32 with matmuls at ``highest`` precision;
no kernel, cache, padding or batching.

It imports nothing of the program.  It remakes the weights from the seed
with ``bench.weights``, one layer at a time, so that a model larger than
the chip at float32 still fits once the program's state is freed.

``quant="fp8"`` is the control: every linear layer (the head too) takes
float8 e4m3 inputs and weights, each scaled by its row's or column's
largest magnitude, and accumulates in float32.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

KIND = "attn"
HEAD_ROWS = 256          # logits rows per head call


def _dims(cfg: Dict) -> Dict[str, int]:
    D = cfg["hidden_size"]
    Hq = cfg["num_attention_heads"]
    return dict(D=D, Hq=Hq, Hkv=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim") or D // Hq,
                F=cfg["intermediate_size"], V=cfg["vocab_size"],
                L=cfg["num_hidden_layers"])


def layer_shapes(cfg: Dict) -> Dict[str, tuple]:
    """Per-layer leaf paths and shapes, as the served tree names them."""
    d = _dims(cfg)
    D, Hq, Hkv, hd, F = d["D"], d["Hq"], d["Hkv"], d["hd"], d["F"]
    pre = f"{weights.STACKED_ROOT}/{KIND}/"
    s = {"ln1": (D,), "ln2": (D,), "wq": (D, Hq * hd), "wk": (D, Hkv * hd),
         "wv": (D, Hkv * hd), "wo": (Hq * hd, D), "mlp/w_gate": (D, F),
         "mlp/w_up": (D, F), "mlp/w_down": (F, D)}
    if cfg["program"]["qkv_bias"]:
        s.update(bq=(Hq * hd,), bk=(Hkv * hd,), bv=(Hkv * hd,))
    if cfg["program"]["qk_norm"]:
        s.update(q_norm=(hd,), k_norm=(hd,))
    return {pre + k: v for k, v in s.items()}


def _dtype(cfg):
    return jnp.dtype(cfg["torch_dtype"])


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _q8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _dot(x, w, quant):
    if quant == "fp8":
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)


def _rope(x, theta):
    """x: (S, H, hd), positions 0..S-1, ``rotate_half`` convention."""
    S, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    rot = jnp.concatenate([-x2, x1], -1)
    return x * cos + rot * sin


@functools.partial(jax.jit, static_argnames=("cfgkey", "quant"))
def _layer(x, w, *, cfgkey, quant):
    cfg = dict(cfgkey)
    D, Hq, Hkv, hd = cfg["D"], cfg["Hq"], cfg["Hkv"], cfg["hd"]
    eps, theta = cfg["eps"], cfg["theta"]
    pre = f"{weights.STACKED_ROOT}/{KIND}/"
    W = {k[len(pre):]: v for k, v in w.items()}
    S = x.shape[0]
    h = _rms(x, W["ln1"], eps)
    q, k, v = _dot(h, W["wq"], quant), _dot(h, W["wk"], quant), \
        _dot(h, W["wv"], quant)
    if "bq" in W:
        q, k, v = q + W["bq"], k + W["bk"], v + W["bv"]
    q = q.reshape(S, Hq, hd)
    k = k.reshape(S, Hkv, hd)
    v = v.reshape(S, Hkv, hd)
    if "q_norm" in W:
        q = _rms(q, W["q_norm"], eps)
        k = _rms(k, W["k_norm"], eps)
    q, k = _rope(q, theta), _rope(k, theta)
    G = Hq // Hkv
    k = jnp.repeat(k, G, axis=1)                    # q head h reads kv h // G
    v = jnp.repeat(v, G, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=jax.lax.Precision.HIGHEST)
    x = x + _dot(o.reshape(S, Hq * hd), W["wo"], quant)
    h = _rms(x, W["ln2"], eps)
    g = _dot(h, W["mlp/w_gate"], quant)
    u = _dot(h, W["mlp/w_up"], quant)
    return x + _dot(jax.nn.silu(g) * u, W["mlp/w_down"], quant)


def _cfgkey(cfg):
    d = _dims(cfg)
    return tuple(sorted(dict(D=d["D"], Hq=d["Hq"], Hkv=d["Hkv"], hd=d["hd"],
                             eps=float(cfg["rms_norm_eps"]),
                             theta=float(cfg["rope_theta"])).items()))


def _pad_len(n: int) -> int:
    p = 256
    while p < n:
        p *= 2
    return p


def final_hidden(cfg: Dict, seed: int, seqs: Sequence[np.ndarray],
                 positions: Sequence[np.ndarray],
                 quant: Optional[str] = None) -> jnp.ndarray:
    """Final-norm hidden states (sum of len(positions), D) at ``positions``
    of each sequence, in order.  Layer by layer over all sequences, each
    padded at its end to a power of two (causal: padding changes nothing
    before it)."""
    d = _dims(cfg)
    dt = _dtype(cfg)
    key = weights.seed_key(seed)
    V = d["V"]
    emb = _embed_rows(cfg, key)
    xs = []
    for t in seqs:
        P = _pad_len(len(t))
        ids = np.zeros((P,), np.int32)
        ids[:len(t)] = t
        xs.append(jnp.take(emb, jnp.asarray(ids), axis=0).astype(jnp.float32))
    del emb
    shapes = layer_shapes(cfg)
    gen = jax.jit(lambda k, l: {p: weights.layer_leaf(k, p, l, s, dt, V)
                                .astype(jnp.float32)
                                for p, s in shapes.items()})
    ck = _cfgkey(cfg)
    for l in range(d["L"]):
        w = gen(key, jnp.uint32(l))
        xs = [_layer(x, w, cfgkey=ck, quant=quant) for x in xs]
        del w
    fn = _leaf(key, path="final_norm", shape=(d["D"],), dtype=dt,
               vocab=V).astype(jnp.float32)
    eps = float(cfg["rms_norm_eps"])
    rows = [_rms(x[jnp.asarray(pos)], fn, eps) for x, pos in zip(xs, positions)]
    return jnp.concatenate(rows, 0)


@functools.partial(jax.jit, static_argnames=("path", "shape", "dtype", "vocab"))
def _leaf(key, *, path, shape, dtype, vocab):
    return weights.make_leaf(key, path, shape, dtype, vocab)


def _embed_rows(cfg, key):
    d = _dims(cfg)
    return _leaf(key, path="embed", shape=(d["V"], d["D"]), dtype=_dtype(cfg),
                 vocab=d["V"])


def head(cfg: Dict, seed: int) -> jnp.ndarray:
    """(D, V) float32 output head over the published vocabulary."""
    d = _dims(cfg)
    key = weights.seed_key(seed)
    if cfg["tie_word_embeddings"]:
        return _embed_rows(cfg, key).astype(jnp.float32).T
    return _leaf(key, path="lm_head", shape=(d["D"], d["V"]),
                 dtype=_dtype(cfg), vocab=d["V"]).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("quant",))
def _row_gaps(h_ref, W, toks, h_test, *, quant):
    """Per row, the reference's best logit minus its logit of the token:
    ``toks``, or with ``quant`` the one the control puts first."""
    ref = jnp.dot(h_ref, W, precision=jax.lax.Precision.HIGHEST)
    if quant is not None:
        toks = jnp.argmax(_dot(h_test, W, quant), axis=-1)
    return jnp.max(ref, -1) - jnp.take_along_axis(ref, toks[:, None], 1)[:, 0]


def gaps(cfg: Dict, seed: int, h_ref: jnp.ndarray, tokens: np.ndarray,
         h_test: Optional[jnp.ndarray] = None,
         quant: Optional[str] = None) -> np.ndarray:
    """Per row: the reference's best logit minus its logit of the token.

    Without ``h_test`` the token is ``tokens`` (what the program served).
    With it (the control), the token is the argmax of the control's logits
    computed from ``h_test`` in ``quant`` precision."""
    W = head(cfg, seed)
    if h_test is None:
        h_test, quant = h_ref, None
    out = []
    n = h_ref.shape[0]
    for i in range(0, n, HEAD_ROWS):
        j = min(n, i + HEAD_ROWS)
        pad = ((0, HEAD_ROWS - (j - i)), (0, 0))
        tk = jnp.pad(jnp.asarray(tokens[i:j], jnp.int32), pad[:1])
        g = _row_gaps(jnp.pad(h_ref[i:j], pad), W, tk,
                      jnp.pad(h_test[i:j], pad), quant=quant)
        out.append(np.asarray(g)[:j - i])
    return np.concatenate(out) if out else np.zeros((0,), np.float32)
