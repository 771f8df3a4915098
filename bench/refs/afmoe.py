"""Plain float32 reference of the AFMoE decoder (Arcee Trinity) at one
chip's expert share.

Follows the published AFMoE forward pass, item by item as the
configuration file's ``assumed`` lists them:

* (a) per-head RMSNorm of q and k before RoPE;
* (b) RoPE on sliding layers only, none on full ones;
* (c) the attention output times ``sigmoid(h @ wg)`` before ``wo``, with
  ``h`` the normed input;
* (d) four norms a block: ``h = x + post_ln1(attn(ln1(x)))``,
  ``y = h + post_ln2(mlp(ln2(h)))``;
* (e) embeddings times sqrt(hidden_size);
* (f) sigmoid router over every expert of the deployment, top-k of the
  scores plus a selection-only bias, weights ``s / (sum s + 1e-20) *
  route_scale``;
* (g) an ungated shared expert added to the routed sum;
* (h) on a sliding layer a query attends keys with ``q - k < window``.

The first ``num_dense_layers`` layers have a SwiGLU MLP, the rest the
MoE FFN.  Of the experts the router picks, only those this chip holds
(``expert_offset`` .. ``expert_offset + num_experts``) are computed, as in
the program: what the experts held elsewhere would add is left out.
Everything is float32 with matmuls at ``highest`` precision; each held
expert runs on every token and is weighted by its routing weight (zero
where it was not picked); attention runs in blocks of queries.

It imports nothing of the program.  It remakes the weights from the seed
with ``bench.weights``, one layer at a time, and shares the head, the
per-token gap, RMSNorm, RoPE and the fp8 control with ``qwen_dense``:
``quant="fp8"`` rounds every linear layer's inputs and weights (router,
experts and head included) to float8 e4m3 with one scale per row or
column, accumulating in float32.

The check reads ``gaps`` here as block means: each served token's gap
(the reference's best logit less its logit of the token) averaged over
its block of ``GAP_BLOCK`` consecutive served tokens, so the harness's
maximum is the widest mean over any block.  A single token's gap does
not separate the program from its control: with 128 sigmoid scores a
near-tie flips an expert now and then, and one flip moves that token's
logits nearly as far as fp8 does.  A mean over a block still reads any
stretch of tokens the program gets wrong (past the window, say).
"""
from __future__ import annotations

import functools
import sys
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights
from bench.refs import qwen_dense as dense
from bench.refs.qwen_dense import head  # noqa: F401  (the interface)

Q_BLOCK = 512            # queries per attention block
GAP_BLOCK = 128          # served tokens per mean of the check
HI = jax.lax.Precision.HIGHEST


def _dims(cfg: Dict) -> Dict:
    D = cfg["hidden_size"]
    Hq = cfg["num_attention_heads"]
    return dict(D=D, Hq=Hq, Hkv=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim") or D // Hq,
                F=cfg["intermediate_size"], Fe=cfg["moe_intermediate_size"],
                E=cfg["num_experts"], Er=cfg["num_experts_published"],
                S=cfg["num_shared_experts"], V=cfg["vocab_size"],
                L=cfg["num_hidden_layers"], n_dense=cfg["num_dense_layers"])


def _kind(cfg, l: int) -> str:
    return "attn" if l < cfg["num_dense_layers"] else "moe"


def layer_shapes(cfg: Dict, kind: str) -> Dict[str, tuple]:
    """Per-layer leaf paths and shapes, as the served tree names them."""
    d = _dims(cfg)
    D, Hq, Hkv, hd = d["D"], d["Hq"], d["Hkv"], d["hd"]
    s = {"ln1": (D,), "ln2": (D,), "post/ln1": (D,), "post/ln2": (D,),
         "wq": (D, Hq * hd), "wk": (D, Hkv * hd), "wv": (D, Hkv * hd),
         "wo": (Hq * hd, D), "wg": (D, Hq * hd), "q_norm": (hd,),
         "k_norm": (hd,)}
    if kind == "attn":
        s.update({"mlp/w_gate": (D, d["F"]), "mlp/w_up": (D, d["F"]),
                  "mlp/w_down": (d["F"], D)})
    else:
        E, Fe, S = d["E"], d["Fe"], d["S"]
        s.update({"mlp/router": (D, d["Er"]), "mlp/expert_bias": (d["Er"],),
                  "mlp/w_gate": (E, D, Fe), "mlp/w_up": (E, D, Fe),
                  "mlp/w_down": (E, Fe, D),
                  "mlp/shared/w_gate": (S, D, Fe),
                  "mlp/shared/w_up": (S, D, Fe),
                  "mlp/shared/w_down": (S, Fe, D)})
    return {f"{weights.STACKED_ROOT}/{kind}/{k}": v for k, v in s.items()}


def _edot(x, w, quant):
    """x (S, D) through each expert's (D, F): (S, E, F)."""
    if quant == "fp8":
        x, w = dense._q8(x, -1), dense._q8(w, 1)
    return jnp.einsum("sd,edf->sef", x, w, precision=HI)


def _attention(x, W, c, quant, window, rope):
    Sq = x.shape[0]
    Hq, Hkv, hd, eps = c["Hq"], c["Hkv"], c["hd"], c["eps"]
    h = dense._rms(x, W["ln1"], eps)
    q = dense._dot(h, W["wq"], quant).reshape(Sq, Hq, hd)
    k = dense._dot(h, W["wk"], quant).reshape(Sq, Hkv, hd)
    v = dense._dot(h, W["wv"], quant).reshape(Sq, Hkv, hd)
    gate = dense._dot(h, W["wg"], quant)
    q, k = dense._rms(q, W["q_norm"], eps), dense._rms(k, W["k_norm"], eps)
    if rope:
        q, k = dense._rope(q, c["theta"]), dense._rope(k, c["theta"])
    k = jnp.repeat(k, Hq // Hkv, axis=1)            # q head h reads kv h // G
    v = jnp.repeat(v, Hq // Hkv, axis=1)
    kj = jnp.arange(Sq)[None, :]
    outs = []
    for a in range(0, Sq, Q_BLOCK):
        qi = jnp.arange(a, min(Sq, a + Q_BLOCK))[:, None]
        s = jnp.einsum("qhd,khd->hqk", q[a:a + Q_BLOCK], k,
                       precision=HI) / np.sqrt(hd)
        ok = kj <= qi
        if window:
            ok = ok & (qi - kj < window)
        p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v, precision=HI))
    o = jnp.concatenate(outs, 0).reshape(Sq, Hq * hd) * jax.nn.sigmoid(gate)
    return x + dense._rms(dense._dot(o, W["wo"], quant), W["post/ln1"], eps)


def _moe(h, W, c, quant):
    """The held experts' weighted outputs plus the shared expert."""
    s = jax.nn.sigmoid(dense._dot(h, W["mlp/router"], quant))
    _, sel = jax.lax.top_k(s + W["mlp/expert_bias"], c["K"])
    w = jnp.take_along_axis(s, sel, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * c["scale"]
    held = c["offset"] + jnp.arange(c["E"])
    wt = jnp.sum(jnp.where(sel[:, :, None] == held, w[:, :, None], 0.0), 1)
    g = _edot(h, W["mlp/w_gate"], quant)
    u = _edot(h, W["mlp/w_up"], quant)
    hid = jax.nn.silu(g) * u * wt[:, :, None]         # (S, E, Fe)
    if quant == "fp8":
        hid, wd = dense._q8(hid, -1), dense._q8(W["mlp/w_down"], 1)
    else:
        wd = W["mlp/w_down"]
    y = jnp.einsum("sef,efd->sd", hid, wd, precision=HI)
    gs = _edot(h, W["mlp/shared/w_gate"], quant)
    us = _edot(h, W["mlp/shared/w_up"], quant)
    hs = jax.nn.silu(gs) * us
    if quant == "fp8":
        hs, sd = dense._q8(hs, -1), dense._q8(W["mlp/shared/w_down"], 1)
    else:
        sd = W["mlp/shared/w_down"]
    return y + jnp.einsum("sef,efd->sd", hs, sd, precision=HI)


@functools.partial(jax.jit, static_argnames=("cfgkey", "kind", "window",
                                             "rope", "quant"))
def _layer(x, w, *, cfgkey, kind, window, rope, quant):
    c = dict(cfgkey)
    pre = f"{weights.STACKED_ROOT}/{kind}/"
    W = {k[len(pre):]: v for k, v in w.items()}
    x = _attention(x, W, c, quant, window, rope)
    h = dense._rms(x, W["ln2"], c["eps"])
    if kind == "attn":
        g = dense._dot(h, W["mlp/w_gate"], quant)
        u = dense._dot(h, W["mlp/w_up"], quant)
        y = dense._dot(jax.nn.silu(g) * u, W["mlp/w_down"], quant)
    else:
        y = _moe(h, W, c, quant)
    return x + dense._rms(y, W["post/ln2"], c["eps"])


def _cfgkey(cfg):
    d = _dims(cfg)
    return tuple(sorted(dict(
        Hq=d["Hq"], Hkv=d["Hkv"], hd=d["hd"], E=d["E"],
        K=cfg["num_experts_per_tok"], offset=cfg["expert_offset"],
        scale=float(cfg["route_scale"]), eps=float(cfg["rms_norm_eps"]),
        theta=float(cfg["rope_theta"])).items()))


def final_hidden(cfg: Dict, seed: int, seqs: Sequence[np.ndarray],
                 positions: Sequence[np.ndarray],
                 quant: Optional[str] = None) -> jnp.ndarray:
    """Final-norm hidden states (sum of len(positions), D) at ``positions``
    of each sequence, in order.  Layer by layer over all sequences, each
    padded at its end to a power of two (causal, and routing is per
    token: padding changes nothing before it)."""
    d = _dims(cfg)
    dt = jnp.dtype(cfg["torch_dtype"])
    key = weights.seed_key(seed)
    V = d["V"]
    emb = dense._embed_rows(cfg, key)
    xs = []
    for t in seqs:
        ids = np.zeros((dense._pad_len(len(t)),), np.int32)
        ids[:len(t)] = t
        x = jnp.take(emb, jnp.asarray(ids), axis=0).astype(jnp.float32)
        xs.append(x * np.float32(np.sqrt(d["D"])))
    del emb
    gens = {}
    for kind in ("attn", "moe"):
        shapes = layer_shapes(cfg, kind)
        gens[kind] = jax.jit(functools.partial(
            lambda k, l, shapes: {
                p: weights.layer_leaf(k, p, l, s, dt, V).astype(jnp.float32)
                for p, s in shapes.items()}, shapes=shapes))
    ck = _cfgkey(cfg)
    for l in range(d["L"]):
        kind = _kind(cfg, l)
        sliding = cfg["layer_types"][l] == "sliding_attention"
        w = gens[kind](key, jnp.uint32(l if kind == "attn"
                                       else l - d["n_dense"]))
        xs = [_layer(x, w, cfgkey=ck, kind=kind,
                     window=int(cfg["sliding_window"]) if sliding else 0,
                     rope=sliding, quant=quant) for x in xs]
        del w
    fn = dense._leaf(key, path="final_norm", shape=(d["D"],), dtype=dt,
                     vocab=V).astype(jnp.float32)
    eps = float(cfg["rms_norm_eps"])
    rows = [dense._rms(x[jnp.asarray(pos)], fn, eps)
            for x, pos in zip(xs, positions)]
    return jnp.concatenate(rows, 0)


def gaps(cfg: Dict, seed: int, h_ref: jnp.ndarray, tokens: np.ndarray,
         h_test: Optional[jnp.ndarray] = None,
         quant: Optional[str] = None) -> np.ndarray:
    """Per row, the mean of ``qwen_dense.gaps`` over the row's block: the
    rows in order cut into ``max(1, n // GAP_BLOCK)`` blocks of
    ``GAP_BLOCK`` to ``2 * GAP_BLOCK - 1`` consecutive rows (a block may
    span two requests).  Logs the widest single gap beside it."""
    g = dense.gaps(cfg, seed, h_ref, tokens, h_test=h_test, quant=quant)
    if g.size == 0:
        return g
    blocks = np.array_split(g, max(1, g.size // GAP_BLOCK))
    out = np.concatenate([np.full(b.shape, b.mean(), g.dtype)
                          for b in blocks])
    print(f"[afmoe] gaps{' (' + quant + ')' if quant else ''}: "
          f"{g.size} rows, widest {float(g.max())!r}, mean "
          f"{float(g.mean())!r}, widest {GAP_BLOCK}-row mean "
          f"{float(out.max())!r}", file=sys.stderr, flush=True)
    return out
