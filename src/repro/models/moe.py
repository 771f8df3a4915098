"""Mixture-of-Experts FFN — dropless, routed over every expert of the
deployment, computing only the experts held here.

Expert parallelism, one chip's share: the router scores all
``cfg.n_router_experts`` experts and picks ``top_k`` per token; this layer
holds experts ``[expert_offset, expert_offset + n_experts)`` and computes
their part of the result for the tokens routed to them.  What experts held
elsewhere would add is left out (their chips add it in a deployment);
shared experts run on every token.  With ``router_experts`` 0 every expert
is held and the layer is the whole MoE FFN.

Routing is per token: softmax scores (Qwen / Mixtral) or sigmoid scores
with an optional selection-only bias (AFMoE), then top-k, and the chosen
scores renormalized and scaled by ``route_scale``.  No capacity: every
(token, held expert) pair is computed, so the layer matches a reference
exactly and a token's result never depends on the other rows of the batch.
Tokens outside ``token_mask`` (padding of a bucketed prefill, free decode
slots) are routed nowhere.

The pairs are sorted by expert and run as one grouped FFN over the held
experts: the Pallas ``moe_gmm`` kernel on TPU, which fetches only the
experts some pair was routed to, and ``jax.lax.ragged_dot`` elsewhere
(differentiable, for training).  Each call returns its counts: ``pairs``
(token-expert pairs computed here) and ``experts_hit`` (held experts with
at least one pair).

In a layer scan the routed experts' weights are the whole stacks of the
model's MoE layers, and the layer's params carry its index into them as
``layer``: the kernel reads them there in place, where a scanned slice
would be copied whole before each call.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init

# Grouped-FFN backend: "auto" is the Pallas kernel on TPU and ragged_dot
# elsewhere; chosen at trace time, like the decode-attention backend.
GMM_IMPL = ["auto"]      # "auto" | "pallas" | "xla"

# the routed experts' weights, (G, D, F) / (G, F, D) a layer
EXPERTS = ("w_gate", "w_up", "w_down")


def init_moe_mlp(key, cfg, dtype) -> Dict:
    D, E, F = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 8)
    p = {
        "router": dense_init(ks[0], (D, cfg.n_router_experts), dtype,
                             scale=0.02),
        "w_gate": dense_init(ks[1], (E, D, F), dtype),
        "w_up": dense_init(ks[2], (E, D, F), dtype),
        "w_down": dense_init(ks[3], (E, F, D), dtype),
    }
    if cfg.router_bias:
        p["expert_bias"] = jnp.zeros((cfg.n_router_experts,), dtype)
    if cfg.n_shared_experts:
        S = cfg.n_shared_experts
        p["shared"] = {
            "w_gate": dense_init(ks[4], (S, D, F), dtype),
            "w_up": dense_init(ks[5], (S, D, F), dtype),
            "w_down": dense_init(ks[6], (S, F, D), dtype),
        }
        if cfg.shared_expert_gate:
            p["shared"]["gate"] = dense_init(ks[7], (D, 1), dtype, scale=0.02)
    return p


def route(cfg, p, xt) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """xt (T, D) -> (experts (T, K) int32 over the router's width, their
    weights (T, K) f32, the scores as probabilities (T, E) f32)."""
    logits = jnp.dot(xt, p["router"], preferred_element_type=jnp.float32)
    if cfg.router_score == "sigmoid":
        s = jax.nn.sigmoid(logits)
        probs = s / jnp.sum(s, axis=-1, keepdims=True)
    else:
        s = probs = jax.nn.softmax(logits, axis=-1)
    pick = s + p["expert_bias"].astype(jnp.float32) if "expert_bias" in p \
        else s
    _, sel = jax.lax.top_k(pick, cfg.top_k)
    w = jnp.take_along_axis(s, sel, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * cfg.route_scale
    return sel.astype(jnp.int32), w, probs


def _use_pallas() -> bool:
    impl = GMM_IMPL[0]
    if impl == "auto":
        return jax.default_backend() == "tpu"
    return impl == "pallas"


def grouped_ffn(rows, experts, layer, group_sizes, act):
    """Rows sorted by held expert through their expert's gated FFN, the
    experts those of ``layer`` in the stacked ``experts`` (w_gate, w_up,
    w_down); rows at or past ``sum(group_sizes)`` are unspecified."""
    if _use_pallas():
        from repro.kernels import ops
        return ops.moe_gmm(rows, *experts, group_sizes, layer, act=act)
    # as the kernel computes: f32 accumulation, the hidden rounded to the
    # weights' dtype for the down projection
    f32 = jnp.float32
    wg, wu, wd = (w[layer] for w in experts)
    g = jax.lax.ragged_dot(rows, wg, group_sizes, preferred_element_type=f32)
    u = jax.lax.ragged_dot(rows, wu, group_sizes, preferred_element_type=f32)
    h = (act(g) * u).astype(wd.dtype)
    return jax.lax.ragged_dot(h, wd, group_sizes,
                              preferred_element_type=f32).astype(rows.dtype)


def moe_mlp(cfg, p, x, act, token_mask: Optional[jnp.ndarray] = None
            ) -> Tuple[jnp.ndarray, jnp.ndarray, Dict[str, jnp.ndarray]]:
    """x: (B, S, D) -> (y (B, S, D), aux_loss, counts).

    ``p`` holds one layer's routed experts, or with ``layer`` (its index)
    the whole stacks of every MoE layer.  ``token_mask`` (B, S) bool:
    tokens routed at all (None = every token).  ``counts``: ``pairs`` and
    ``experts_hit`` of this call, int32."""
    B, S, D = x.shape
    T, K, E = B * S, cfg.top_k, cfg.n_experts
    xt = x.reshape(T, D)
    sel, w, probs = route(cfg, p, xt)
    local = sel - cfg.expert_offset
    held = (local >= 0) & (local < E)
    if token_mask is not None:
        held = held & token_mask.reshape(T, 1)
    # pairs sorted by held expert; the rest (group E) go to the end
    gid = jnp.where(held, local, E).reshape(T * K)
    order = jnp.argsort(gid, stable=True)
    group_sizes = jnp.bincount(gid, length=E + 1)[:E].astype(jnp.int32)
    if "layer" in p:
        experts, layer = [p[k] for k in EXPERTS], p["layer"]
    else:
        experts, layer = [p[k][None] for k in EXPERTS], 0
    out = grouped_ffn(jnp.take(xt, order // K, axis=0), experts, layer,
                      group_sizes, act)
    out = jnp.take(out, jnp.argsort(order), axis=0).reshape(T, K, D)
    y = jnp.sum(jnp.where(held[..., None], w[..., None] * out, 0.0), axis=1)
    if "shared" in p:
        sp = p["shared"]
        hs = act(jnp.einsum("td,sdf->tsf", xt, sp["w_gate"])) * \
            jnp.einsum("td,sdf->tsf", xt, sp["w_up"])
        ys = jnp.einsum("tsf,sfd->td", hs, sp["w_down"])
        if "gate" in sp:
            ys = ys * jax.nn.sigmoid(
                (xt @ sp["gate"]).astype(jnp.float32)).astype(ys.dtype)
        y = y + ys
    # load-balance aux loss (Switch-style) over the router's experts
    En = cfg.n_router_experts
    frac_tokens = jnp.mean(jax.nn.one_hot(sel, En, dtype=jnp.float32
                                          ).sum(1), axis=0)
    aux = En * jnp.sum(frac_tokens * jnp.mean(probs, axis=0)) / K
    counts = {"pairs": jnp.sum(group_sizes),
              "experts_hit": jnp.sum(group_sizes > 0).astype(jnp.int32)}
    return y.astype(x.dtype).reshape(B, S, D), aux, counts
