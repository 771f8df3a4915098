"""Operations and bytes that a request or a kernel call needs.

Counted from the configuration's published shapes, never from what the
program computes: padded rows, padded buckets, free slots and cache
positions past a slot's length are no work.  Matmul operations only
(2 per multiply-add); norms, rotary, softmax and biases are left out.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterable


@dataclass(frozen=True)
class Dims:
    D: int
    Hq: int
    Hkv: int
    hd: int
    F: int
    V: int
    L: int
    act_bytes: int = 2          # bytes of one served value (bf16)


def dims(cfg: Dict) -> Dims:
    """Shapes of a configuration file (Hugging Face keys)."""
    D = cfg["hidden_size"]
    Hq = cfg["num_attention_heads"]
    return Dims(D=D, Hq=Hq, Hkv=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim") or D // Hq,
                F=cfg["intermediate_size"], V=cfg["vocab_size"],
                L=cfg["num_hidden_layers"],
                act_bytes=2 if cfg["torch_dtype"] in ("bfloat16", "float16")
                else 4)


def layer_matmul_params(d: Dims) -> int:
    """Weights one token multiplies in one decoder layer (gated MLP)."""
    return (d.D * d.Hq * d.hd + 2 * d.D * d.Hkv * d.hd + d.Hq * d.hd * d.D
            + 3 * d.D * d.F)


def attn_flops(d: Dims, n_keys: int) -> int:
    """QK^T and PV of one query over ``n_keys`` keys, all layers."""
    return 4 * d.L * d.Hq * d.hd * n_keys


def head_flops(d: Dims) -> int:
    return 2 * d.D * d.V


def prefill_flops(d: Dims, n: int) -> int:
    """A prompt of ``n`` tokens: every token through every layer, causal
    attention over n(n+1)/2 pairs, and the head for the last token only
    (the program gathers the last position's logits)."""
    return (2 * d.L * layer_matmul_params(d) * n
            + attn_flops(d, n * (n + 1) // 2) + head_flops(d))


def decode_flops(d: Dims, cache_lens: Iterable[int]) -> int:
    """One decode step of the active slots; ``cache_lens`` are the tokens
    each slot holds before the step (the new one is attended too)."""
    per = 2 * d.L * layer_matmul_params(d) + head_flops(d)
    return sum(per + attn_flops(d, n + 1) for n in cache_lens)


def decode_attn_call(d: Dims, cache_lens: Iterable[int]):
    """(flops, bytes) one layer's decode-attention call needs: q and the
    output once, and K and V of each active slot's valid positions plus
    the new token, each read once."""
    flops = 0
    nbytes = 0
    for n in cache_lens:
        flops += 4 * d.Hq * d.hd * (n + 1)
        nbytes += ((n + 1) * 2 * d.Hkv * d.hd + 2 * d.Hq * d.hd) * d.act_bytes
    return flops, nbytes


def roofline_s(flops: float, nbytes: float, peak: Dict) -> float:
    """Least time the chip needs: the larger of compute and memory time."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
