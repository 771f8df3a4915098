"""PipeBoost pipeline-parallel serve step as a shard_map lowering.

This is the paper's §4.3 technique in distributed form: stage *i* holds a
contiguous slice of the (stacked) layers; microbatches flow stage→stage via
``lax.ppermute`` over the 'stage' mesh axis on a GPipe belt schedule:

    tick t:  stage s computes microbatch (t - s), then the belt shifts.

All stages execute the same program (SPMD); off-belt stages compute on a
zeros buffer whose results are discarded — the standard JAX collective-
-permute pipeline (cf. GPipe [arXiv:1811.06965] / DAPPLE collective
schedules), TPU-native rather than a torch.distributed port.

Used for the TTFT-critical cold-start prefill (after strategy switching the
engine serves per-replica, so decode rides the standard lowering).  Uniform
layer stacks only (dense/GQA/MoE/SSM/encoder); the hybrid arch pipelines in
the functional engine but is excluded from this lowering (DESIGN.md §5).

See ``docs/ARCHITECTURE.md`` § "Distributed: the pipeline belt".
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models.attention import default_block_q
from repro.launch.mesh import make_pipeline_mesh, pipeline_stages_for
from repro.models import transformer
from repro.models.transformer import _apply_norm


def stage_mesh(n_stages: int, n_data: Optional[int] = None) -> Mesh:
    """('data', 'stage') mesh over a SUBSET of the visible XLA devices.

    ``jax.make_mesh`` wants the axis product to equal the device count;
    elastic repartition needs stage counts that do NOT divide it (3 stages
    on an 8-device host after a 4→3 shrink).  This builds the mesh over the
    first ``n_data * n_stages`` devices instead — the idle remainder simply
    doesn't participate in the belt.  ``n_data`` defaults to the largest
    replica count that fits.
    """
    import numpy as np
    devs = jax.devices()
    if n_data is None:
        n_data = max(1, len(devs) // n_stages)
    need = n_data * n_stages
    if need > len(devs):
        raise ValueError(
            f"stage_mesh({n_stages=}, {n_data=}) needs {need} devices, "
            f"have {len(devs)}")
    arr = np.array(devs[:need]).reshape(n_data, n_stages)
    return Mesh(arr, ("data", "stage"))


def belt_param_spec(path, leaf) -> P:
    """Where the belt holds a parameter: stacked block leaves split over
    'stage' by layer, embed/head/final_norm replicated per stage."""
    names = [getattr(p, "key", None) for p in path]
    if "blocks" in names and leaf.ndim >= 1:
        return P("stage", *([None] * (leaf.ndim - 1)))
    return P()


def _uniform_kind(cfg: ArchConfig) -> str:
    kinds = set(cfg.layer_kinds())
    if len(kinds) != 1:
        raise ValueError(
            f"pipeline lowering needs a uniform layer stack; {cfg.name} has "
            f"{sorted(kinds)} (hybrid pipelines run via core/engine.py)")
    return next(iter(kinds))


def build_pipeline_prefill(cfg: ArchConfig, *, n_stages: int, n_micro: int,
                           mesh: Mesh, seq_len: int,
                           max_len: Optional[int] = None,
                           return_cache: bool = False):
    """Returns f(params, batch) -> last-token logits (B, V), shard_map'ed.

    params['blocks'][kind] leaves are (L, ...) sharded over 'stage' on dim 0;
    embed/head replicated (stage 0 embeds, last stage unembeds — replication
    costs HBM but keeps the belt code uniform; refining this is a recorded
    perf lever).

    ``return_cache=True`` (the engine's overlapped cold-start wiring): f
    additionally returns the per-layer decode state — attn KV sized to
    ``attn_cache_capacity(cfg, max_len)`` (ring-rolled like the standard
    prefill when windowed) or SSM conv/state — stacked (L, B, ...) with the
    layer dim sharded over 'stage' (each stage's KV lives where its
    segment's layers live) and B over 'data'.  Shapes match
    ``transformer.forward(mode="prefill")``'s cache exactly, so the fused
    per-replica decode step consumes it WITHOUT a retrace: the TTFT-
    critical prefill runs on the partial pipeline chain, then decoding
    strategy-switches seamlessly (paper §4.3.3).

    ``batch["last_index"]`` (B,) int32, optional: per-row true last prompt
    token for right-padded (bucketed) prompts — logits are gathered there
    (the serving engine's padded-admission contract, mirroring
    ``transformer.forward(last_index=...)``; attn-only, like bucketing).
    """
    kind = _uniform_kind(cfg)
    L_local = cfg.n_layers // n_stages
    cap = transformer.attn_cache_capacity(cfg, max_len or seq_len)

    def body(params, batch):
        # --- local (per-stage) program -----------------------------------
        stage = jax.lax.axis_index("stage")
        tokens = batch.get("tokens")
        embeds = batch.get("embeds")
        last_index = batch.get("last_index")
        B = (tokens if tokens is not None else embeds).shape[0]
        mb = B // n_micro
        D = cfg.d_model
        blocks = params["blocks"][kind]          # (L_local, ...) local slice

        positions = jnp.broadcast_to(jnp.arange(seq_len)[None, :],
                                     (mb, seq_len))

        def embed_mb(i):
            if tokens is not None:
                sl = jax.lax.dynamic_slice_in_dim(tokens, i * mb, mb, 0)
                return jnp.take(params["embed"], sl, axis=0)
            return jax.lax.dynamic_slice_in_dim(embeds, i * mb, mb, 0)

        def run_local_layers(x):
            def layer(x, p_l):
                if kind == "ssm":
                    from repro.models import mamba2
                    x, st = mamba2.ssm_block_fwd(cfg, p_l, x)
                    return x, (st if return_cache else None)
                x, kv, _ = transformer.attn_layer_fwd(
                    cfg, p_l, x, positions,
                    kv_write=cap if return_cache else None)
                return x, (kv if return_cache else None)
            x, st = jax.lax.scan(layer, x, blocks)
            return x, st

        n_ticks = n_micro + n_stages - 1
        logits_buf = jnp.zeros((n_micro, mb, cfg.padded_vocab), jnp.float32)
        # per-stage decode-state buffer: (L_local, n_micro, mb, ...) — each
        # stage only materializes its OWN layers' state (1/n_stages of it)
        if not return_cache:
            st_buf = {}
        elif kind == "ssm":
            ch = cfg.d_inner + 2 * cfg.ssm_state
            st_buf = {
                "conv": jnp.zeros((L_local, n_micro, mb, cfg.ssm_conv - 1,
                                   ch), jnp.dtype(cfg.dtype)),
                "state": jnp.zeros((L_local, n_micro, mb, cfg.ssm_heads,
                                    cfg.ssm_head_dim, cfg.ssm_state),
                                   jnp.float32),
            }
        else:
            hd = cfg.resolved_head_dim
            st_buf = {
                "k": jnp.zeros((L_local, n_micro, mb, cap, cfg.n_kv_heads,
                                hd), jnp.dtype(cfg.dtype)),
                "v": jnp.zeros((L_local, n_micro, mb, cap, cfg.n_kv_heads,
                                hd), jnp.dtype(cfg.dtype)),
            }

        def tick(carry, t):
            belt, logits_buf, st_buf = carry     # belt: (mb, S, D)
            mb_idx = t - stage                   # microbatch this stage sees
            feed = jnp.clip(mb_idx, 0, n_micro - 1)
            x_in = jnp.where(jnp.equal(stage, 0)[..., None, None],
                             embed_mb(feed), belt)
            x_out, st = run_local_layers(x_in)
            # last stage: final norm + last-token unembed (at the row's
            # true last prompt position when the batch is right-padded)
            if last_index is not None:
                li = jax.lax.dynamic_slice_in_dim(last_index, feed * mb,
                                                  mb, 0)
                x_last = x_out[jnp.arange(mb), li][:, None, :]
            else:
                x_last = x_out[:, -1:, :]
            xl = _apply_norm(cfg, params["final_norm"], x_last)
            head = params["embed"].T if cfg.tie_embeddings \
                else params["lm_head"]
            lg = jnp.einsum("bsd,dv->bsv", xl, head,
                            preferred_element_type=jnp.float32)[:, 0]
            on_belt = (mb_idx >= 0) & (mb_idx < n_micro)
            is_mine = jnp.equal(stage, n_stages - 1) & on_belt
            logits_buf = jax.lax.cond(
                is_mine,
                lambda b: jax.lax.dynamic_update_slice_in_dim(
                    b, lg[None], feed, 0),
                lambda b: b, logits_buf)
            if return_cache:
                new = ({"conv": st[0], "state": st[1]} if kind == "ssm"
                       else {"k": st[0], "v": st[1]})
                # off-belt ticks compute on the zeros belt — their state is
                # garbage and must not land in the buffer
                st_buf = jax.lax.cond(
                    on_belt,
                    lambda b: {key: jax.lax.dynamic_update_slice_in_dim(
                        b[key], new[key][:, None].astype(b[key].dtype),
                        feed, 1) for key in b},
                    lambda b: b, st_buf)
            # belt shift: stage s -> s+1 (last stage's output is dropped
            # by feeding zeros around the ring into stage 0, which ignores it)
            nxt = jax.lax.ppermute(
                x_out, "stage",
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (nxt, logits_buf, st_buf), None

        belt0 = jnp.zeros((mb, seq_len, D), jnp.dtype(cfg.dtype))
        (_, logits_buf, st_buf), _ = jax.lax.scan(
            tick, (belt0, logits_buf, st_buf), jnp.arange(n_ticks))
        # only the last stage wrote real logits; share them along the belt
        logits_buf = jax.lax.psum(logits_buf, "stage")
        logits = logits_buf.reshape(B, cfg.padded_vocab)
        if not return_cache:
            return logits
        state = {("ssm" if kind == "ssm" else "attn"): {
            key: st_buf[key].reshape((L_local, B) + st_buf[key].shape[3:])
            for key in st_buf}}
        return logits, state

    # --- shard_map wiring --------------------------------------------------
    def f(params, batch):
        pspecs = jax.tree_util.tree_map_with_path(belt_param_spec, params)
        bspecs = jax.tree.map(
            lambda a: P("data", *([None] * (a.ndim - 1))), batch)
        if return_cache:
            # state leaves: layer dim over 'stage', batch dim over 'data'
            if kind == "ssm":
                state_specs = {"ssm": {"conv": P("stage", "data", None, None),
                                       "state": P("stage", "data", None,
                                                  None, None)}}
            else:
                kv_spec = P("stage", "data", None, None, None)
                state_specs = {"attn": {"k": kv_spec, "v": kv_spec}}
            out_specs = (P("data", None), state_specs)
        else:
            out_specs = P("data", None)
        with default_block_q(512):
            return jax.shard_map(
                body, mesh=mesh,
                in_specs=(pspecs, bspecs),
                out_specs=out_specs,
                check_vma=False,
            )(params, batch)

    return f


def build_pipeline_prefill_seqchunk(cfg: ArchConfig, *, n_stages: int,
                                    n_chunks: int, mesh: Mesh,
                                    seq_len: int):
    """TeraPipe-style pipeline prefill: microbatches are SEQUENCE CHUNKS of
    the same requests [arXiv:2102.07988], not batch splits.

    With tiny per-replica batches (the cold-start regime), batch-split
    GPipe has n_micro <= B_local and drowns in bubbles (utilization
    n_micro/(n_micro+S-1)).  Chunking the sequence gives n_chunks = S/chunk
    microbatches regardless of batch: each stage keeps the KV of its local
    layers for already-seen chunks and attends causally (q_offset +
    kv_valid_len); the belt carries one (B, chunk, D) block per tick —
    also ~n_chunks x smaller hidden-state hops.  See EXPERIMENTS.md §Perf.
    """
    kind = _uniform_kind(cfg)
    if kind not in ("attn", "moe"):
        raise ValueError("seq-chunk pipeline needs attention KV semantics")
    assert seq_len % n_chunks == 0
    chunk = seq_len // n_chunks
    hd = cfg.resolved_head_dim

    def body(params, batch):
        stage = jax.lax.axis_index("stage")
        tokens = batch.get("tokens")
        embeds = batch.get("embeds")
        B = (tokens if tokens is not None else embeds).shape[0]
        D = cfg.d_model
        blocks = params["blocks"][kind]
        L_local = jax.tree.leaves(blocks)[0].shape[0]

        def embed_chunk(i):
            if tokens is not None:
                sl = jax.lax.dynamic_slice_in_dim(tokens, i * chunk, chunk, 1)
                return jnp.take(params["embed"], sl, axis=0)
            return jax.lax.dynamic_slice_in_dim(embeds, i * chunk, chunk, 1)

        n_ticks = n_chunks + n_stages - 1
        logits_buf = jnp.zeros((B, cfg.padded_vocab), jnp.float32)
        kv0 = jnp.zeros((L_local, 2, B, seq_len, cfg.n_kv_heads, hd),
                        jnp.dtype(cfg.dtype))

        def tick(carry, t):
            belt, kv, logits_buf = carry
            ci = jnp.clip(t - stage, 0, n_chunks - 1)     # chunk index here
            x_in = jnp.where(jnp.equal(stage, 0), embed_chunk(ci), belt)
            q_off = ci * chunk
            positions = q_off + jnp.broadcast_to(jnp.arange(chunk)[None, :],
                                                 (B, chunk))
            if cfg.mrope:  # text-like stub stream: t=h=w position ids
                positions = jnp.broadcast_to(positions[..., None],
                                             (B, chunk, 3))

            def layer(x, per):
                p_l, kv_l = per
                from repro.models.transformer import (_apply_norm,
                                                      _ffn_branch,
                                                      _project_qkv, _rope)
                from repro.models import attention as attn_lib
                h = _apply_norm(cfg, p_l["ln1"], x)
                q, k, v = _project_qkv(cfg, p_l, h)
                q = _rope(cfg, q, positions)
                k = _rope(cfg, k, positions)
                kv_l = jax.lax.dynamic_update_slice(
                    kv_l, jnp.stack([k, v]), (0, 0, q_off, 0, 0))
                # causal over [0, q_off + chunk): prefix chunks full,
                # current chunk causal — one blocked pass over the buffer
                o = attn_lib.finalize_partial(
                    attn_lib.attention_partial(
                        q, kv_l[0], kv_l[1], causal=True, window=0,
                        q_offset=q_off, k_offset=0,
                        kv_valid_len=q_off + chunk,
                        block_k=max(chunk, 1024)), q.dtype)
                o = o.reshape(B, chunk, -1) @ p_l["wo"]
                x, _, _ = _ffn_branch(cfg, p_l, x + o)
                return x, kv_l

            x_out, kv = jax.lax.scan(layer, x_in, (blocks, kv))
            # last stage, last chunk: final norm + last-token unembed
            xl = _apply_norm(cfg, params["final_norm"], x_out[:, -1:, :])
            head = params["embed"].T if cfg.tie_embeddings \
                else params["lm_head"]
            lg = jnp.einsum("bsd,dv->bsv", xl, head,
                            preferred_element_type=jnp.float32)[:, 0]
            is_last = (jnp.equal(stage, n_stages - 1)
                       & jnp.equal(t - stage, n_chunks - 1))
            logits_buf = jnp.where(is_last, lg, logits_buf)
            nxt = jax.lax.ppermute(
                x_out, "stage",
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (nxt, kv, logits_buf), None

        belt0 = jnp.zeros((B, chunk, D), jnp.dtype(cfg.dtype))
        (_, _, logits_buf), _ = jax.lax.scan(
            tick, (belt0, kv0, logits_buf), jnp.arange(n_ticks))
        logits_buf = jax.lax.psum(logits_buf, "stage")
        return logits_buf

    def f(params, batch):
        pspecs = jax.tree_util.tree_map_with_path(belt_param_spec, params)
        bspecs = jax.tree.map(
            lambda a: P("data", *([None] * (a.ndim - 1))), batch)
        return jax.shard_map(body, mesh=mesh, in_specs=(pspecs, bspecs),
                             out_specs=P("data", None), check_vma=False,
                             )(params, batch)

    return f


def build_pipeline_cell(cfg: ArchConfig, shape: ShapeConfig, *,
                        total_chips: int = 256, n_micro: Optional[int] = None,
                        seq_chunk: bool = False) -> Tuple[Any, tuple]:
    """Dry-run entry: returns (jitted fn, arg structs) for the pipeline
    prefill of one (arch x shape) cell."""
    if shape.kind != "prefill":
        raise ValueError("pipeline lowering targets the prefill (TTFT) step")
    n_stages = pipeline_stages_for(cfg.n_layers)
    B = shape.global_batch
    n_data = total_chips // n_stages
    while n_data > 1 and B % n_data != 0:
        n_data //= 2        # idle replicas rather than unshardable batch
    mesh = make_pipeline_mesh(n_stages, total=n_data * n_stages)
    n_micro = n_micro or max(2, min(8, B // max(1, n_data)))

    params_struct = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0),
                                        jnp.bfloat16))
    if cfg.family in ("audio", "vlm"):
        batch_struct = {"embeds": jax.ShapeDtypeStruct(
            (B, shape.seq_len, cfg.d_model), jnp.bfloat16)}
    else:
        batch_struct = {"tokens": jax.ShapeDtypeStruct((B, shape.seq_len),
                                                       jnp.int32)}

    if seq_chunk:
        n_chunks = max(n_stages, 8)
        f = build_pipeline_prefill_seqchunk(
            cfg, n_stages=n_stages, n_chunks=n_chunks, mesh=mesh,
            seq_len=shape.seq_len)
    else:
        f = build_pipeline_prefill(cfg, n_stages=n_stages, n_micro=n_micro,
                                   mesh=mesh, seq_len=shape.seq_len)

    pshard = jax.tree_util.tree_map_with_path(
        lambda pa, l: NamedSharding(mesh, belt_param_spec(pa, l)),
        params_struct)
    bshard = jax.tree.map(
        lambda a: NamedSharding(mesh, P("data", *([None] * (a.ndim - 1)))),
        batch_struct)
    fn = jax.jit(f, in_shardings=(pshard, bshard),
                 out_shardings=NamedSharding(mesh, P("data", None)))
    return fn, (params_struct, batch_struct)
