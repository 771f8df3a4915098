#!/usr/bin/env python3
"""Chip smoke test: drive the serving path once on a TPU at the full
published width of qwen3-1.7b (28 layers, d_model 2048, vocab 151936,
bf16, random weights from a fixed seed) and check what comes out.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the pipeline belt over four chips

One chip runs four phases in one process:

  server    ``serve.main``: PipeBoost cold start, bucketed admission,
            donated fused decode; every request returns all its tokens.
  mosaic    the compiled decode step that served holds a Mosaic kernel
            (``tpu_custom_call``): not interpret mode, not the XLA path.
  cluster   ``serve.main --cluster``: two servers, a whole-server crash,
            re-route and rejoin; every request completes.
  kernels   Pallas and XLA decode attention give the same logits (within
            a bf16 tolerance) and the same greedy tokens.

``--four-chips`` runs only the shard_map belt over four real chips and
compares it with the single-device prefill of the same batch.

There is no CPU fallback: without a TPU the script exits non-zero before
any phase.  The last line of stdout is one JSON object naming the device.
Wall times printed on the way are smoke timings of one cold run, not
metrics.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import get_arch  # noqa: E402
from repro.core.engine import PipeBoostEngine  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.models.attention import decode_attn_impl  # noqa: E402
from repro.serving.engine import ServeRequest, ServingEngine  # noqa: E402

ARCH = "qwen3-1.7b"
SEED = 0
# logits may differ by this share of their largest magnitude: bf16
# activations rounded differently through every layer
LOGIT_RTOL = 3e-2


class SmokeError(AssertionError):
    pass


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeError(what)


def _tolerance(ref) -> float:
    return LOGIT_RTOL * float(np.max(np.abs(np.asarray(ref, np.float32))))


def _logits_close(got, ref, what: str) -> float:
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    _check(got.shape == ref.shape, f"{what}: shapes {got.shape} != "
                                   f"{ref.shape}")
    _check(bool(np.isfinite(got).all() and np.isfinite(ref).all()),
           f"{what}: non-finite logits")
    err = float(np.max(np.abs(got - ref)))
    tol = _tolerance(ref)
    _check(err <= tol, f"{what}: max |logit diff| {err:.4g} > {tol:.4g}")
    return err


def _greedy_agree(tokens, ref, what: str) -> int:
    """Greedy ``tokens`` against the reference logits ``ref`` (..., V).

    A token must be the reference's argmax, or tie with it: its reference
    logit within the bf16 tolerance of the best.  With random weights the
    top two logits of 151936 can sit closer than the rounding differences
    of two correct bf16 paths (seen on a v5e: top-2 margin 0.0098, logit
    difference 0.062), and there either choice is right.  Returns how
    many tokens differ from the argmax."""
    ref = np.asarray(ref, np.float32)
    tokens = np.asarray(tokens)
    best = ref.argmax(-1)
    gap = ref.max(-1) - np.take_along_axis(ref, tokens[..., None], -1)[..., 0]
    tol = _tolerance(ref)
    _check(bool((gap <= tol).all()),
           f"{what}: greedy tokens {tokens.tolist()} vs {best.tolist()}; "
           f"one sits {float(gap.max()):.4g} below the best logit, more "
           f"than the tolerance {tol:.4g}")
    return int((tokens != best).sum())


def _agree(got, ref, what: str) -> str:
    """Logits within the tolerance and greedy tokens that agree; returns
    a line that says by how much."""
    err = _logits_close(got, ref, what)
    tokens = np.asarray(got, np.float32).argmax(-1)
    n = _greedy_agree(tokens, ref, what)
    return (f"{what}: max |logit diff| {err:.4g} (tolerance "
            f"{_tolerance(ref):.4g}); {n} of {tokens.size} greedy tokens "
            f"differ, each at a near-tie")


def _prompts(cfg, n: int, length: int) -> jnp.ndarray:
    rng = np.random.default_rng(SEED)
    return jnp.asarray(rng.integers(0, cfg.vocab_size, size=(n, length)),
                       jnp.int32)


def phase_server(cfg, n_requests: int = 4, new_tokens: int = 8):
    """One server through ``serve.main``; returns its ServingEngine."""
    srv = serve.main(["--requests", str(n_requests), "--slots", "4",
                      "--new-tokens", str(new_tokens), "--devices", "4"],
                     cfg=cfg)
    done = srv.completed
    _check(len(done) == n_requests,
           f"served {len(done)} of {n_requests} requests")
    for r in done:
        _check(len(r.generated) == new_tokens,
               f"req{r.rid} returned {len(r.generated)} of {new_tokens} "
               f"tokens")
    print(f"[smoke] server compile counts: {srv.batcher.compile_stats()}")
    return srv


def phase_mosaic(batcher) -> None:
    """The decode step that served runs the Mosaic decode kernel: its
    fused decode step, compiled for the arguments its last step ran
    with, holds a ``tpu_custom_call``."""
    hlo = batcher._decode_fused.lower(
        batcher.params, batcher._dev_tokens, batcher._dev_active,
        batcher.cache).compile().as_text()
    n = hlo.count("tpu_custom_call")
    _check(n > 0, "the compiled decode step holds no tpu_custom_call: decode "
                  "attention ran in interpret mode or on the XLA path")
    print(f"[smoke] compiled decode step: {n} tpu_custom_call sites")


def phase_cluster(cfg):
    """Two servers through ``serve.main --cluster``; one crashes whole,
    its requests re-route to the survivor, and it rejoins."""
    router = serve.main(["--cluster", "--servers", "2", "--max-servers", "2",
                         "--requests", "8", "--slots", "2", "--new-tokens",
                         "8", "--crash-at", "1", "--devices", "4"], cfg=cfg)
    s = router.metrics.summary()
    kinds = {kind for _, kind, _ in router.metrics.events}
    _check(int(s["n_completed"]) == 8,
           f"cluster completed {s['n_completed']} of 8")
    _check("crash" in kinds and s["n_rerouted"] >= 1,
           f"no crash re-route in the event log: {sorted(kinds)}")
    _check("rejoin" in kinds, f"no rejoin in the event log: {sorted(kinds)}")
    return router


def phase_kernels(cfg, n_steps: int = 4, max_len: int = 64) -> None:
    """Decode the same prompts with Pallas and with XLA decode attention.

    Pallas decodes greedily; XLA is fed the same tokens, so one flip
    cannot cascade.  Logits must agree within ``LOGIT_RTOL`` and the
    greedy tokens at every step (``_greedy_agree``)."""
    params = T.init_params(cfg, jax.random.PRNGKey(SEED))
    tokens = _prompts(cfg, 4, 8)
    logits, cache0 = jax.jit(lambda p, b: T.forward(
        cfg, p, b, mode="prefill", max_len=max_len))(params,
                                                     {"tokens": tokens})
    feed = [jnp.argmax(logits, -1).astype(jnp.int32)]
    out = {}
    for impl in ("pallas", "xla"):
        with decode_attn_impl(impl):
            step = jax.jit(lambda p, t, c: T.decode_step(
                cfg, p, {"tokens": t}, c))
            cache, lgs = cache0, []
            for i in range(n_steps):
                lg, cache = step(params, feed[i], cache)
                lgs.append(np.asarray(lg, np.float32))
                if impl == "pallas":
                    feed.append(jnp.argmax(lg, -1).astype(jnp.int32))
        out[impl] = np.stack(lgs)
    print("[smoke] " + _agree(out["pallas"], out["xla"],
                              f"pallas vs xla decode, {n_steps} steps x "
                              f"{tokens.shape[0]} prompts"))


def phase_four_chips(cfg, n_devices: int = 4, max_len: int = 64) -> None:
    """The shard_map belt over ``n_devices`` real devices against the
    single-device prefill of the same batch: logits, greedy tokens,
    ContinuousBatcher pipeline admission, and the strategy switch
    without a decode retrace."""
    params = T.init_params(cfg, jax.random.PRNGKey(SEED))
    B, S = 4, 16
    batch = {"tokens": _prompts(cfg, B, S)}

    pipe = PipeBoostEngine(cfg, params, n_devices=n_devices, max_len=max_len)
    _check(pipe.enable_pipeline_prefill(), "pipeline prefill refused")
    _check(pipe._pipe_n_stages == n_devices,
           f"{pipe._pipe_n_stages} stages, not {n_devices}")
    pipe.load_round()
    _check(pipe.ready and not pipe.fully_loaded,
           "one load round should make a chain, not the whole model")

    # the stages' decode state lands on every device, L / n_stages layers
    # each, before the hand-off to the per-replica decode step
    _, state = pipe._pipeline_prefill_fn(B, S)(params, batch)
    k = state["attn"]["k"]
    homes = {s.device for s in k.addressable_shards}
    _check(len(homes) == n_devices,
           f"belt state sits on {len(homes)} devices, not {n_devices}")
    _check(all(s.data.shape[0] == cfg.n_layers // n_devices
               for s in k.addressable_shards),
           f"stage shards {[s.data.shape for s in k.addressable_shards]}")
    print(f"[smoke] belt KV on {sorted(d.id for d in homes)}, "
          f"{cfg.n_layers // n_devices} layers each")

    single = PipeBoostEngine(cfg, params, n_devices=n_devices,
                             max_len=max_len)
    while single.load_round():
        pass
    single.maybe_switch_strategy(1.0)
    ref_logits = single.prefill(batch)
    _check(single.prefill_backend_used == "single", "reference not single")
    got_logits = pipe.prefill(batch)
    _check(pipe.prefill_backend_used == "pipeline",
           f"prefill ran {pipe.prefill_backend_used}, not the belt")
    print("[smoke] " + _agree(got_logits, ref_logits,
                              "belt vs single prefill"))
    # decode continues from the belt's cache like from the single one's;
    # both are fed the reference's tokens, so a near-tie cannot cascade
    refs = [np.asarray(ref_logits, np.float32)]
    for i in range(2):
        tok = jnp.asarray(refs[-1].argmax(-1), jnp.int32)
        got, ref = pipe.decode(tok), single.decode(tok)
        print("[smoke] " + _agree(got, ref, f"decode step {i} after the "
                                            f"belt vs after single"))
        refs.append(np.asarray(ref, np.float32))

    # ContinuousBatcher admission through the belt while still mid-load;
    # its first tokens are held to the single prefill, its second to the
    # single decode step wherever the first was the reference's
    srv = ServingEngine(cfg, params, n_slots=B, max_len=max_len)
    srv.batcher.set_pipeline_prefill(pipe.serving_pipeline_prefill,
                                     fits=pipe.serving_pipeline_fits)
    srv.batcher.prefill_backend = (
        lambda: "pipeline" if pipe.strategy == "pipeline" else "single")
    prompts = np.asarray(batch["tokens"])
    for i in range(B):
        srv.submit(ServeRequest(i, prompts[i], max_new_tokens=2))
    done = sorted(srv.run(), key=lambda r: r.rid)
    _check(srv.batcher.n_prefill_pipeline == B,
           f"{srv.batcher.n_prefill_pipeline} of {B} admissions on the belt")
    got = np.asarray([r.generated for r in done])
    _check(got.shape == (B, 2), f"batcher tokens {got.tolist()}")
    n = _greedy_agree(got[:, 0], refs[0], "batcher first tokens")
    same = got[:, 0] == refs[0].argmax(-1)
    if same.any():
        n += _greedy_agree(got[same, 1], refs[1][same],
                           "batcher second tokens")
    print(f"[smoke] batcher: {srv.batcher.n_prefill_pipeline} of {B} "
          f"admissions on the belt; {B + int(same.sum())} tokens checked "
          f"against the single-device engine, {n} differ at a near-tie")

    # strategy switch: the post-switch prefill lowers single, and the
    # decode step is still the one compile
    while pipe.load_round():
        pass
    _check(pipe.maybe_switch_strategy(request_rate=1.0), "no switch")
    pipe.prefill(batch)
    _check(pipe.prefill_backend_used == "single", "post-switch backend")
    pipe.decode(tok)
    cs = pipe.compile_stats()
    print(f"[smoke] engine compile counts: {cs}")
    _check(cs["decode_compiles"] == 1, f"decode recompiled: {cs}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the pipeline belt over four chips and "
                         "the single-device prefill it is compared with")
    args = ap.parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{dev.platform!r}); nothing was run")
    if args.four_chips and len(devices) < 4:
        raise SystemExit(f"chip_smoke --four-chips: {len(devices)} "
                         f"device(s), need 4")
    print(f"[smoke] device {dev.device_kind} x{len(devices)}; compile "
          f"cache {enable_compile_cache()}")
    cfg = get_arch(ARCH)

    def timed(name, fn, *a):
        t0 = time.perf_counter()
        res = fn(*a)
        print(f"[smoke] phase {name}: ok ({time.perf_counter() - t0:.1f}s "
              f"wall, smoke timing, not a metric)")
        return res

    if args.four_chips:
        timed("four_chips", phase_four_chips, cfg)
    else:
        srv = timed("server", phase_server, cfg)
        timed("mosaic", phase_mosaic, srv.batcher)
        del srv
        timed("cluster", phase_cluster, cfg)
        timed("kernels", phase_kernels, cfg)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
