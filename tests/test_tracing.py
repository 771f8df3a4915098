"""The serving path's tracer: off by default, a span tree on the
profiler's clock, the compile and gc hooks, and the spans of admission
and decode on a tiny model, on one device and on the four-stage
pipeline belt."""
import gc
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.configs.base import get_arch
from repro.models import transformer as T
from repro.serving.engine import ServeRequest, ServingEngine


@pytest.fixture(autouse=True)
def _fresh_tracer():
    tracing.disable()
    tracing.clear()
    yield
    tracing.disable()
    tracing.clear()


def test_off_records_nothing_and_opens_no_annotation(monkeypatch):
    opened = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda *a, **k: opened.append(a))
    with tracing.span("pb.decode", n_active=3) as sp:
        assert not sp
        sp.end(1.0, rids=[1])
        tracing.count("x")
    assert tracing.spans() == [] and tracing.counters() == {}
    assert opened == []
    assert not tracing._on


def test_span_tree_nests_and_keeps_the_callers_clock():
    tracing.enable()
    with tracing.span("pb.admit", rids=[7]) as outer:
        with tracing.span("pb.prefill") as mid:
            with tracing.span("pb.prefill.wait"):
                pass
            mid.end(backend="single")
        with tracing.span("pb.decode", t0=outer.t0) as d:
            d.end(outer.t0 + 5.0)
    tracing.count("n", 2)
    tracing.count("n")
    by = {r.name: r for r in tracing.spans()}
    assert by["pb.admit"].parent is None
    assert by["pb.prefill"].parent == by["pb.admit"].id
    assert by["pb.prefill.wait"].parent == by["pb.prefill"].id
    assert by["pb.decode"].parent == by["pb.admit"].id
    assert by["pb.prefill"].meta == {"backend": "single"}
    assert by["pb.admit"].meta == {"rids": [7]}
    assert by["pb.decode"].t0 == by["pb.admit"].t0
    assert by["pb.decode"].t1 == by["pb.admit"].t0 + 5.0
    w, p = by["pb.prefill.wait"], by["pb.prefill"]
    assert p.t0 <= w.t0 <= w.t1 <= p.t1
    assert tracing.counters() == {"n": 3}
    tracing.clear()
    assert tracing.spans() == [] and tracing.counters() == {}


def test_compile_hook_records_the_function_name():
    tracing.enable()

    def fresh_fn_for_the_hook(x):
        return x * 3 + 1

    jax.jit(fresh_fn_for_the_hook)(jnp.arange(5)).block_until_ready()
    comp = [r for r in tracing.spans() if r.name == "pb.compile"]
    name = "jit(fresh_fn_for_the_hook)"
    assert any(r.meta["fun_name"] == name for r in comp)
    assert tracing.counters()["compiles." + name] == 1
    for r in comp:
        assert r.t0 <= r.t1


def test_gc_hook_records_collections():
    tracing.enable()
    gc.collect()
    assert any(r.name == "pb.gc" for r in tracing.spans())


def test_disable_unregisters_both_hooks():
    from jax._src import monitoring
    tracing.enable()
    assert tracing._on_compile in monitoring._event_time_span_listeners
    assert tracing._on_gc in gc.callbacks
    tracing.disable()
    assert tracing._on_compile not in monitoring._event_time_span_listeners
    assert tracing._on_gc not in gc.callbacks
    gc.collect()
    jax.jit(lambda x: x - 7)(jnp.arange(3)).block_until_ready()
    assert tracing.spans() == []


def test_a_profiler_session_turns_the_tracer_on_and_off(tmp_path):
    with tracing.span("pb.decode"):
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.span("pb.decode"):
            pass
        assert tracing._on
    finally:
        jax.profiler.stop_trace()
    with tracing.span("pb.decode") as sp:
        assert not sp
    assert not tracing._on
    assert [r.name for r in tracing.spans()] == ["pb.decode"]


def test_each_profiler_session_starts_from_empty_records(tmp_path):
    for name in ("pb.admit", "pb.decode"):
        jax.profiler.start_trace(str(tmp_path / name))
        try:
            with tracing.span(name):
                pass
        finally:
            jax.profiler.stop_trace()
        tracing.span("pb.schedule")         # the session is over: off
        assert [r.name for r in tracing.spans()] == [name]


@pytest.fixture(scope="module")
def tiny():
    cfg = get_arch("qwen3-1.7b").reduced(n_layers=2)
    return cfg, T.init_params(cfg, jax.random.PRNGKey(3))


def test_admission_and_decode_spans_on_a_tiny_model(tiny):
    cfg, params = tiny
    srv = ServingEngine(cfg, params, n_slots=4, max_len=64)
    rng = np.random.default_rng(0)
    lens = (5, 9, 20)
    for i, n in enumerate(lens):
        srv.submit(ServeRequest(i, rng.integers(0, 250, size=n),
                                max_new_tokens=3, arrival=0.0))
    srv.run()                       # compiles outside the traced run
    for i, n in enumerate(lens):
        srv.submit(ServeRequest(10 + i, rng.integers(0, 250, size=n),
                                max_new_tokens=3, arrival=0.0))
    tracing.enable()
    srv.run()
    tracing.disable()
    rec = tracing.spans()
    by_id = {r.id: r for r in rec}

    def named(n):
        return [r for r in rec if r.name == n]

    sched, admits = named("pb.schedule"), named("pb.admit")
    prefills, waits = named("pb.prefill"), named("pb.prefill.wait")
    assert len(admits) == 2 and len(prefills) == 2
    # one schedule span a step; only the first admits, and says so
    assert len(sched) == 2 and sched[1].meta == {}
    assert sorted(sched[0].meta["rids"]) == [10, 11, 12]
    # they arrived at 0; the first run left the step clock at 2
    assert sched[0].meta["waits"] == [2.0] * 3
    assert all(by_id[a.parent] is sched[0] for a in admits)
    assert sorted(sum((a.meta["rids"] for a in admits), [])) == [10, 11, 12]
    assert all(a.meta["prefix_hits"] == 0 for a in admits)
    for p in prefills:
        assert by_id[p.parent].name == "pb.admit"
        assert p.meta["rids"] == by_id[p.parent].meta["rids"]
        assert p.meta["backend"] == "single" and p.meta["rows"] == 4
    # bucket 16 holds prompts 5 and 9, bucket 32 the prompt of 20
    got = sorted((p.meta["bucket"], p.meta["real_tokens"]) for p in prefills)
    assert got == [(16, 14), (32, 20)]
    assert [by_id[w.parent].name for w in waits] == ["pb.prefill"] * 2
    decodes, dwaits = named("pb.decode"), named("pb.decode.wait")
    assert len(decodes) == len(dwaits) == 2
    assert [d.meta["n_active"] for d in decodes] == [3, 3]
    assert sorted(decodes[1].meta["rids"]) == [10, 11, 12]
    assert sorted(decodes[0].meta["cache_lens"]) == [5, 9, 20]
    for w in dwaits:
        d = by_id[w.parent]
        assert d.name == "pb.decode" and d.t0 <= w.t0 <= w.t1 <= d.t1
    hs = srv.hotpath_stats()
    assert "decode_steps_per_s" not in hs
    assert hs["n_prefill_padded_tokens"] == 4 * (16 + 32) * 2
    assert hs["n_prefill_tokens"] == 2 * (5 + 9 + 20)


def test_queue_waits_are_on_the_engines_own_clock(tiny):
    # step() without ``now`` runs the engine's logical clock, one tick a
    # decode step: a request that waits for the only slot waits in ticks
    cfg, params = tiny
    srv = ServingEngine(cfg, params, n_slots=1, max_len=64)
    srv.submit(ServeRequest(0, np.arange(6), max_new_tokens=3))
    srv.submit(ServeRequest(1, np.arange(7), max_new_tokens=2))
    tracing.enable()
    srv.run()
    tracing.disable()
    got = [(s.meta["rids"], s.meta["waits"]) for s in tracing.spans()
           if s.name == "pb.schedule" and s.meta]
    # request 0 decodes two tokens (its first comes from the prefill), so
    # request 1 is admitted two ticks after both arrived at 0
    assert got == [([0], [0.0]), ([1], [2.0])]


def test_decode_span_counts_the_kernels_kv_blocks(tiny, monkeypatch):
    """``kv_blocks`` is what the decode kernel fetches per layer: every
    slot up to its length, a free slot at the length its last request
    left, an unused slot its first block."""
    from repro.kernels import decode_attention as dec
    cfg, params = tiny
    # 128-row blocks at these widths (2 KV heads of 16, f32): a 400-row
    # cache is 4 blocks, the last one ragged
    monkeypatch.setattr(dec, "BLOCK_BYTES", 2 * 16 * 4 * 128)
    srv = ServingEngine(cfg, params, n_slots=4, max_len=400)
    assert dec.block_k_for(400, 2, 16, srv.batcher.cache["attn"]["k"]
                           .dtype.itemsize) == 128
    rng = np.random.default_rng(1)
    # A decodes at lengths 127 and 128 (one block) and leaves its slot at
    # 129 (two); B stays within one block; two slots are never used
    srv.submit(ServeRequest(0, rng.integers(0, 250, size=127),
                            max_new_tokens=3, arrival=0.0))
    srv.submit(ServeRequest(1, rng.integers(0, 250, size=10),
                            max_new_tokens=6, arrival=0.0))
    tracing.enable()
    srv.run()
    tracing.disable()
    decodes = [r for r in tracing.spans() if r.name == "pb.decode"]
    assert [d.meta["kv_blocks"] for d in decodes] == [4, 4, 5, 5, 5]
    assert {d.meta["kv_blocks_all"] for d in decodes} == {4 * 4}
    # the same count from the device's own lengths after the run
    pos = np.asarray(srv.batcher.cache["pos"])
    assert sorted(pos.tolist()) == [0, 0, 10 + 5, 127 + 2]
    assert dec.kv_blocks(pos, 400, 128) == 5


def test_decode_time_is_the_decode_spans_time(tiny):
    cfg, params = tiny
    srv = ServingEngine(cfg, params, n_slots=2, max_len=64)
    srv.submit(ServeRequest(0, np.arange(6), max_new_tokens=5))
    srv.step()
    before = srv.batcher.decode_time_s
    tracing.enable()
    srv.run()
    tracing.disable()
    decodes = [r for r in tracing.spans() if r.name == "pb.decode"]
    assert len(decodes) == 3
    assert srv.batcher.decode_time_s - before == pytest.approx(
        sum(d.t1 - d.t0 for d in decodes), rel=1e-9, abs=1e-12)


_BELT = r"""
import json
import jax
import numpy as np
from repro import tracing
from repro.configs.base import get_arch
from repro.core.adapter_scheduler import EpochSchedulerPolicy
from repro.core.engine import PipeBoostEngine
from repro.models import transformer as T
from repro.serving.engine import ServeRequest, ServingEngine
assert len(jax.devices()) == 4, jax.devices()
cfg = get_arch("qwen3-1.7b").reduced(n_layers=4)
params = T.init_params(cfg, jax.random.PRNGKey(5))
# one loading round and no fill: the chain stays mid-load, so every
# admission prefill rides the belt (wired as launch/serve.py wires it)
eng = PipeBoostEngine(cfg, params, n_devices=4, max_len=64)
eng.load_round()
assert eng.enable_pipeline_prefill(n_micro=2)
srv = ServingEngine(cfg, params, n_slots=4, max_len=64,
                    policy=EpochSchedulerPolicy(max_batch=4))
srv.batcher.set_pipeline_prefill(eng.serving_pipeline_prefill,
                                 fits=eng.serving_pipeline_fits)
srv.batcher.prefill_backend = (
    lambda: "pipeline" if eng.strategy == "pipeline" else "single")
rng = np.random.default_rng(0)
tracing.enable()
for i, n in enumerate((5, 9, 20, 7, 12, 3)):
    srv.submit(ServeRequest(i, rng.integers(0, 250, size=n),
                            max_new_tokens=3 + i % 3))
srv.run()
tracing.disable()
b = srv.batcher
print(json.dumps({
    "prefills": [r.meta for r in tracing.spans() if r.name == "pb.prefill"],
    "compiles": tracing.counters(),
    "n_prefill_pipeline": b.n_prefill_pipeline,
    "n_prefill_reqs": b.n_prefill_reqs,
    "decode_compiles": b.compile_stats()["decode_compiles"]}))
"""


def test_belt_admissions_carry_the_pipeline_backend_on_four_devices():
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ, PYTHONPATH=str(root / "src"), JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", _BELT], env=env, cwd=root,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    pre = out["prefills"]
    assert pre and {p["backend"] for p in pre} == {"pipeline"}
    assert sorted(sum((p["rids"] for p in pre), [])) == list(range(6))
    assert out["n_prefill_pipeline"] == out["n_prefill_reqs"] == 6
    # the belt hands off a committed cache; the decode step still
    # compiles once, and the compile hook saw that compile
    assert out["decode_compiles"] == 1
    assert sum(n for k, n in out["compiles"].items()
               if "decode" in k) >= 1
