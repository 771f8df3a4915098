"""Reduction of a profiler trace to busy, idle, program and kernel time."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402

FIX = Path(__file__).parent / "fixtures"


def _brute_busy_ns(ops):
    """Busy nanoseconds by marking every covered nanosecond."""
    covered = set()
    for _, s, d in ops:
        covered.update(range(int(s), int(s + d)))
    return len(covered)


def test_union_merges_overlaps_and_touching_intervals():
    assert trace.union_ns([(5, 7), (0, 2), (1, 3), (3, 4)]) == \
        [(0, 4), (5, 7)]
    assert trace.union_ns([]) == []


def test_synthetic_trace_by_hand():
    ev = json.load(open(FIX / "trace_synthetic.json"))
    r = trace.reduce(ev, window_s=5e-6, chips=["/device:TPU:0"])
    # ops cover [900, 5200) under the loop op; inside it [1000, 1800)
    # [2500, 3000) [4000, 5000)
    assert r["busy_s"] == pytest.approx(4300e-9)
    assert r["window_s"] == 5e-6
    assert r["module_s"]["decode"] == pytest.approx(2000e-9)
    assert r["module_s"]["prefill"] == pytest.approx(1000e-9)
    assert r["kernel_s"]["decode_attention"] == pytest.approx(1600e-9)
    ops = dict((k, v) for k, v in r["breakdown"]["device_ops"])
    # numbered instructions of one kind are summed; the loop is left out
    assert ops == {"decode_attention": pytest.approx(1600e-9),
                   "fusion": pytest.approx(1000e-9),
                   "copy": pytest.approx(100e-9)}
    assert r["breakdown"]["device_ops"][0][0] == "decode_attention"
    assert r["breakdown"]["idle_gaps"] == []


def test_idle_gaps_are_labelled_by_the_host_annotation():
    ev = json.load(open(FIX / "trace_synthetic.json"))
    d = ev["devices"]["/device:TPU:0"]
    d["ops"] = [op for op in d["ops"] if not op[0].startswith("while")]
    r = trace.reduce(ev, window_s=5e-6, chips=["/device:TPU:0"])
    assert r["busy_s"] == pytest.approx(2300e-9)
    # gaps: [3000, 4000) in the generator wait, [1800, 2500) in a step
    assert r["breakdown"]["idle_gaps"] == [
        ["generator_wait", pytest.approx(1000e-9)],
        ["decode_step", pytest.approx(700e-9)]]


def test_two_chips_are_averaged():
    ev = json.load(open(FIX / "trace_synthetic.json"))
    r = trace.reduce(ev, window_s=5e-6)
    assert r["busy_s"] == pytest.approx((4300e-9 + 2000e-9) / 2)
    assert r["module_s"]["decode"] == pytest.approx(1000e-9)


def test_brute_force_agrees_on_the_synthetic_ops():
    ev = json.load(open(FIX / "trace_synthetic.json"))
    ops = ev["devices"]["/device:TPU:0"]["ops"]
    r = trace.reduce(ev, 1.0, chips=["/device:TPU:0"])
    assert r["busy_s"] == pytest.approx(_brute_busy_ns(ops) * 1e-9)


def test_extract_reads_host_annotations_from_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.decode_step"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = trace.extract(trace.find_xplane(tmp_path))
    assert [h[0] for h in ev["host"]] == ["bench.decode_step"]
    assert ev["host"][0][2] > 0
    # no TPU plane on the CPU: nothing busy, nothing to read
    assert ev["devices"] == {}


def _sweep_busy_ns(ops):
    """Busy time by a sweep over sorted start and end points."""
    pts = sorted([(s, 1) for _, s, _ in ops] + [(s + d, -1) for _, s, d in ops],
                 key=lambda p: (p[0], -p[1]))
    busy, depth, since = 0.0, 0, None
    for t, step in pts:
        if depth == 0 and step == 1:
            since = t
        depth += step
        if depth == 0:
            busy += t - since
    return busy


def test_recorded_v5e_decode_trace():
    ev = json.load(open(FIX / "trace_v5e_decode.json"))
    ops = ev["devices"]["/device:TPU:0"]["ops"]
    first = min(s for _, s, _ in ops)
    last = max(s + d for _, s, d in ops)
    window = (last - first) * 1e-9
    r = trace.reduce(ev, window_s=window)
    assert r["busy_s"] == pytest.approx(_sweep_busy_ns(ops) * 1e-9)
    assert 0.0 <= 1.0 - r["busy_s"] / window < 0.01
    kernel = [d for n, _, d in ops if n.startswith("decode_attention.")]
    assert len(kernel) == 3
    assert r["kernel_s"]["decode_attention"] == pytest.approx(
        sum(kernel) * 1e-9)
    tops = dict(r["breakdown"]["device_ops"])
    assert tops["decode_attention"] == pytest.approx(sum(kernel) * 1e-9)
    assert len(r["breakdown"]["device_ops"]) == trace.TOP
    assert r["module_s"] == {"prefill": 0.0, "decode": 0.0}
