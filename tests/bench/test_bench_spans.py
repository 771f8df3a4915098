"""Readers of the program's own spans, added as new files and manifest
entries only."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, spans  # noqa: E402
from repro.tracing import Record  # noqa: E402

FIX = Path(__file__).parent / "fixtures"


def _reader(name):
    return harness.load_module(ROOT / "bench" / "layers" / f"{name}.py")


def _run(trace_window=(10.0, 20.0), trace=None, chips=1):
    cell = harness.Cell(name="x.y", entry={"chips": chips}, config_name="x",
                        config={}, mix={}, spec={}, end_to_end=[],
                        per_layer=[])
    return harness.Run(cell=cell, dims=None, peak=None, spans=[],
                       window=(0.0, 30.0), trace_window=trace_window,
                       trace=trace)


def _recs():
    """Two admissions (one on the belt) and three decode steps; one step
    starts before the traced window and is left out."""
    R = Record
    return [
        R(1, None, "pb.decode", 9.9, 10.1, {"n_active": 2}),
        R(2, 1, "pb.decode.wait", 9.95, 10.05, {}),
        R(3, None, "pb.schedule", 11.0, 11.5, {}),
        R(4, 3, "pb.admit", 11.0, 11.5, {"rids": [5, 6]}),
        R(5, 4, "pb.prefill", 11.1, 11.4,
          {"backend": "pipeline", "rows": 4, "bucket": 16,
           "real_tokens": 20, "rids": [5, 6]}),
        R(6, 5, "pb.prefill.wait", 11.2, 11.4, {}),
        R(7, None, "pb.decode", 12.0, 12.030, {"n_active": 4}),
        R(8, 7, "pb.decode.wait", 12.010, 12.025, {}),
        R(9, None, "pb.decode", 13.0, 13.020, {"n_active": 4}),
        R(10, 9, "pb.decode.wait", 13.001, 13.019, {}),
        R(11, None, "pb.admit", 14.0, 14.2, {"rids": [7]}),
        R(12, 11, "pb.prefill", 14.0, 14.2,
          {"backend": "single", "rows": 4, "bucket": 32, "real_tokens": 30,
           "rids": [7]}),
        R(13, None, "pb.compile", 15.0, 15.5, {"fun_name": "jit(f)"}),
    ]


@pytest.fixture
def recorded(monkeypatch):
    monkeypatch.setattr(spans, "records", _recs)


def test_decode_host_ms_is_the_steps_less_their_waits(recorded, capfd):
    # (30 - 15) and (20 - 18) ms: the step that straddles the window's
    # start is left out
    assert _reader("decode_host_ms").read(_run()) == pytest.approx(8.5)
    err = capfd.readouterr().err
    assert "compiles by function {'jit(f)': 1}" in err
    assert "admissions 3 requests in 2 prefill calls, 2 on the pipeline " \
        "belt" in err


def test_prefill_pad_share_is_the_padding_of_rows_times_bucket(recorded):
    assert _reader("prefill_pad_share").read(_run()) == pytest.approx(
        100.0 * (1 - 50 / (4 * 16 + 4 * 32)))


@pytest.mark.parametrize("name", ["decode_host_ms", "prefill_pad_share",
                                  "device_idle_host"])
def test_readers_read_nothing_without_the_programs_tracer(monkeypatch,
                                                          name):
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert spans.records() == []
    run = _run(trace={"window_s": 10.0, "busy_s": 9.0})
    assert _reader(name).read(run) is None


@pytest.mark.parametrize("name", ["decode_host_ms", "prefill_pad_share",
                                  "device_idle_host"])
def test_readers_read_nothing_without_a_traced_window(recorded, name):
    assert _reader(name).read(_run(trace_window=None)) is None


def test_idle_host_by_hand():
    ev = json.load(open(FIX / "trace_program_spans.json"))
    recs = [Record(*r[:5], {}) for r in ev.pop("records")]
    ev["marks"] = {int(k): v for k, v in ev["marks"].items()}
    r = spans.idle_host(ev, recs)
    # chip 0 ops [1000, 2000) [2500, 3000) [4000, 5000): gaps [2000, 2500)
    # and [3000, 4000); chip 1 ops [1000, 4500): no gap.  The records sit
    # 100 ns after their marks' clock: the decode span [1900, 2700) holds
    # all of the first gap, the gc span [3300, 3500) 200 ns of the second
    assert r["idle_s"] == pytest.approx((500e-9 + 1000e-9) / 2)
    assert r["idle_host_s"] == pytest.approx((500e-9 + 200e-9) / 2)
    assert r["gaps"] == [["host", pytest.approx(1000e-9)],
                         ["pb.decode.wait", pytest.approx(500e-9)]]


def test_idle_host_needs_a_mark():
    ev = json.load(open(FIX / "trace_program_spans.json"))
    recs = [Record(*r[:5], {}) for r in ev.pop("records")]
    ev["marks"] = {}
    assert spans.idle_host(ev, recs) is None


def test_extract_reads_marks_from_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    from repro import tracing
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    tracing.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.span("pb.decode"):
            with tracing.span("pb.decode.wait"):
                f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    recs = tracing.spans()
    tracing.disable()
    tracing.clear()
    ev = spans.extract(spans.trace_mod.find_xplane(tmp_path), 1)
    assert sorted(ev["marks"]) == sorted(r.id for r in recs)
    assert ev["ops"] == {}          # no device plane on the CPU
    assert spans.idle_host(ev, recs) is None
