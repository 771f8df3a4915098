"""``decode_kv_read_share``: the decode kernel's fetched K/V blocks over
the cache's, from the program's ``pb.decode`` spans."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, spans  # noqa: E402
from repro.tracing import Record  # noqa: E402


def _read(trace_window=(10.0, 20.0)):
    cell = harness.Cell(name="x.y", entry={"chips": 1}, config_name="x",
                        config={}, mix={}, spec={}, end_to_end=[],
                        per_layer=[])
    run = harness.Run(cell=cell, dims=None, peak=None, spans=[],
                      window=(0.0, 30.0), trace_window=trace_window,
                      trace=None)
    return harness.load_module(
        ROOT / "bench" / "layers" / "decode_kv_read_share.py").read(run)


def _steps(*metas, t=12.0):
    """One ``pb.decode`` span a second from ``t``, with its wait."""
    out = []
    for i, m in enumerate(metas):
        out += [Record(2 * i + 1, None, "pb.decode", t + i, t + i + 0.02, m),
                Record(2 * i + 2, 2 * i + 1, "pb.decode.wait", t + i + 0.01,
                       t + i + 0.015, {})]
    return out


def test_share_is_the_fetched_blocks_over_all_blocks(monkeypatch):
    # three steps of 24 slots x 3 blocks; the first starts before the
    # traced window and is left out
    recs = _steps({"n_active": 15, "kv_blocks": 72, "kv_blocks_all": 72},
                  t=9.5)
    recs += _steps({"n_active": 15, "kv_blocks": 40, "kv_blocks_all": 72},
                   {"n_active": 16, "kv_blocks": 44, "kv_blocks_all": 72})
    monkeypatch.setattr(spans, "records", lambda: recs)
    assert _read() == pytest.approx(100.0 * (40 + 44) / (72 + 72))


def test_spans_without_the_counts_read_nothing(monkeypatch):
    # a program that records decode spans but not the kernel's blocks
    monkeypatch.setattr(spans, "records", lambda: _steps(
        {"n_active": 3, "cache_lens": [5, 9, 20]}))
    assert _read() is None


@pytest.mark.parametrize("case", ["no tracer", "no traced window"])
def test_reads_nothing_without_spans_in_a_traced_window(monkeypatch, case):
    if case == "no tracer":
        monkeypatch.setitem(sys.modules, "repro.tracing", None)
        assert _read() is None
    else:
        monkeypatch.setattr(spans, "records", lambda: _steps(
            {"kv_blocks": 4, "kv_blocks_all": 8}))
        assert _read(trace_window=None) is None
