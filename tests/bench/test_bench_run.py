"""The benchmark's command end to end on the CPU, at a test size.

``bench/run.py`` itself refuses to run without a TPU, so these tests call
the harness below that check (``harness.run_cell``), in a temporary copy
of the benchmark to which a configuration, a traffic mix, a cell and a
per-layer metric are added as new files and manifest entries only.  The
same copy then drives runs with the timed path broken underneath (a decode
step that leaves its state unchanged, half of the batch left out, a token
altered where it is produced, requests taken and never served) and the
fp8 control, and each must read as not correct.  The one-chip cells have no exchange between chips to leave
out.
"""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

TINY_CONFIG = dict(hidden_size=128, intermediate_size=256,
                   num_attention_heads=4, num_key_value_heads=2, head_dim=32,
                   num_hidden_layers=2, vocab_size=1000)
TINY_MIX = {"arrival": {"process": "poisson", "rate_per_s": 8.0},
            "prompt_tokens": {"dist": "lognormal", "median": 12,
                              "sigma": 0.5, "min": 4, "max": 40},
            "output_tokens": {"dist": "lognormal", "median": 12,
                              "sigma": 0.5, "min": 4, "max": 24},
            "lead_in_s": 1}
# Readings at this size on the CPU, eight seeds each: the program's widest
# gap 0 to 0.0147, the fp8 control's 0.0579 to 0.120.  The limit lies
# between, with more room above the program's readings.
TINY_LIMIT = 0.035
TINY_CELL = {"server": "single", "n_slots": 4, "max_len": 64, "drain_s": 10,
             "check": {"requests": 8, "max_logit_gap": TINY_LIMIT}}
NEW_READER = '''"""Decode loop: mean active slots per decode step in the window."""
from bench.harness import in_window


def read(run):
    s = in_window(run, "decode")
    return sum(len(x.meta["cache_lens"]) for x in s) / len(s) if s else None
'''

DRIVER = r'''
import json, sys, time
from pathlib import Path
root = Path(sys.argv[1])
sys.path[:0] = [str(root), str(root / "src")]
import jax, jax.numpy as jnp, numpy as np
from bench import harness
from repro.models import transformer
from repro.serving.engine import ContinuousBatcher, ServingEngine

info = lambda: {"platform": "cpu", "kind": "cpu", "count": 1,
                "memory_peak_bytes": 0}
orig = transformer.decode_step


def run(seed, trace=False):
    return harness.run_cell(root, "tiny.chat", seed, 2.0, trace,
                            time.perf_counter(), info)


def state_unchanged(cfg, params, batch, cache, **kw):
    logits, _ = orig(cfg, params, batch, cache, **kw)
    return logits, cache


orig_build = ContinuousBatcher._build_jits


def half_batch_build(self):
    """Every other active slot (the first among them too) is left out of
    each decode step: its token does not advance."""
    orig_build(self)
    step = self._decode_fused

    def left_out(p, toks, active, cache):
        nxt, cache = step(p, toks, active, cache)
        act = np.flatnonzero(np.asarray(active))
        skip = np.zeros(active.shape, bool)
        skip[act[::2]] = True
        return jnp.where(jnp.asarray(skip), toks, nxt), cache
    self._decode_fused = left_out


def token_altered(cfg, params, batch, cache, **kw):
    logits, new = orig(cfg, params, batch, cache, **kw)
    return jnp.roll(logits, 1, axis=-1), new


out = {"trace": run(11, True), "plain": run(12)}
for name, fn in [("state_unchanged", state_unchanged),
                 ("token_altered", token_altered)]:
    transformer.decode_step = fn
    try:
        out[name] = run(13)
    finally:
        transformer.decode_step = orig
ContinuousBatcher._build_jits = half_batch_build
try:
    out["half_batch"] = run(13)
finally:
    ContinuousBatcher._build_jits = orig_build
orig_submit = ServingEngine.submit


def drop_odd(self, req):
    """Requests with an odd id are taken and never served."""
    if req.rid < 0 or req.rid % 2 == 0:
        orig_submit(self, req)


ServingEngine.submit = drop_odd
try:
    out["unserved"] = run(14)
finally:
    ServingEngine.submit = orig_submit
out["control"] = []
for seed in (21, 22, 23):
    s = harness.serve(root, harness.load_cell(root, "tiny.chat"), seed, 2.0,
                      False, time.perf_counter(), info)
    prog, _ = harness.reference_gap(root, s.cell, seed, s.sample)
    ctrl, _ = harness.reference_gap(root, s.cell, seed, s.sample,
                                    control="fp8")
    out["control"].append([prog, ctrl])
print(json.dumps(out))
'''


def _copy(tmp: Path) -> Path:
    """The benchmark's files as a checkout has them, plus new files."""
    dst = tmp / "checkout"
    dst.mkdir()
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", dst)
    (dst / "src").symlink_to(ROOT / "src")
    return dst


def _add_tiny_cell(dst: Path) -> None:
    cfg = json.load(open(dst / "bench" / "configs" / "qwen3-1.7b.json"))
    cfg.update(TINY_CONFIG, reduced=sorted(TINY_CONFIG))
    json.dump(cfg, open(dst / "bench" / "configs" / "tiny.json", "w"))
    json.dump(TINY_MIX, open(dst / "bench" / "traffic" / "chat.json", "w"))
    json.dump(TINY_CELL, open(dst / "bench" / "cells" / "tiny.chat.json",
                              "w"))
    (dst / "bench" / "layers" / "decode_batch.py").write_text(NEW_READER)
    b = json.load(open(dst / "BENCHMARK.json"))
    b["configs"].append({"name": "tiny", "source": "test size",
                         "file": "bench/configs/tiny.json",
                         "reduced": sorted(TINY_CONFIG), "why": "test"})
    b["workloads"].append({"name": "tiny.chat", "config": "tiny",
                           "traffic": "chat", "chips": 1, "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("tiny.chat")
    b["per_layer"].append({"name": "decode_batch", "unit": "slots",
                           "better": "higher", "source": "host_clock",
                           "layer": "decode loop", "moves": "out_tok_s",
                           "workloads": ["tiny.chat"]})
    json.dump(b, open(dst / "BENCHMARK.json", "w"))


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


@pytest.fixture(scope="module")
def driven(tmp_path_factory):
    dst = _copy(tmp_path_factory.mktemp("bench"))
    before = {p: p.read_bytes() for p in (dst / "bench").rglob("*.py")}
    _add_tiny_cell(dst)
    after = {p: p.read_bytes() for p in (dst / "bench").rglob("*.py")
             if p in before}
    assert after == before, "adding a cell edited a harness file"
    r = subprocess.run([sys.executable, "-c", DRIVER, str(dst)], env=_env(),
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_run_exits_nonzero_without_a_tpu(tmp_path):
    r = subprocess.run([sys.executable, str(ROOT / "bench" / "run.py"),
                        "--workload", "qwen3-1.7b.longout", "--seed",
                        str(2**31 + 3), "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=_env(), capture_output=True,
                       text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    dst = tmp_path / "bare"
    dst.mkdir()
    shutil.copytree(ROOT / "bench", dst / "bench")
    shutil.copy(ROOT / "BENCHMARK.json", dst)
    r = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "qwen3-1.7b.longout", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=dst, env=_env(),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0 and r.stdout.strip() == ""


def test_new_files_make_a_cell_and_a_metric(driven):
    t = driven["trace"]
    assert t["correct"] is True
    assert t["metrics"]["decode_batch"]["unit"] == "slots"
    assert 1.0 <= t["metrics"]["decode_batch"]["value"] <= 4.0
    assert {"prefill_ms", "decode_step_ms"} <= set(t["metrics"])
    # no device plane on the CPU: the trace readers find nothing to read
    assert "device_idle" not in t["metrics"]
    assert list(t)[-1] == "checks"
    p = driven["plain"]
    assert p["correct"] is True and p["attempted"] > 0 and p["failed"] == 0
    bench = json.load(open(ROOT / "BENCHMARK.json"))
    assert set(p["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    assert all(m["value"] > 0 for m in p["metrics"].values())
    assert p["checks"]["max_logit_gap"]["limit"] == TINY_LIMIT


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered"])
def test_broken_timed_path_reads_not_correct(driven, fault):
    r = driven[fault]
    assert r["correct"] is False
    gap = r["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_unserved_requests_read_not_correct(driven):
    r = driven["unserved"]
    assert r["failed"] > 0 and r["correct"] is False
    assert r["checks"]["max_logit_gap"]["value"] <= TINY_LIMIT
    assert r["checks"]["failed"] == {"value": r["failed"], "limit": 0}


def test_fp8_control_reads_not_correct(driven):
    for prog, ctrl in driven["control"]:
        assert prog <= TINY_LIMIT < ctrl
