"""CPU rehearsal of ``chip_smoke.py``: its phases on a reduced qwen3-1.7b
with the Pallas decode kernel in interpret mode, the four-chip phase on
four virtual CPU devices, its refusal to run without a TPU, and where the
persistent compile cache goes."""
import importlib.util
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs.base import get_arch
from repro.launch import compile_cache
from repro.models.attention import decode_attn_impl

ROOT = Path(__file__).resolve().parents[1]


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


smoke = _load_smoke()


@pytest.fixture
def cfg():
    return get_arch("qwen3-1.7b").reduced(n_layers=4)


@pytest.fixture
def no_repo_cache(monkeypatch, tmp_path):
    """``serve.main`` turns the persistent compile cache on.  With the
    variable set it leaves JAX's setting alone, and JAX read the variable
    at import, before this: nothing is written to the repo's cache."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


def test_server_phase_then_mosaic_check_refuses_interpret_mode(
        cfg, no_repo_cache):
    with decode_attn_impl("pallas"):
        srv = smoke.phase_server(cfg)
        assert all(len(r.generated) == 8 for r in srv.completed)
        # interpret mode lowers the kernel to plain HLO: the Mosaic check
        # must see through it
        with pytest.raises(smoke.SmokeError, match="tpu_custom_call"):
            smoke.phase_mosaic(srv.batcher)


def test_cluster_phase_crash_reroute_rejoin(cfg, no_repo_cache):
    with decode_attn_impl("pallas"):
        router = smoke.phase_cluster(cfg)
    kinds = [k for _, k, _ in router.metrics.events]
    assert kinds.index("crash") < kinds.index("rejoin")


def test_kernel_phase_pallas_matches_xla(cfg):
    smoke.phase_kernels(cfg)


def test_kernel_phase_catches_a_wrong_kernel(cfg, monkeypatch):
    """A decode kernel that ignores the cache must fail the phase."""
    from repro.kernels import ops
    real = ops.decode_attention

    def broken(q, k, v, lens, **kw):
        return real(q, k, v, lens * 0 + 1, **kw)

    monkeypatch.setattr(ops, "decode_attention", broken)
    with pytest.raises(smoke.SmokeError):
        smoke.phase_kernels(cfg)


@pytest.mark.parametrize("tokens,n_differ", [([2, 0], 0), ([1, 0], 1)])
def test_greedy_agreement_accepts_only_near_ties(tokens, n_differ):
    """Token 1 sits 0.05 below the best logit, inside the tolerance (3% of
    4.0); token 0 of the second row is the argmax."""
    ref = np.array([[0.0, 3.95, 4.0], [1.0, -4.0, 0.0]], np.float32)
    assert smoke._greedy_agree(np.array(tokens), ref, "t") == n_differ


def test_greedy_agreement_refuses_a_far_token():
    ref = np.array([[0.0, 3.0, 4.0]], np.float32)
    with pytest.raises(smoke.SmokeError, match="below the best logit"):
        smoke._greedy_agree(np.array([1]), ref, "t")


_FOUR = textwrap.dedent("""
    import importlib.util, sys
    import jax
    assert len(jax.devices()) == 4, jax.devices()
    from repro.configs.base import get_arch
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    smoke.phase_four_chips(get_arch("qwen3-1.7b").reduced(n_layers=4))
    print("FOUR_OK")
""")


@pytest.mark.slow
def test_four_chip_phase_on_virtual_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    r = subprocess.run([sys.executable, "-c", _FOUR], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "FOUR_OK" in r.stdout


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_main_refuses_without_tpu(argv, capsys):
    assert jax.devices()[0].platform != "tpu"
    with pytest.raises(SystemExit) as e:
        smoke.main(argv)
    assert e.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_compile_cache_goes_where_the_variable_says(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_repo(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
