"""The benchmark's seeded weights and its plain float32 reference."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import harness, weights  # noqa: E402

SMALL = dict(hidden_size=64, intermediate_size=96, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
             vocab_size=300)


def small(name, dtype="float32"):
    cfg = json.load(open(ROOT / "bench" / "configs" / f"{name}.json"))
    cfg.update(SMALL, torch_dtype=dtype)
    return cfg


def _shapes(cfg, name="t"):
    from repro.models import transformer
    arch = harness.program_config(name, cfg)
    return arch, jax.eval_shape(lambda k: transformer.init_params(arch, k),
                                jax.random.PRNGKey(0))


@pytest.mark.parametrize("name", ["qwen3-1.7b", "qwen2.5-14b-pp4"])
def test_reference_matches_the_program_forward_in_float32(name):
    from repro.models import transformer
    cfg = small(name)
    arch, shapes = _shapes(cfg)
    seed = 2**31 + 99
    params = weights.make_params(seed, shapes, arch.vocab_size)
    ref = harness.load_module(ROOT / "bench" / "refs" / "qwen_dense.py")
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, 300, size=n) for n in (37, 5)]
    with jax.default_matmul_precision("highest"):
        want = [np.asarray(transformer.forward(
            arch, params, {"tokens": jnp.asarray(t[None], jnp.int32)},
            mode="train")[0][0, :, :300]) for t in seqs]
    pos = [np.arange(len(t)) for t in seqs]
    h = ref.final_hidden(cfg, seed, seqs, pos)
    got = np.asarray(h @ ref.head(cfg, seed))
    np.testing.assert_allclose(got, np.concatenate(want), rtol=2e-4,
                               atol=2e-4)
    # the gap of the program's own argmax is 0; of another token, positive
    best = np.argmax(np.concatenate(want), -1)
    assert np.max(ref.gaps(cfg, seed, h, best)) == pytest.approx(0, abs=1e-4)
    assert np.min(ref.gaps(cfg, seed, h, (best + 1) % 300)) > 0


def test_layer_leaf_equals_the_stacked_leaf_and_padding_is_zero():
    cfg = small("qwen3-1.7b", "bfloat16")
    arch, shapes = _shapes(cfg)
    p = weights.make_params(5, shapes, arch.vocab_size)
    key = weights.seed_key(5)
    for path, (shape, dt) in weights.leaf_paths(shapes).items():
        if path.startswith("blocks/"):
            one = weights.layer_leaf(key, path, 1, shape[1:], dt, 300)
            stacked = p
            for part in path.split("/"):
                stacked = stacked[part]
            assert bool((one == stacked[1]).all()), path
    assert p["embed"].shape[0] == arch.padded_vocab > 300
    assert float(jnp.abs(p["embed"][300:]).max()) == 0.0
    e = weights.make_leaf(key, "embed", (300, 64), jnp.bfloat16, 300)
    assert bool((e == p["embed"][:300]).all())


_DIGEST = """
import hashlib, json, sys
sys.path[:0] = [{root!r}, {src!r}]
import jax, numpy as np
from bench import harness, weights
from repro.models import transformer
cfg = json.loads({cfg!r})
arch = harness.program_config("t", cfg)
shapes = jax.eval_shape(lambda k: transformer.init_params(arch, k),
                        jax.random.PRNGKey(0))
p = weights.make_params({seed}, shapes, arch.vocab_size)
h = hashlib.sha256()
for leaf in jax.tree.leaves(p):
    h.update(np.asarray(leaf.astype("float32")).tobytes())
print(h.hexdigest())
"""


def test_weights_depend_only_on_the_seed_across_processes():
    cfg = json.dumps(small("qwen2.5-14b-pp4", "bfloat16"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    def digest(seed, hashseed):
        code = _DIGEST.format(root=str(ROOT), src=str(ROOT / "src"), cfg=cfg,
                              seed=seed)
        env["PYTHONHASHSEED"] = str(hashseed)
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        return out.stdout.strip().splitlines()[-1]

    a = digest(2**31 + 7, 1)
    assert a == digest(2**31 + 7, 2)
    assert a != digest(2**31 + 8, 1)
