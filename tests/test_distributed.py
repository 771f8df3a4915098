"""Distribution layer: sharding rules (pure metadata) + multi-device
numerical equivalence (subprocess with fake devices so the main test
process keeps seeing 1 CPU device, per the harness contract)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import SHAPES, get_arch
from repro.launch.mesh import auto_mesh, data_axes, pipeline_stages_for


def test_pipeline_stage_counts():
    assert pipeline_stages_for(48) == 16
    assert pipeline_stages_for(28) == 4
    assert pipeline_stages_for(62) == 2
    assert pipeline_stages_for(40) == 8
    assert pipeline_stages_for(80) == 16
    assert pipeline_stages_for(24) == 8
    assert pipeline_stages_for(26) == 2
    assert pipeline_stages_for(32) == 16


def test_main_process_sees_one_device():
    # conftest/pyproject must NOT set the fake-device flag globally
    assert len(jax.devices()) == 1


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.configs.base import get_arch
    from repro.distributed import shardings as shd
    from repro.distributed.context import ShardingPolicy, use_policy
    from repro.launch.mesh import auto_mesh
    from repro.models import transformer as T

    cfg = get_arch("qwen3-1.7b").reduced(n_layers=4, vocab_size=256)
    key = jax.random.PRNGKey(0)
    params = T.init_params(cfg, key, jnp.float32)
    batch = {"tokens": jax.random.randint(key, (4, 32), 0, 256)}

    # single-device reference
    ref, _ = T.forward(cfg, params, batch, mode="train")

    mesh = auto_mesh((2, 4), ("data", "model"))
    pol = ShardingPolicy(mesh, dp_axes=("data",), seq_axis="model")
    pspec = shd.param_specs(cfg, params, mesh, mode="fsdp")
    bspec = shd.batch_specs(cfg, batch, mesh, shard_seq=True)
    p_sh = jax.device_put(params, shd.named(mesh, pspec))
    b_sh = jax.device_put(batch, shd.named(mesh, bspec))

    def fwd(p, b):
        return T.forward(cfg, p, b, mode="train")[0]

    with use_policy(pol):
        out = jax.jit(fwd,
                      in_shardings=(shd.named(mesh, pspec),
                                    shd.named(mesh, bspec)))(p_sh, b_sh)
    err = float(jnp.max(jnp.abs(out - ref)))
    assert err < 2e-4, err

    # pipeline lowerings == plain forward (prefill logits)
    from repro.distributed.pipeline import (build_pipeline_prefill,
                                            build_pipeline_prefill_seqchunk)
    pmesh = auto_mesh((2, 4), ("data", "stage"))
    f = build_pipeline_prefill(cfg, n_stages=4, n_micro=2, mesh=pmesh,
                               seq_len=32)
    lg_pipe = f(params, batch)
    lg_ref, _ = T.forward(cfg, params, batch, mode="prefill", max_len=32)
    err2 = float(jnp.max(jnp.abs(lg_pipe - lg_ref)))
    assert err2 < 2e-3, err2
    # TeraPipe-style sequence-chunk belt (the §Perf hillclimb variant)
    f2 = build_pipeline_prefill_seqchunk(cfg, n_stages=4, n_chunks=8,
                                         mesh=pmesh, seq_len=32)
    lg_sc = f2(params, batch)
    err3 = float(jnp.max(jnp.abs(lg_sc - lg_ref)))
    assert err3 < 2e-3, err3
    print("OK", err, err2, err3)
""")


@pytest.mark.slow
def test_sharded_equals_single_device_and_pipeline():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SUBPROC], env=env,
                       capture_output=True, text=True, timeout=900,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_param_specs_divisible():
    """Every sharded dim must divide by its axis product (all archs)."""
    from repro.distributed import shardings as shd
    mesh = auto_mesh((1, 1), ("data", "model"))

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    from repro.configs.base import ARCH_IDS
    for arch in ARCH_IDS:
        cfg = get_arch(arch)
        struct = jax.eval_shape(
            lambda: __import__("repro.models.transformer",
                               fromlist=["x"]).init_params(
                cfg, jax.random.PRNGKey(0), jnp.bfloat16))
        specs = shd.param_specs(cfg, struct, FakeMesh(), mode="fsdp")

        def check(leaf, spec):
            for d, s in enumerate(spec):
                if s is None:
                    continue
                names = s if isinstance(s, tuple) else (s,)
                n = 1
                for a in names:
                    n *= FakeMesh.shape[a]
                assert leaf.shape[d] % n == 0, (arch, leaf.shape, spec)

        jax.tree.map(check, struct, specs,
                     is_leaf=lambda x: hasattr(x, "shape"))


_ELASTIC = textwrap.dedent("""
    import os, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh
    from repro.configs.base import get_arch
    from repro.distributed import shardings as shd
    from repro.launch.mesh import auto_mesh
    from repro.training.checkpoint import Checkpointer
    from repro.training.data import SyntheticLM
    from repro.training.optimizer import AdamWConfig
    from repro.training.train import init_train_state, make_train_step

    cfg = get_arch("qwen3-1.7b").reduced(n_layers=2, vocab_size=256)
    opt = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    step = jax.jit(make_train_step(cfg, opt, remat=False))
    ds = SyntheticLM(vocab_size=256, seq_len=16, batch_size=8, seed=2)

    def run(state, n):
        for _ in range(n):
            b = ds.next_batch()
            state, _ = step(state, {k: jnp.asarray(v) for k, v in b.items()})
        return state

    # train 2 steps on an 8-device mesh (FSDP-sharded state)
    devs = jax.devices()
    mesh8 = auto_mesh((4, 2), ("data", "model"), devices=devs[:8])
    state = init_train_state(cfg, jax.random.PRNGKey(0), jnp.float32)
    spec8 = shd.param_specs(cfg, state.params, mesh8, mode="fsdp")
    sspec8 = type(state)(spec8, type(state.opt)(
        __import__("jax").sharding.PartitionSpec(), spec8, spec8))
    state = jax.device_put(state, shd.named(mesh8, sspec8))
    state = run(state, 2)

    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        ck.save(2, state, extra={"data": ds.state()})
        # ELASTIC RESTART: half the devices died -> new 4-device mesh
        mesh4 = auto_mesh((2, 2), ("data", "model"), devices=devs[:4])
        tmpl = init_train_state(cfg, jax.random.PRNGKey(0), jnp.float32)
        spec4 = shd.param_specs(cfg, tmpl.params, mesh4, mode="fsdp")
        sspec4 = type(tmpl)(spec4, type(tmpl.opt)(
            __import__("jax").sharding.PartitionSpec(), spec4, spec4))
        st2, extra = ck.restore(tmpl, shardings=shd.named(mesh4, sspec4))
    # values identical across meshes
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(st2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=0)
    # and training continues on the survivor mesh
    ds2 = SyntheticLM(vocab_size=256, seq_len=16, batch_size=8, seed=2)
    ds2.restore(extra["data"])
    b = ds2.next_batch()
    st3, m = step(st2, {k: jnp.asarray(v) for k, v in b.items()})
    assert np.isfinite(float(m["loss"]))
    print("ELASTIC_OK")
""")


@pytest.mark.slow
def test_elastic_restart_onto_smaller_mesh():
    """Checkpoint on an 8-device mesh, restore + continue on 4 devices —
    the mesh-agnostic checkpointing claim (DESIGN.md §2 elasticity)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _ELASTIC], env=env,
                       capture_output=True, text=True, timeout=900,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr[-3000:]
    assert "ELASTIC_OK" in r.stdout
