"""Distributed-runtime tests.

Multi-device correctness runs in a SUBPROCESS with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the main test process
keeps 1 device, per the dry-run isolation contract).
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.distributed.elastic import choose_mesh_shape
from repro.distributed.fault_tolerance import (
    HeartbeatMonitor,
    RestartBudget,
    StragglerPolicy,
)

pytestmark = pytest.mark.slow  # subprocess multi-device suites dominate runtime

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_subprocess(code: str) -> str:
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_moe_sharded_matches_local():
    """shard_map expert-parallel MoE == single-device MoE bit-for-math."""
    run_subprocess("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.distributed.context import axis_rules, single_pod_rules
        from repro.models.moe import MoEConfig, moe_init, moe_apply
        # capacity_factor high enough that no token drops in either the
        # local (global-capacity) or sharded (per-source-capacity) path —
        # dropping policies legitimately differ at tight capacity.
        cfg = MoEConfig(n_experts=8, top_k=2, d_ff=32, capacity_factor=8.0)
        rng = jax.random.PRNGKey(0)
        p = moe_init(rng, 16, cfg, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 16))
        out_local, aux_local = moe_apply(p, x, cfg)  # no mesh
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        with axis_rules(single_pod_rules(), mesh):
            out_sh, aux_sh = jax.jit(lambda p, x: moe_apply(p, x, cfg))(p, x)
        np.testing.assert_allclose(np.asarray(out_local), np.asarray(out_sh),
                                   rtol=2e-4, atol=2e-5)
        # aux loss is per-shard averaged in the sharded path (standard
        # micro-batch-level load-balance loss) — same scale, not identical
        assert np.isfinite(float(aux_sh)) and 0.2 < float(aux_sh)/float(aux_local) < 5.0
        print("MOE-OK")
    """)


def test_embedding_bag_sharded_matches_local():
    run_subprocess("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.distributed.context import axis_rules, single_pod_rules
        from repro.models.embedding import EmbeddingSpec, embedding_bag, init_table
        spec = EmbeddingSpec((100, 60, 200), 8, pad_to_multiple=8)
        table = init_table(jax.random.PRNGKey(0), spec)
        rng = np.random.default_rng(0)
        ids = np.stack([rng.integers(0, v, size=(16, 2)) for v in spec.vocab_sizes], 1)
        ids[:, :, 1] = np.where(rng.uniform(size=(16, 3)) < 0.5, -1, ids[:, :, 1])
        ids = jnp.asarray(ids.astype(np.int32))
        ref = embedding_bag(table, ids, spec)  # no mesh -> local
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        with axis_rules(single_pod_rules(), mesh):
            got = jax.jit(lambda t, i: embedding_bag(t, i, spec))(table, ids)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(got), rtol=1e-5, atol=1e-6)
        print("EMB-OK")
    """)


def test_lm_train_step_sharded_matches_single():
    """One SGD-free loss eval: sharded vs single-device (tiny MoE LM)."""
    run_subprocess("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.distributed.context import axis_rules, single_pod_rules
        from repro.models.transformer import TransformerConfig, init_params, train_loss
        from repro.models.moe import MoEConfig
        # aux_loss_coef=0: the aux term is per-shard averaged when sharded
        # (tested separately); here we check the CE path is identical.
        cfg = TransformerConfig(name="t", n_layers=2, d_model=32, n_heads=4,
                                n_kv_heads=2, d_ff=64, vocab=64, dtype=jnp.float32,
                                chunk_q=16, aux_loss_coef=0.0,
                                moe=MoEConfig(n_experts=8, top_k=2, d_ff=32,
                                              capacity_factor=8.0))
        params = init_params(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 64)
        l0 = float(train_loss(params, {"tokens": toks}, cfg))
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        with axis_rules(single_pod_rules(), mesh):
            l1 = float(jax.jit(lambda p, b: train_loss(p, b, cfg))(params, {"tokens": toks}))
        assert abs(l0 - l1) < 5e-3, (l0, l1)
        print("LM-OK")
    """)


def test_train_restart_after_injected_failure(tmp_path):
    """Failure injection + auto-resume: the restart continues training."""
    ckpt = str(tmp_path / "ck")
    base = [sys.executable, "-m", "repro.launch.train", "--arch", "deepfm",
            "--reduced", "--steps", "40", "--batch", "64", "--ckpt-dir", ckpt,
            "--ckpt-every", "10", "--log-every", "100"]
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r1 = subprocess.run(base + ["--fail-at-step", "25"], capture_output=True,
                        text=True, env=env, cwd=REPO, timeout=600)
    assert r1.returncode != 0 and "injected failure" in r1.stderr
    steps = sorted(d for d in os.listdir(ckpt) if d.startswith("step_"))
    assert steps, "no checkpoint committed before failure"
    r2 = subprocess.run(base + ["--resume", "auto"], capture_output=True,
                        text=True, env=env, cwd=REPO, timeout=600)
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "resumed from step 20" in r2.stdout
    summary = json.loads(r2.stdout.strip().splitlines()[-1])
    assert summary["steps_run"] == 20  # 40 total - 20 resumed


def test_elastic_mesh_choice():
    assert choose_mesh_shape(512, 16) == (32, 16)
    assert choose_mesh_shape(496, 16) == (31, 16)  # lost a host: DP shrinks
    assert choose_mesh_shape(504, 16) == (31, 16)
    # policy prefers preserving the TP axis over using every survivor
    assert choose_mesh_shape(7, 16) == (1, 4)
    assert choose_mesh_shape(24, 8) == (3, 8)


def test_elastic_reshard_subprocess():
    """Lose 4 of 8 devices -> rebuild mesh -> state is intact."""
    run_subprocess("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.distributed.elastic import make_elastic_mesh, reshard
        devs = jax.devices()
        mesh1 = make_elastic_mesh(devs, model_pref=4)      # (2, 4)
        x = jnp.arange(64.0).reshape(8, 8)
        x1 = jax.device_put(x, NamedSharding(mesh1, P("data", "model")))
        survivors = devs[:4]                               # pod loses 4 chips
        mesh2 = make_elastic_mesh(survivors, model_pref=4) # (1, 4)
        x2 = reshard(x1, NamedSharding(mesh2, P("data", "model")))
        np.testing.assert_array_equal(np.asarray(x2), np.asarray(x))
        assert mesh2.devices.shape == (1, 4)
        print("ELASTIC-OK")
    """)


def test_heartbeat_monitor():
    t = [0.0]
    hb = HeartbeatMonitor(n_hosts=3, timeout=10.0, clock=lambda: t[0])
    assert hb.dead_hosts() == []
    t[0] = 5.0
    hb.beat(0)
    hb.beat(1)
    t[0] = 12.0  # host 2 last beat at 0 -> dead
    assert hb.dead_hosts() == [2]
    assert hb.alive_hosts() == [0, 1]


def test_straggler_policy():
    sp = StragglerPolicy(factor=2.0, window=8, min_samples=3)
    for step in range(6):
        for h in range(4):
            sp.report(h, 1.0 if h != 3 else 3.5)  # host 3 is 3.5x median
    assert sp.stragglers() == [3]


def test_restart_budget():
    rb = RestartBudget(max_restarts=2, horizon_s=100.0)
    assert rb.record(now=0.0)
    assert rb.record(now=10.0)
    assert not rb.record(now=20.0)  # 3rd within horizon -> crash-loop
    assert rb.record(now=200.0)  # old events expired
