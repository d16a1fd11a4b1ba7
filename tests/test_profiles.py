"""§Perf profile correctness: the optimized sharding profiles must
compute the same math as the single-device reference (subprocess with 8
virtual devices)."""
import os
import subprocess
import sys
import textwrap

import pytest

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


def test_fsdp_ep_rules_match_reference_loss():
    """LM loss under fsdp_ep (seq-sharded activations, ZeRO-3 params,
    EP experts) == unsharded reference."""
    run_subprocess("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.distributed.context import axis_rules, fsdp_ep_rules
        from repro.models.transformer import TransformerConfig, init_params, train_loss
        from repro.models.moe import MoEConfig
        cfg = TransformerConfig(name="t", n_layers=2, d_model=32, n_heads=4,
                                n_kv_heads=2, d_ff=64, vocab=64, dtype=jnp.float32,
                                chunk_q=8, aux_loss_coef=0.0,
                                moe=MoEConfig(n_experts=8, top_k=2, d_ff=32,
                                              capacity_factor=8.0))
        params = init_params(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 64)
        l0 = float(train_loss(params, {"tokens": toks}, cfg))
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        rules = dict(fsdp_ep_rules(False))
        with axis_rules(rules, mesh):
            l1 = float(jax.jit(lambda p, b: train_loss(p, b, cfg))(params, {"tokens": toks}))
        assert abs(l0 - l1) < 5e-3, (l0, l1)
        print("FSDP-EP-OK")
    """)


def test_a2a_recsys_profile_matches_reference_loss():
    run_subprocess("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.distributed.context import axis_rules, recsys_a2a_rules
        from repro.models import recsys
        from repro.data import recsys_batches
        cfg = recsys.RecsysConfig(
            name="t", vocab_sizes=(50, 30, 80, 20), embed_dim=8,
            interaction="fm", mlp_dims=(16,), dtype=jnp.float32,
            emb_mode="alltoall")
        params = recsys.init_params(jax.random.PRNGKey(0), cfg)
        b = next(recsys_batches(cfg.vocab_sizes, batch=32, seed=0))
        ids = jnp.asarray(b["ids"]); y = jnp.asarray(b["labels"])
        ref_cfg = recsys.RecsysConfig(**{**cfg.__dict__, "emb_mode": "psum"})
        l0 = float(recsys.bce_loss(params, {"ids": ids, "labels": y}, ref_cfg))
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        with axis_rules(recsys_a2a_rules(False), mesh):
            l1 = float(jax.jit(lambda p: recsys.bce_loss(p, {"ids": ids, "labels": y}, cfg))(params))
        assert abs(l0 - l1) < 1e-4, (l0, l1)
        print("A2A-PROFILE-OK")
    """)
