"""Substrate tests: optimizer, schedule, checkpointing, data pipeline,
gradient compression, roofline parser."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.ckpt import CheckpointManager, load_pytree, save_pytree
from repro.data import TokenPipeline, TokenPipelineConfig
from repro.optim import AdamWConfig, adamw_init, adamw_update, cosine_schedule
from repro.optim.grad_compress import compress, compress_ef, decompress
from repro.roofline.analysis import collective_bytes, roofline_terms


# ---------------------------------------------------------------------- optim

def test_adamw_reduces_quadratic():
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0, grad_clip=100.0)
    params = {"w": jnp.array([5.0, -3.0, 2.0])}
    state = adamw_init(cfg, params)
    for _ in range(200):
        grads = {"w": 2.0 * params["w"]}
        params, state, _ = adamw_update(cfg, grads, state, params,
                                        jnp.asarray(0.1))
    assert float(jnp.max(jnp.abs(params["w"]))) < 0.1


def test_adamw_grad_clip():
    cfg = AdamWConfig(lr=1e-3, grad_clip=1.0)
    params = {"w": jnp.zeros(4)}
    state = adamw_init(cfg, params)
    _, _, m = adamw_update(cfg, {"w": jnp.full(4, 100.0)}, state, params,
                           jnp.asarray(1e-3))
    assert float(m["grad_norm"]) == pytest.approx(200.0, rel=1e-3)


def test_adamw_master_fp32_for_bf16_params():
    cfg = AdamWConfig()
    params = {"w": jnp.zeros(8, jnp.bfloat16)}
    state = adamw_init(cfg, params)
    assert "master" in state and state["master"]["w"].dtype == jnp.float32
    # f32 params: no master (avoids donation aliasing)
    state2 = adamw_init(cfg, {"w": jnp.zeros(8, jnp.float32)})
    assert "master" not in state2


def test_cosine_schedule_shape():
    s = cosine_schedule(1e-3, warmup=100, total=1000)
    assert float(s(jnp.asarray(0))) == 0.0
    assert float(s(jnp.asarray(100))) == pytest.approx(1e-3, rel=1e-5)
    assert float(s(jnp.asarray(1000))) == pytest.approx(1e-4, rel=1e-3)


# ----------------------------------------------------------------------- ckpt

def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(5, dtype=jnp.float32),
            "b": {"c": jnp.ones((2, 3), jnp.bfloat16)}}
    p = str(tmp_path / "x.npz")
    save_pytree(p, tree)
    out = load_pytree(p, jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree))
    assert np.allclose(out["a"], np.arange(5))
    assert out["b"]["c"].dtype == jnp.bfloat16


def test_checkpoint_manager_keep_and_resume(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    for step in (1, 2, 3):
        mgr.save(step, {"w": jnp.full(3, float(step))})
    assert mgr.steps() == [2, 3]
    out = mgr.restore({"w": jax.ShapeDtypeStruct((3,), jnp.float32)})
    assert np.allclose(out["w"], 3.0)
    # atomic: no tmp debris
    assert not [f for f in os.listdir(tmp_path) if f.startswith(".tmp")]


def test_checkpoint_async(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3, async_save=True)
    mgr.save(7, {"w": jnp.ones(4)})
    mgr.wait()
    assert mgr.latest_step() == 7


# ----------------------------------------------------------------------- data

def test_token_pipeline_deterministic_and_restart_safe():
    cfg = TokenPipelineConfig(vocab_size=1000, global_batch=8, seq_len=32, seed=3)
    p1, p2 = TokenPipeline(cfg), TokenPipeline(cfg)
    a1, t1 = p1.global_batch_at(jnp.asarray(17))
    a2, t2 = p2.global_batch_at(jnp.asarray(17))
    assert np.array_equal(np.asarray(a1), np.asarray(a2))
    assert np.array_equal(np.asarray(t1), np.asarray(t2))
    b1, _ = p1.global_batch_at(jnp.asarray(18))
    assert not np.array_equal(np.asarray(a1), np.asarray(b1))
    # host shard slicing is consistent with the global batch
    s0, _ = p1.host_shard_at(17, 0, 4)
    assert np.array_equal(np.asarray(s0), np.asarray(a1[:2]))
    assert int(a1.max()) < 1000 and int(a1.min()) >= 0


# ----------------------------------------------------------------- compression

def test_compress_roundtrip_error_small():
    x = jax.random.normal(jax.random.PRNGKey(0), (1000,))
    q, s = compress(x)
    x2 = decompress(q, s, x.shape)
    err = float(jnp.max(jnp.abs(x - x2)))
    assert err <= float(jnp.max(jnp.abs(x))) / 127.0 + 1e-6


def test_error_feedback_accumulates():
    x = jax.random.normal(jax.random.PRNGKey(1), (512,)) * 1e-3
    residual = jnp.zeros_like(x)
    total_sent = jnp.zeros_like(x)
    for _ in range(50):
        q, s, residual = compress_ef(x, residual)
        total_sent = total_sent + decompress(q, s, x.shape)
    # over many steps the *sum* of transmitted grads converges to 50x
    rel = float(jnp.linalg.norm(total_sent - 50 * x) / jnp.linalg.norm(50 * x))
    assert rel < 0.05


def test_compressed_psum_multi_device_subprocess():
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import numpy as np, jax, jax.numpy as jnp
        from repro.optim.grad_compress import compressed_psum
        from repro.launch.mesh import make_conquer_mesh
        mesh = make_conquer_mesh("pod")
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 300))
        out = compressed_psum(x, mesh, "pod")
        want = jnp.sum(x, 0)
        for i in range(4):
            rel = float(jnp.linalg.norm(out[i] - want) / jnp.linalg.norm(want))
            assert rel < 0.02, rel
        print("OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr


# -------------------------------------------------------------------- roofline

def test_collective_parser_counts_ops():
    hlo = """
  %ag = bf16[16,1024]{1,0} all-gather(%x), replica_groups={}
  %ar = f32[256]{0} all-reduce(%y), to_apply=%add
  %rs = f32[64,32]{1,0} reduce-scatter(%z), dimensions={0}
  %aa = bf16[8,8]{1,0} all-to-all(%w), dimensions={0}
  %cp = f32[4]{0} collective-permute(%v), source_target_pairs={{0,1}}
  %other = f32[2]{0} add(%a, %b)
"""
    out = collective_bytes(hlo)
    assert out["counts"]["all-gather"] == 1
    assert out["counts"]["all-reduce"] == 1
    ag = 16 * 1024 * 2
    ar = 256 * 4 * 2.0      # 2x multiplier
    rs = 64 * 32 * 4
    aa = 8 * 8 * 2
    cp = 4 * 4
    assert out["total_bytes"] == pytest.approx(ag + ar + rs + aa + cp)


def test_roofline_terms_bottleneck():
    cost = {"flops": 1e15, "bytes accessed": 1e9}
    t = roofline_terms(cost, coll_bytes=1e6)
    assert t["bottleneck"] == "compute"
    t2 = roofline_terms({"flops": 1e9, "bytes accessed": 1e12}, 1e6)
    assert t2["bottleneck"] == "memory"
    t3 = roofline_terms({"flops": 1e9, "bytes accessed": 1e9}, 1e12)
    assert t3["bottleneck"] == "collective"
