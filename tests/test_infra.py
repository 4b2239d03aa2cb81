"""Infrastructure tests: data determinism, checkpoint fault tolerance,
optimizer, gradient compression, fault/elasticity planning, sharding."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # tiny deterministic fallback (tests/_hypothesis_shim.py)
    from _hypothesis_shim import given, settings, strategies as st

from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.dist import fault
from repro.dist import sharding as S
from repro.optim import compress, optimizer as opt_mod

settings.register_profile("ci", max_examples=10, deadline=None)
settings.load_profile("ci")


# ---------------------------------------------------------------- data

def test_data_deterministic_and_sharded():
    cfg = DataConfig(vocab=97, seq_len=16, global_batch=8, seed=3)
    a = SyntheticLM(cfg).batch(5)
    b = SyntheticLM(cfg).batch(5)
    assert (a["tokens"] == b["tokens"]).all()
    # shards assemble exactly into the single-host global batch
    sharded = SyntheticLM(cfg, 0, 4).global_batch_for_test(5)
    # shard streams differ from each other
    s0 = SyntheticLM(cfg, 0, 4).batch(5)
    s1 = SyntheticLM(cfg, 1, 4).batch(5)
    assert not (s0["tokens"] == s1["tokens"]).all()
    assert sharded["tokens"].shape == (8, 16)
    # labels are next tokens
    assert (a["labels"][:, :-1] == a["tokens"][:, 1:]).all()


def test_data_markov_structure_learnable():
    cfg = DataConfig(vocab=64, seq_len=32, global_batch=4, seed=0, branching=4)
    d = SyntheticLM(cfg)
    b = d.batch(0)
    # every transition is one of the `branching` successors
    succ = d.successors
    ok = np.isin(b["labels"], succ[b["tokens"]])
    # labels[i] must be a successor of tokens[i]
    for bi in range(4):
        for t in range(31):
            assert b["tokens"][bi, t + 1] in succ[b["tokens"][bi, t]]


# ---------------------------------------------------------------- ckpt

def test_checkpoint_roundtrip_and_rotation(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    tree = {"w": jnp.arange(6.0).reshape(2, 3), "b": jnp.ones(3)}
    for step in (1, 2, 3):
        mgr.save(step, jax.tree.map(lambda x: x * step, tree))
    assert mgr.all_steps() == [2, 3]  # rotated
    restored, step = mgr.restore(tree)
    assert step == 3
    np.testing.assert_allclose(restored["w"], np.arange(6.0).reshape(2, 3) * 3)


def test_checkpoint_crash_safety(tmp_path):
    """A checkpoint without COMMITTED must be invisible to restore."""
    mgr = CheckpointManager(str(tmp_path))
    tree = {"w": jnp.ones(4)}
    mgr.save(7, tree)
    # simulate a crash mid-write of step 9: dir without COMMITTED
    broken = os.path.join(str(tmp_path), "step_0000009")
    os.makedirs(broken)
    with open(os.path.join(broken, "arrays.npz"), "wb") as f:
        f.write(b"garbage")
    restored, step = mgr.restore(tree)
    assert step == 7


def test_checkpoint_integrity_detection(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    tree = {"w": jnp.ones(4)}
    path = mgr.save(3, tree)
    npz = os.path.join(path, "arrays.npz")
    with open(npz, "r+b") as f:
        f.seek(30)
        f.write(b"\x00\x01")
    with pytest.raises(IOError):
        mgr.restore(tree)


def test_checkpoint_async(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_write=True)
    mgr.save(1, {"w": jnp.ones(3)})
    mgr.wait()
    assert mgr.latest_step() == 1


# ------------------------------------------------------------- optimizer

def test_adamw_reduces_quadratic():
    cfg = opt_mod.AdamWConfig(lr_peak=0.1, warmup_steps=2, total_steps=100,
                              weight_decay=0.0)
    params = {"w": jnp.array([3.0, -2.0])}
    state = opt_mod.init(cfg, params)
    for _ in range(60):
        grads = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(params)
        params, state, m = opt_mod.update(cfg, grads, state, params)
    assert float(jnp.abs(params["w"]).max()) < 0.5


def test_adamw_clip_and_schedule():
    cfg = opt_mod.AdamWConfig(lr_peak=1.0, warmup_steps=10, total_steps=100,
                              clip_norm=1.0)
    assert float(opt_mod.schedule(cfg, jnp.int32(0))) == 0.0
    assert float(opt_mod.schedule(cfg, jnp.int32(10))) == pytest.approx(1.0)
    assert float(opt_mod.schedule(cfg, jnp.int32(100))) == pytest.approx(0.1)
    params = {"w": jnp.zeros(3)}
    state = opt_mod.init(cfg, params)
    _, _, m = opt_mod.update(cfg, {"w": jnp.full(3, 100.0)}, state, params)
    assert float(m["grad_norm"]) == pytest.approx(np.sqrt(3) * 100, rel=1e-5)


def test_adamw_bf16_moments():
    cfg = opt_mod.AdamWConfig(moment_dtype=jnp.bfloat16)
    params = {"w": jnp.ones(4)}
    state = opt_mod.init(cfg, params)
    assert state["m"]["w"].dtype == jnp.bfloat16
    _, s2, _ = opt_mod.update(cfg, {"w": jnp.ones(4)}, state, params)
    assert s2["m"]["w"].dtype == jnp.bfloat16


# ----------------------------------------------------------- compression

@given(st.integers(0, 10 ** 6))
def test_compression_error_feedback_unbiased(seed):
    """With error feedback, the ACCUMULATED applied gradient converges to
    the accumulated true gradient: ||sum(g_hat) - sum(g)|| stays bounded
    by one quantization step, not growing with steps."""
    key = jax.random.PRNGKey(seed)
    g = {"w": jax.random.normal(key, (32,))}
    err = compress.init_state(g)
    total_hat = jnp.zeros(32)
    for i in range(20):
        ghat, err = compress.roundtrip(g, err)
        total_hat = total_hat + ghat["w"]
    total_true = 20 * g["w"]
    resid = float(jnp.abs(total_hat - total_true).max())
    qstep = float(jnp.max(jnp.abs(g["w"]))) / 127.0
    assert resid <= 2.5 * qstep  # bounded, not O(steps)


def test_compression_wire_ratio():
    stats = compress.wire_bytes({"w": jnp.zeros((128, 128))})
    assert stats["ratio"] > 3.9


# ------------------------------------------------------------- fault

def test_heartbeat_monitor():
    mon = fault.HeartbeatMonitor(n_hosts=4, dead_after=1.0,
                                 straggler_factor=2.0)
    for h in range(4):
        mon.beat(h, now=0.0, step_time=1.0 if h != 2 else 5.0)
    assert mon.stragglers() == [2]
    mon.beat(0, 2.0)
    mon.beat(1, 2.0)
    mon.beat(2, 2.0)
    assert mon.dead_hosts(2.5) == [3]
    assert mon.to_drain(2.5) == [2, 3]


def test_remesh_plan_preserves_global_batch():
    full = fault.plan_remesh(512, model_parallel=16, full_data=16, full_pod=2)
    assert full.devices_used == 512 and full.microbatch_scale == 1
    # lose a host of 8 chips -> 504 survive -> largest valid submesh
    p = fault.plan_remesh(504, model_parallel=16, full_data=16, full_pod=2)
    assert p.devices_used <= 504
    assert p.model == 16
    dp = p.pod * p.data
    assert 32 % dp == 0 and p.microbatch_scale == 32 // dp
    with pytest.raises(ValueError):
        fault.plan_remesh(8, model_parallel=16)


# ------------------------------------------------------------- sharding

def test_logical_to_pspec_dedup_and_divisibility():
    from repro.layers.common import logical_to_pspec

    class FakeMesh:
        shape = {"data": 16, "model": 16}

    rules = {"experts": "model", "embed": "data", "mlp": "model"}
    # 8 experts cannot split 16 -> dropped, mlp gets model instead
    spec = logical_to_pspec(("experts", "embed", "mlp"), rules,
                            (8, 4096, 14336), FakeMesh())
    assert tuple(spec) == (None, "data", "model")
    # 64 experts can -> dedup drops the second 'model'
    spec = logical_to_pspec(("experts", "embed", "mlp"), rules,
                            (64, 2048, 1408), FakeMesh())
    assert tuple(spec) == ("model", "data", None)


def test_param_shardings_tree(tmp_path):
    import jax
    from repro import configs
    from repro.configs.base import reduced
    from repro.models import transformer as M
    from repro.launch.mesh import device_mesh

    cfg = reduced(configs.get_config("mixtral-8x7b"))
    shapes, specs = M.abstract_init(cfg)
    mesh = device_mesh()
    shards = S.param_shardings(mesh, shapes, specs, S.rules_train(False))
    # same tree structure
    assert jax.tree.structure(shapes) == jax.tree.structure(shards)


def test_device_mesh_covers_devices_present():
    from repro.launch.mesh import device_mesh

    mesh = device_mesh()
    assert mesh.axis_names == ("data", "model")
    assert mesh.devices.shape == (len(jax.devices()), 1)
    assert device_mesh(jax.devices()[:1]).devices.shape == (1, 1)


def test_compile_cache_dir_rule(monkeypatch):
    """An outside JAX_COMPILATION_CACHE_DIR is left alone; without one
    the cache goes to the checkout's fixed .jax_cache/."""
    from repro.launch import compile_cache as cc

    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "outside-dir")
        assert cc.enable_compile_cache() == "outside-dir"
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = cc.enable_compile_cache()
        assert path == os.path.join(cc.ROOT, ".jax_cache")
        assert os.path.isfile(os.path.join(cc.ROOT, "chip_smoke.py"))
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_chip_smoke_refuses_cpu():
    """With no TPU the chip smoke run exits non-zero with no result line
    instead of falling back to the CPU."""
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, os.path.join(root, "chip_smoke.py")],
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert '"ok"' not in r.stdout
