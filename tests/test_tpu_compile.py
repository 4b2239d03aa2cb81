"""Ahead-of-time compiles of the Pallas kernels for a TPU v5e chip.

The TPU compiler ships with jaxlib, so a chip that is described (not
attached) is enough to compile for: these tests catch what interpret
mode cannot — block shapes the tiling refuses, reshapes Mosaic cannot
lay out, kernels that overflow VMEM — at the real widths of the models
the engine serves (qwen1.5-0.5b GQA, deepseek-v2-lite MLA latents,
mixtral sliding-window rings).  Nothing runs: numerics stay with the
interpret-mode differentials in test_xnor_kernel.py and
test_paged_kernels.py.

The topology is described only inside the module fixture below, so that
importing this file (every xdist worker does) never loads the TPU
library; kernels are called with ``interpret=False`` because on the CPU
backend their default is interpret mode.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.dist import sharding as S
from repro.kernels import binarize_pack as bp
from repro.kernels import fused_bnn as fb
from repro.kernels import paged_attention as pa
from repro.kernels import xnor_popcount as xp
from repro.serving.sampling import sample_tokens


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compile_text(topo):
    """compile(fn, *(shape, dtype)) -> compiled text, for one v5e chip.

    The persistent compile cache is off meanwhile: entries written for
    a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    dev = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=dev) for s, d in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def compile_tpu(compile_text):
    """compile_text for a program that holds a Pallas kernel."""
    def compile_(fn, *shapes):
        text = compile_text(fn, *shapes)
        assert "tpu_custom_call" in text
        return text
    return compile_


# qwen1.5-0.5b projections as (K, N): q/k/v/o, gate/up, down, and the
# 151936-wide (tied) head width
QWEN_GEMMS = [(1024, 1024), (1024, 2816), (2816, 1024), (1024, 151936)]


@pytest.mark.parametrize("m", [8, 64], ids=["decode8", "prefill64"])
@pytest.mark.parametrize("k,n", QWEN_GEMMS)
def test_fused_bnn_compiles(compile_tpu, m, k, n):
    kw = -(-k // 32)
    compile_tpu(
        lambda x, wp, a: fb.fused_bnn_matmul(x, wp, k, mode="dot_scaled",
                                             alpha=a, interpret=False),
        ((m, k), jnp.float32), ((n, kw), jnp.uint32), ((n,), jnp.float32))


@pytest.mark.parametrize("k,n", QWEN_GEMMS[:3])
def test_binarize_pack_weight_transpose_compiles(compile_tpu, k, n):
    """bnn_dense packs w.T (N, K) inside every jitted step."""
    compile_tpu(lambda w: bp.binarize_pack(w.T, interpret=False),
                ((k, n), jnp.float32))


def test_xnor_popcount_compiles(compile_tpu):
    compile_tpu(lambda a, w: xp.xnor_popcount_matmul(a, w, 1024,
                                                     interpret=False),
                ((8, 32), jnp.uint32), ((2816, 32), jnp.uint32))


def test_sample_tokens_compiles_without_scatter(compile_text):
    """A decode batch of 32 rows over qwen1.5-0.5b's 151936-entry
    vocabulary: the top-k/top-p filter masks in vocabulary order, so the
    program holds no scatter (a TPU expands one over every row's
    vocabulary into an index sort and flattened fusions)."""
    b, v = 32, 151936
    text = compile_text(sample_tokens, ((b, v), jnp.float32),
                        ((b,), jnp.int32), ((b,), jnp.uint32),
                        ((b,), jnp.float32), ((b,), jnp.int32),
                        ((b,), jnp.float32))
    # every instruction's opcode: the first lower-case word after its
    # (possibly tuple) shape that opens the operand list
    ops = re.findall(r"^\s*(?:ROOT )?%\S+ = .*?\s([a-z][\w-]*)\(", text,
                     re.M)
    assert "sort" in ops
    assert not [op for op in ops if "scatter" in op]


# published head counts and dims; block_size 16, 16 blocks per row
PAGED = {
    # qwen1.5-0.5b: 16 heads (kv 16) x 64
    "gqa": dict(h=16, dq=64, a=(16, 16, 64), b=(16, 16, 64)),
    # deepseek-v2-lite: 16 heads, nope 128 + rope 64, kv_lora 512, v 128
    "mla": dict(h=16, dq=192, a=(16, 512), b=(16, 64), layout="mla"),
    # mixtral-8x7b: 32 heads over 8 kv heads x 128, window 4096, ring
    "ring": dict(h=32, dq=128, a=(16, 8, 128), b=(16, 8, 128), ring=True,
                 window=4096),
}


@pytest.mark.parametrize("c", [1, 64], ids=["decode", "prefill64"])
@pytest.mark.parametrize("variant", sorted(PAGED))
def test_paged_attention_compiles(compile_tpu, variant, c):
    v = dict(PAGED[variant])
    b, nb, mb = 8, 129, 16
    h, dq, sa, sb = v.pop("h"), v.pop("dq"), v.pop("a"), v.pop("b")
    layout = v.pop("layout", "gqa")
    ups = ([((512, h * 128), jnp.float32)] * 2 if layout == "mla" else [])

    def fn(q, pool_a, pool_b, table, kv_len, q_off, newest, *up):
        kw = dict(v)
        if up:
            kw.update(k_up=up[0], v_up=up[1], nope_dim=128)
        return pa.paged_attention(
            q, pool_a, pool_b, table, kv_len=kv_len, q_offset=q_off,
            newest=newest if v.get("ring") else None, layout=layout,
            causal=c > 1, interpret=False, **kw)

    i32 = jnp.int32
    compile_tpu(fn, ((b, c, h, dq), jnp.float32), ((nb,) + sa, jnp.float32),
                ((nb,) + sb, jnp.float32), ((b, mb), i32), ((b,), i32),
                ((b,), i32), ((b,), i32), *ups)


def test_shard_meshes_one_chip_per_shard(topo):
    """Shards land on distinct chips when there are enough; chips are
    never tiled (tiling is for the CPU-device tests only)."""
    meshes = S.shard_meshes(4, devices=topo.devices)
    assert [m.devices.flat[0] for m in meshes] == list(topo.devices)
    with pytest.raises(ValueError):
        S.shard_meshes(5, devices=topo.devices)


# the serving step programs at qwen1.5-0.5b widths, one layer deep: the
# names a profiler trace of the chip attributes device time by
KERNEL_NAMES = ("paged_attention", "bnn_xnor_gemm", "bnn_binarize_pack")
SCOPES = ("attn", "kv_update", "ffn", "head", "sample", "weight_pack")


@pytest.fixture(scope="module")
def step_programs(topo, compile_tpu, request):
    """Compiled text of the engine's decode (bucket 8) and prefill (chunk
    64) programs for one v5e chip, with the Pallas kernels selected as
    on the chip (the platform is reported as "tpu" while tracing)."""
    import numpy as np

    from repro import configs
    from repro.models import transformer as M
    from repro.serving import EngineConfig
    from repro.serving import roles as R

    mp = pytest.MonkeyPatch()
    request.addfinalizer(mp.undo)
    mp.setattr(jax, "default_backend", lambda: "tpu")
    dev = SingleDeviceSharding(topo.devices[0])
    cfg = configs.get_config("qwen1.5-0.5b").replace(n_layers=1,
                                                     precision="bnn")
    ecfg = EngineConfig(block_size=16, num_blocks=65, max_batch=8,
                        prefill_chunk=64, max_model_len=256)
    fns = R.build_step_fns(cfg, ecfg, R.get_role("mixed"), ring=False,
                           spec_k=0)

    def spec(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=dev), tree)

    params = spec(M.abstract_init(cfg)[0])
    pools = spec(jax.eval_shape(lambda: M.init_paged_state(
        cfg, ecfg.num_blocks, ecfg.block_size)))
    mb = ecfg.max_model_len // ecfg.block_size

    def rows(b):
        return spec([np.zeros(b, np.uint32), np.zeros(b, np.float32),
                     np.zeros(b, np.int32), np.ones(b, np.float32)])

    def i32(*shape):
        return spec(np.zeros(shape, np.int32))

    text = {}
    text["decode"] = fns.decode.lower(
        params, pools, i32(8, 1), i32(8, mb), i32(8), spec(np.zeros(8, bool)),
        i32(8), *rows(8)).compile().as_text()
    text["prefill"] = fns.prefill.lower(
        params, pools, i32(1, ecfg.prefill_chunk), i32(1, mb), i32(1),
        i32(1), i32(1), *rows(1)).compile().as_text()
    return text


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_step_programs_carry_kernel_names_and_scopes(step_programs,
                                                     program):
    text = step_programs[program]
    # every Pallas kernel is a tpu_custom_call instruction named after it
    calls = [line.split("=", 1)[0].split()[-1].lstrip("%")
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for name in KERNEL_NAMES:
        assert any(c.startswith(name) for c in calls), (name, calls)
    op_names = set()
    for line in text.splitlines():
        if 'op_name="' in line:
            op_names.update(line.split('op_name="', 1)[1].split('"', 1)[0]
                            .split("/"))
    for scope in SCOPES:
        assert scope in op_names, scope
