"""Pallas kernel sweeps: xnor_popcount + binarize_pack vs pure-jnp oracles.

Shapes sweep tile-aligned / ragged / tiny / paper-sized (S=4608, the max
CNN vector size from Sec. IV-C); all four epilogue modes; dtype checks.
Runs in interpret mode on CPU (the kernel body executes exactly).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import packing
from repro.kernels import ops, ref
from repro.kernels.binarize_pack import binarize_pack
from repro.kernels.xnor_popcount import xnor_popcount_matmul

SHAPES = [
    (1, 1, 32),       # minimal
    (4, 7, 33),       # ragged everything
    (128, 128, 2048),  # tile-aligned
    (130, 129, 300),  # off-tile
    (64, 256, 4608),  # paper's max CNN vector size
    (3, 512, 96),
]

BLOCKS = [dict(bm=32, bn=32, bk=4),
          dict(bm=128, bn=128, bk=64)]


@pytest.mark.parametrize("m,n,s", SHAPES)
@pytest.mark.parametrize("mode", ["bitcount", "dot", "binary_act"])
def test_xnor_kernel_matches_oracle(m, n, s, mode):
    k1, k2 = jax.random.split(jax.random.PRNGKey(m * 7 + n * 3 + s))
    ip = packing.pack_bits(jax.random.bernoulli(k1, 0.5, (m, s)).astype(jnp.uint32))
    wp = packing.pack_bits(jax.random.bernoulli(k2, 0.5, (n, s)).astype(jnp.uint32))
    want = ref.xnor_popcount_matmul_ref(ip, wp, s, mode=mode)
    for blocks in BLOCKS:
        got = xnor_popcount_matmul(ip, wp, s, mode=mode, **blocks)
        assert got.dtype == want.dtype
        assert (np.asarray(got) == np.asarray(want)).all(), (m, n, s, mode, blocks)


@pytest.mark.parametrize("m,n,s", SHAPES[:4])
def test_xnor_kernel_dot_scaled(m, n, s):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    ip = packing.pack_bits(jax.random.bernoulli(k1, 0.5, (m, s)).astype(jnp.uint32))
    wp = packing.pack_bits(jax.random.bernoulli(k2, 0.5, (n, s)).astype(jnp.uint32))
    alpha = jax.random.uniform(k3, (n,), minval=0.1, maxval=2.0)
    got = xnor_popcount_matmul(ip, wp, s, mode="dot_scaled", alpha=alpha,
                               bm=32, bn=32, bk=8)
    want = ref.xnor_popcount_matmul_ref(ip, wp, s, mode="dot_scaled", alpha=alpha)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


@pytest.mark.parametrize("m,s", [(1, 32), (67, 333), (256, 2048), (5, 31)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_binarize_pack_sweep(m, s, dtype):
    x = jax.random.normal(jax.random.PRNGKey(m + s), (m, s)).astype(dtype)
    got = binarize_pack(x.astype(jnp.float32), bm=16, bkw=4)
    want = ref.binarize_pack_ref(x.astype(jnp.float32))
    assert (np.asarray(got) == np.asarray(want)).all()


def test_bnn_dense_paths_agree():
    """pallas == xla == STE-train float path (exact binarization algebra)."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(k1, (9, 300))
    w = jax.random.normal(k2, (300, 33))
    yp = ops.bnn_dense(x, w, precision="bnn", impl="pallas")
    yx = ops.bnn_dense(x, w, precision="bnn", impl="xla")
    yt = ops.bnn_dense(x, w, precision="bnn_train")
    np.testing.assert_allclose(np.asarray(yp), np.asarray(yx), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(yp), np.asarray(yt), rtol=1e-4,
                               atol=1e-4)


def test_bnn_dense_grad_flows():
    k1, k2 = jax.random.split(jax.random.PRNGKey(4))
    x = jax.random.normal(k1, (4, 64))
    w = jax.random.normal(k2, (64, 8)) * 0.1

    def loss(w):
        return jnp.sum(ops.bnn_dense(x, w, precision="bnn_train") ** 2)

    g = jax.grad(loss)(w)
    assert np.isfinite(np.asarray(g)).all()
    assert float(jnp.abs(g).sum()) > 0


def test_kernel_fused_comparator_is_pca_activation():
    """binary_act epilogue == paper's compare(z, 0.5*z_max) (Sec. II-A)."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(5))
    s = 200
    i01 = jax.random.bernoulli(k1, 0.5, (8, s)).astype(jnp.uint32)
    w01 = jax.random.bernoulli(k2, 0.5, (16, s)).astype(jnp.uint32)
    ip, wp = packing.pack_bits(i01), packing.pack_bits(w01)
    act = xnor_popcount_matmul(ip, wp, s, mode="binary_act", bm=8, bn=8, bk=2)
    z = ref.xnor_popcount_matmul_ref(ip, wp, s, mode="bitcount")
    want = (np.asarray(z) > 0.5 * s).astype(np.uint8)
    assert (np.asarray(act) == want).all()
