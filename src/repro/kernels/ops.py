"""Public jit'd wrappers around the Pallas kernels.

``bnn_dense`` is the entry point the model layers use:
  * precision="bf16": ordinary MXU matmul (baseline / non-binarized path)
  * precision="bnn_train": STE-binarized MXU matmul (differentiable)
  * precision="bnn": packed XNOR-popcount inference path
      impl="pallas"  the fused binarize->pack->XNOR-popcount kernel
                     (kernels/fused_bnn.py): packed activations never
                     round-trip through HBM (interpret=True off-TPU)
      impl="xla"     same packed math in plain XLA ops — the
                     differential oracle, and shardable under the
                     512-device dry-run partitioner (see DESIGN.md)
      impl="auto"    pallas on TPU, xla elsewhere (resolve_impl); the
                     module default can be overridden with
                     ``set_default_impl`` (kernel benches / TPU runs)

Weight packing is cached per weight identity: ``binarize_pack(w.T)``
and ``alpha = mean(|w|)`` are static across forwards, so concrete
weight arrays pack exactly once (a weakref-evicted side table).  Under
jit tracing ``w`` is a Tracer and the pack stays inline in the traced
graph — XLA CSEs it within a step, and the serving engine's jitted
steps hold weights as arguments, so the cache serves the eager callers
(benchmarks, legacy loop, tests).
"""
from __future__ import annotations

import functools
import weakref

import jax
import jax.numpy as jnp

from repro.core import packing, xnor
from repro.kernels import binarize_pack as _bp
from repro.kernels import fused_bnn as _fb
from repro.kernels import xnor_popcount as _xp

Array = jax.Array

_DEFAULT_IMPL = "auto"


def set_default_impl(impl: str) -> str:
    """Set the module-wide BNN impl used when callers say "auto";
    returns the previous default.  "auto" restores backend dispatch."""
    global _DEFAULT_IMPL
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(f"unknown bnn impl {impl!r}")
    prev, _DEFAULT_IMPL = _DEFAULT_IMPL, impl
    return prev


def resolve_impl(impl: str = "auto") -> str:
    """'auto' -> module default -> 'pallas' on TPU / 'xla' elsewhere."""
    if impl == "auto":
        impl = _DEFAULT_IMPL
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"unknown bnn impl {impl!r}")
    return impl


@functools.partial(jax.jit, static_argnames=("s", "mode"))
def xnor_matmul(ip: Array, wp: Array, s: int, mode: str = "dot",
                alpha: Array | None = None) -> Array:
    """jit'd packed XNOR GEMM via the Pallas kernel."""
    return _xp.xnor_popcount_matmul(ip, wp, s, mode=mode, alpha=alpha)


@functools.partial(jax.jit, static_argnames=("threshold",))
def pack_activations(x: Array, threshold: float = 0.0) -> Array:
    """jit'd fused binarize+pack via the Pallas kernel."""
    return _bp.binarize_pack(x, threshold=threshold)


def xnor_matmul_xla(ip: Array, wp: Array, s: int, mode: str = "dot",
                    alpha: Array | None = None) -> Array:
    """Packed XNOR GEMM in plain XLA ops (identical math, shardable)."""
    z = xnor.xnor_matmul_packed(ip, wp, s)
    if mode == "bitcount":
        return z
    if mode == "dot":
        return 2 * z - s
    if mode == "dot_scaled":
        return ((2 * z - s).astype(jnp.float32) * alpha[None, :])
    if mode == "binary_act":
        return (z > s / 2).astype(jnp.uint8)
    raise ValueError(mode)


# --------------------------------------------------------------------------
# packed-weight cache: one pack per concrete weight identity

_weight_pack_cache: dict[tuple[int, str, bool], tuple[Array, Array | None]] \
    = {}


def packed_weight_cache_info() -> dict:
    return {"entries": len(_weight_pack_cache)}


def clear_packed_weight_cache():
    _weight_pack_cache.clear()


def _pack_weight(w: Array, impl: str, scale: bool
                 ) -> tuple[Array, Array | None]:
    """(N, Kw) packed transpose of w plus its LQ-Nets alpha column
    scales; cached per concrete array identity (weakref-evicted)."""
    def compute():
        with jax.named_scope("weight_pack"):
            alpha = jnp.mean(jnp.abs(w), axis=0) if scale else None
            if impl == "pallas":
                wp = _bp.binarize_pack(w.astype(jnp.float32).T)
            else:
                wp = jnp.swapaxes(packing.pack_pm1(w, axis=0), 0, 1)
        return wp, alpha

    if isinstance(w, jax.core.Tracer):
        return compute()              # inside jit: stays in the graph
    key = (id(w), impl, scale)
    hit = _weight_pack_cache.get(key)
    if hit is not None:
        return hit
    entry = compute()
    _weight_pack_cache[key] = entry
    # id() values recycle after gc — evict the entry with its owner
    try:
        weakref.finalize(w, _weight_pack_cache.pop, key, None)
    except TypeError:
        pass                          # not weakref-able: keep (rare)
    return entry


def bnn_dense(x: Array, w: Array, *, precision: str = "bf16",
              impl: str = "auto", scale: bool = True) -> Array:
    """Dense projection with selectable precision path.

    x: (..., K) activations; w: (K, N) latent weights (float).
    """
    if precision == "bf16":
        return jnp.matmul(x, w.astype(x.dtype))
    if precision == "bnn_train":
        lead = x.shape[:-1]
        y = xnor.bnn_matmul_train(x.reshape(-1, x.shape[-1]), w, scale=scale)
        return y.reshape(*lead, w.shape[-1])
    if precision == "bnn":
        impl = resolve_impl(impl)
        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        s = x2.shape[-1]
        mode = "dot_scaled" if scale else "dot"
        wp, alpha = _pack_weight(w, impl, scale)
        if impl == "pallas":
            # one fused kernel: binarize+pack x in VMEM, XNOR-popcount
            # against the cached packed weights — no packed-activation
            # round-trip through HBM
            y = _fb.fused_bnn_matmul(x2, wp, s, mode=mode, alpha=alpha)
        else:
            ip = packing.pack_pm1(x2, axis=-1)
            y = xnor_matmul_xla(ip, wp, s, mode=mode, alpha=alpha)
        return y.reshape(*lead, w.shape[-1]).astype(x.dtype)
    raise ValueError(f"unknown precision {precision!r}")
