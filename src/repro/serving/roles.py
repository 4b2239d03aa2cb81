"""Worker roles for disaggregated prefill/decode serving.

Production serving splits the two phases of a request's life onto
different workers: prefill is a long batched pipeline fill, decode is a
latency-bound steady state, and co-scheduling them on one shard makes
each pay the other's bottleneck (the serving analogue of the paper's
weight-stationary OXG pipeline argument — amortize the expensive fill
across many wavelength-parallel activations, keep the steady state
hot).  This module is the role layer both ``Engine`` and ``Scheduler``
consult:

  * ``mixed``   — today's behavior and the correctness oracle: one
                  worker interleaves chunked prefill into its decode
                  batch.  The default everywhere;
  * ``prefill`` — runs chunked prefill ONLY.  A prompt that completes
                  emits its first token locally (the chunk-final logits
                  row is already there), then parks for handoff: the
                  ``ShardedEngine`` streams its finished blocks and
                  recurrent snapshots to a decode shard over the
                  content-hash swap-to-peer path;
  * ``decode``  — runs the full datapath (it must: rescued prompts from
                  a dead prefill shard recompute here) but the
                  placement plane never routes fresh prompts to it
                  while a prefill shard is alive.

Role objects are behavior flags, not subclasses: the single-engine
datapath stays one code path and a role only gates which plan rows run
and whether finished prefills park for handoff.  Because sampling keys
are a pure function of (seed, position) and handoffs ride the same
swap serialization as migration, ANY topology is token-identical to
the mixed-role oracle — tests/test_roles.py pins this per arch family.

``build_step_fns`` also lives here: the jitted prefill / decode /
spec-verify / spec-repair closure construction extracted from
``Engine.__init__``, built per role (a prefill worker never compiles
the decode or verify graphs).

Transfer accounting: a handoff moves ``host_bytes(req)`` over the
modeled inter-shard link.  The destination's scheduler keeps the
request parked (``transfer_pending`` defer reason) until the modeled
transfer has overlapped ``req.transfer_steps`` of its decode steps —
the admission-side half of the cost model's ``transfer_latency_s``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.models import transformer as M
from repro.serving.sampling import sample_tokens


@dataclass(frozen=True)
class Role:
    """Behavior flags of one worker role (see module docstring)."""
    name: str
    runs_decode: bool      # decode / spec-verify plan rows run here
    hands_off: bool        # completed prefills park for peer handoff


MIXED = Role("mixed", runs_decode=True, hands_off=False)
PREFILL = Role("prefill", runs_decode=False, hands_off=True)
DECODE = Role("decode", runs_decode=True, hands_off=False)

ROLES = {r.name: r for r in (MIXED, PREFILL, DECODE)}


def get_role(name: str) -> Role:
    try:
        return ROLES[name]
    except KeyError:
        raise ValueError(
            f"unknown role {name!r}; expected one of {sorted(ROLES)}") \
            from None


def parse_roles(spec: str, n_shards: int | None = None) -> list[str]:
    """Parse a topology spec into a per-shard role list.

    Two forms:
      * ``"P:D"`` counts — ``"1:2"`` = one prefill shard + two decode
        shards (the standard disaggregated topology flag);
      * comma-separated names — ``"prefill,decode,decode"``.

    Validates against ``n_shards`` when given and requires at least one
    decode-capable shard (a prefill-only fleet can never finish).
    """
    spec = spec.strip()
    if ":" in spec and "," not in spec:
        p_s, d_s = spec.split(":", 1)
        p, d = int(p_s), int(d_s)
        if p < 0 or d < 1:
            raise ValueError(
                f"roles spec {spec!r}: need >= 0 prefill and >= 1 "
                "decode shards")
        roles = ["prefill"] * p + ["decode"] * d
    else:
        roles = [r.strip() for r in spec.split(",") if r.strip()]
    validate_roles(roles, n_shards)
    return roles


def validate_roles(roles: list[str], n_shards: int | None = None):
    for r in roles:
        get_role(r)
    if not any(get_role(r).runs_decode for r in roles):
        raise ValueError(
            f"role topology {roles} has no decode-capable shard — "
            "nothing could ever finish a request")
    if n_shards is not None and len(roles) != n_shards:
        raise ValueError(
            f"{len(roles)} roles for {n_shards} shards: {roles}")


# ------------------------------------------------------------- transfer

def host_bytes(req) -> int:
    """Bytes a handoff/migration of ``req`` moves over the inter-shard
    link: the serialized host buffers ``swap_out`` produced (KV block
    tails + recurrent slot snapshots — content the destination already
    holds by hash was never copied) plus the token stream itself."""
    n = req.prompt.nbytes + 4 * len(req.out)
    for bufs in (req.host_kv, req.host_state):
        if bufs:
            for layer in bufs:
                if layer is None:
                    continue
                for arr in (layer.values() if hasattr(layer, "values")
                            else layer):
                    if arr is not None and hasattr(arr, "nbytes"):
                        n += arr.nbytes
    return n


def transfer_pending(req, step: int) -> bool:
    """Admission-side transfer gate: True while ``req`` is still
    streaming over the modeled link (the destination scheduler defers
    it with reason ``transfer_pending``); clears the marks and returns
    False once ``step`` reaches the arrival deadline."""
    until = getattr(req, "transfer_until_step", None)
    if until is None:
        return False
    if step < until:
        return True
    req.transfer_until_step = None
    req.transfer_steps = 0
    return False


# ------------------------------------------------------ jitted closures

@dataclass(frozen=True)
class StepFns:
    """The engine's jitted step closures, built per role: a prefill
    worker only compiles the prefill graph; decode-capable roles get
    the full set (``spec``/``repair`` only when ``spec_k > 0``)."""
    prefill: Callable
    decode: Callable | None = None
    spec: Callable | None = None
    repair: Callable | None = None


def build_step_fns(cfg, ecfg, role: Role, *, ring: bool,
                   spec_k: int) -> StepFns:
    """Construct the jitted prefill/decode/spec-verify/repair closures
    for one worker (extracted from ``Engine.__init__``).  ``cfg`` /
    ``ecfg`` / ``ring`` are baked in as closure constants; params and
    the mixer-state pools stay arguments (pools are donated — XLA
    updates touched blocks/slots in place)."""
    cfg_ = cfg
    ring_ = ring
    attn_impl_ = ecfg.attn_impl

    def _pin_bnn(fn):
        # the BNN impl is resolved at TRACE time inside bnn_dense;
        # pinning the module default around the traced body bakes the
        # engine's choice into the jitted graph without threading an
        # impl kwarg through every layer signature
        if ecfg.bnn_impl == "auto":
            return fn

        def wrapped(*a, **kw):
            prev = kops.set_default_impl(ecfg.bnn_impl)
            try:
                return fn(*a, **kw)
            finally:
                kops.set_default_impl(prev)
        return wrapped

    def _prefill(params, pools, tokens, table, lengths, n_valid, slots,
                 seeds, temps, top_k, top_p):
        logits, pools = M.prefill_chunk(params, cfg_, tokens, pools,
                                        table, lengths, n_valid, slots,
                                        ring=ring_, attn_impl=attn_impl_)
        # chunk-final logits row -> the would-be next token (used by
        # the engine only when this chunk completes the prompt)
        with jax.named_scope("sample"):
            gather = jnp.maximum(n_valid - 1, 0)[:, None, None]
            last = jnp.take_along_axis(
                logits, jnp.broadcast_to(
                    gather, (logits.shape[0], 1, logits.shape[2])),
                axis=1)[:, 0]
            tok = sample_tokens(last, lengths + n_valid,
                                seeds, temps, top_k, top_p)
        return tok, logits, pools

    prefill_fn = jax.jit(_pin_bnn(_prefill), donate_argnums=(1,))
    if not role.runs_decode:
        return StepFns(prefill=prefill_fn)

    def _decode(params, pools, tokens, table, lengths, active, slots,
                seeds, temps, top_k, top_p):
        logits, pools = M.paged_decode_step(params, cfg_, tokens, pools,
                                            table, lengths, active,
                                            slots, ring=ring_,
                                            attn_impl=attn_impl_)
        with jax.named_scope("sample"):
            tok = sample_tokens(logits[:, -1], lengths + 1,
                                seeds, temps, top_k, top_p)
        return tok, logits, pools

    decode_fn = jax.jit(_pin_bnn(_decode), donate_argnums=(1,))
    if not spec_k:
        return StepFns(prefill=prefill_fn, decode=decode_fn)

    def _spec(params, pools, tokens, table, lengths, n_valid, slots,
              draft, seeds, temps, top_k, top_p):
        b, c = tokens.shape
        logits, pools, snaps = M.spec_verify(
            params, cfg_, tokens, pools, table, lengths, n_valid,
            slots, ring=ring_, attn_impl=attn_impl_)
        # sample EVERY position with its own (seed, index) key —
        # identical to what plain decoding would draw there
        idx = (lengths[:, None] + 1
               + jnp.arange(c, dtype=jnp.int32)[None, :])
        rep = lambda a: jnp.repeat(a, c)
        with jax.named_scope("sample"):
            sampled = sample_tokens(
                logits.reshape(b * c, -1), idx.reshape(-1),
                rep(seeds), rep(temps), rep(top_k), rep(top_p)
            ).reshape(b, c)
        # accepted draft prefix: position j counts while the verifier's
        # token agrees with the draft's
        j = jnp.arange(c - 1, dtype=jnp.int32)[None, :]
        ok = (sampled[:, :-1] == draft) & (j < (n_valid - 1)[:, None])
        acc = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1),
                      axis=1)
        n_commit = jnp.where(n_valid > 0, acc + 1, 0)
        return sampled, n_commit, pools, snaps

    def _repair(params, pools, tokens, table, lengths, n_commit,
                slots, snaps):
        # SSM rollback for partially-accepted rows: restore the
        # pre-verify slot snapshots, then re-advance every row by
        # exactly its committed prefix (masked prefill re-writes
        # identical K/V for block layers — idempotent)
        pools = M.restore_slot_state(cfg_, pools, slots, snaps)
        _, pools = M.prefill_chunk(params, cfg_, tokens, pools,
                                   table, lengths, n_commit, slots,
                                   ring=ring_, attn_impl=attn_impl_)
        return pools

    return StepFns(
        prefill=prefill_fn, decode=decode_fn,
        spec=jax.jit(_pin_bnn(_spec), donate_argnums=(1,)),
        repair=jax.jit(_pin_bnn(_repair), donate_argnums=(1,)))
