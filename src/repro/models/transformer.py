"""Unified decoder LM covering all assigned architecture families.

Families map onto one layer plan:
  dense         GQA attention + GLU FFN                  (llama/qwen/gemma/...)
  moe           GQA (+SWA) or MLA attention + MoE FFN    (mixtral/deepseek)
  ssm           Mamba-2 SSD mixer, no FFN                (mamba2)
  hybrid        1 attention per `attn_period` layers,
                MoE every `moe_every` layers             (jamba)
  audio / vlm   dense backbone, stub modality frontend
                (precomputed frame/patch embeddings)     (musicgen/pixtral)

The repeated layer period is stacked and driven by ``jax.lax.scan`` so
lowering stays compact for 28-72 layer models at 512 devices.  Leading
non-periodic layers (DeepSeek's first dense layer) form an unrolled
prefix segment.

Every projection dispatches through the OXBNN precision modes
(kernels/ops.bnn_dense): bf16 baseline, bnn_train (STE), bnn (packed
XNOR-popcount inference).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.layers import attn_block, common as C, ffn, mamba2, mla, moe

Array = jax.Array


# ---------------------------------------------------------------------------
# layer plan


def layer_plan(cfg: ArchConfig) -> list[tuple[str, str]]:
    """Per-layer (mixer, ffn) kinds."""
    plan = []
    for i in range(cfg.n_layers):
        if cfg.attn_kind == "none":
            mix = "ssm"
        elif cfg.attn_period:
            mix = "gqa" if i % cfg.attn_period == cfg.attn_offset else "ssm"
        else:
            mix = cfg.attn_kind
        if cfg.n_experts and i >= cfg.first_dense and \
                i % max(cfg.moe_every, 1) == max(cfg.moe_every, 1) - 1:
            f = "moe"
        elif cfg.d_ff or (i < cfg.first_dense and cfg.dense_d_ff):
            f = "dense"
        else:
            f = "none"
        plan.append((mix, f))
    return plan


def segments(cfg: ArchConfig):
    """[('unroll', plan_prefix)] + [('scan', period_plan, n_groups)]."""
    plan = layer_plan(cfg)
    segs = []
    i = cfg.first_dense
    if i:
        segs.append(("unroll", plan[:i], 1))
    rest = plan[i:]
    p = cfg.scan_period
    assert len(rest) % p == 0, (cfg.name, len(rest), p)
    period = rest[:p]
    for j in range(0, len(rest), p):
        assert rest[j:j + p] == period, "scan_period does not tile the plan"
    segs.append(("scan", period, len(rest) // p))
    return segs


# ---------------------------------------------------------------------------
# single layer


def _init_layer(key, cfg: ArchConfig, mix: str, f: str, dense_width: bool):
    ks = jax.random.split(key, 4)
    p: dict[str, Any] = {}
    s: dict[str, Any] = {}
    p["norm1"], s["norm1"] = C.norm_init(cfg.d_model, cfg.norm)
    if mix == "gqa":
        p["attn"], s["attn"] = attn_block.init(ks[0], cfg)
    elif mix == "mla":
        p["attn"], s["attn"] = mla.init(ks[0], cfg)
    elif mix == "ssm":
        p["attn"], s["attn"] = mamba2.init(ks[0], cfg)
    if f != "none":
        p["norm2"], s["norm2"] = C.norm_init(cfg.d_model, cfg.norm)
    if f == "dense":
        width = cfg.dense_d_ff if (dense_width and cfg.dense_d_ff) else cfg.d_ff
        p["ffn"], s["ffn"] = ffn.init(ks[1], cfg.d_model, width, cfg.act)
    elif f == "moe":
        p["ffn"], s["ffn"] = moe.init(
            ks[1], cfg.d_model, cfg.moe_d_ff or cfg.d_ff, cfg.n_experts,
            cfg.act, n_shared=cfg.n_shared_experts,
            shared_d_ff=cfg.moe_d_ff or cfg.d_ff)
    return p, s


def _apply_layer(params, cfg: ArchConfig, mix: str, f: str, x, positions,
                 precision: str):
    h = C.norm(x, params["norm1"], cfg.norm, cfg.norm_eps)
    if mix == "gqa":
        y = attn_block.forward(params["attn"], cfg, h, positions,
                               precision=precision)
    elif mix == "mla":
        y = mla.forward(params["attn"], cfg, h, positions, precision=precision,
                        window=cfg.sliding_window)
    elif mix == "ssm":
        y = mamba2.forward(params["attn"], cfg, h, chunk=cfg.ssd_chunk,
                           precision=precision)
    x = x + y
    aux = jnp.zeros((), jnp.float32)
    if f != "none":
        h = C.norm(x, params["norm2"], cfg.norm, cfg.norm_eps)
        if f == "moe":
            y, aux = moe.forward(params["ffn"], h, top_k=cfg.top_k, kind=cfg.act,
                                 capacity_factor=cfg.capacity_factor,
                                 precision=precision,
                                 dispatch_groups=cfg.moe_dispatch_groups,
                                 reduce_bf16=cfg.tp_reduce_bf16)
        else:
            y = ffn.forward(params["ffn"], h, cfg.act, precision)
        x = x + y
    x = C.lsc(x, "batch", None, None)
    return x, aux


def _init_cache_layer(cfg: ArchConfig, mix: str, batch: int, max_len: int,
                      dtype):
    if mix == "gqa":
        return attn_block.init_cache(cfg, batch, max_len, dtype)
    if mix == "mla":
        return mla.init_cache(cfg, batch, max_len, dtype)
    return mamba2.init_cache(cfg, batch, dtype)


def _decode_layer(params, cfg: ArchConfig, mix: str, f: str, x, cache, length,
                  precision: str):
    h = C.norm(x, params["norm1"], cfg.norm, cfg.norm_eps)
    if mix == "gqa":
        y, cache = attn_block.decode_step(params["attn"], cfg, h, cache, length,
                                          precision=precision)
    elif mix == "mla":
        y, cache = mla.decode_step(params["attn"], cfg, h, cache, length,
                                   precision=precision)
    else:
        y, cache = mamba2.decode_step(params["attn"], cfg, h, precision=precision,
                                      cache=cache)
    x = x + y
    if f != "none":
        h = C.norm(x, params["norm2"], cfg.norm, cfg.norm_eps)
        if f == "moe":
            # drop-free routing (capacity_factor=0): inference must not
            # let batch composition or padding decide which tokens keep
            # their expert slots (see moe.forward)
            y, _ = moe.forward(params["ffn"], h, top_k=cfg.top_k, kind=cfg.act,
                               capacity_factor=0.0,
                               precision=precision)
        else:
            y = ffn.forward(params["ffn"], h, cfg.act, precision)
        x = x + y
    return x, cache


# ---------------------------------------------------------------------------
# full model


def init(key, cfg: ArchConfig):
    """Returns (params, specs).  Use ``abstract_init`` for the dry-run."""
    keys = jax.random.split(key, 4)
    params: dict[str, Any] = {}
    specs: dict[str, Any] = {}
    params["embed"], specs["embed"] = C.embed_init(keys[0], cfg.vocab, cfg.d_model)
    params["final_norm"], specs["final_norm"] = C.norm_init(cfg.d_model, cfg.norm)
    if not cfg.tie_embeddings:
        params["head"], specs["head"] = C.dense_init(
            keys[1], cfg.d_model, cfg.vocab, ("embed", "vocab"))

    segs = segments(cfg)
    params["segments"], specs["segments"] = [], []
    kidx = 2
    for kind, plan, n_groups in segs:
        if kind == "unroll":
            ps, ss = [], []
            for li, (mix, f) in enumerate(plan):
                p, s = _init_layer(jax.random.fold_in(keys[kidx], li), cfg,
                                   mix, f, dense_width=True)
                ps.append(p)
                ss.append(s)
            params["segments"].append(ps)
            specs["segments"].append(ss)
        else:
            spec_cell = {}

            def one_group(k):
                p = {}
                for li, (mix, f) in enumerate(plan):
                    pl, sl = _init_layer(jax.random.fold_in(k, li), cfg, mix, f,
                                         dense_width=False)
                    p[f"l{li}"] = pl
                    spec_cell[f"l{li}"] = sl
                return p

            gkeys = jax.random.split(jax.random.fold_in(keys[kidx], 997), n_groups)
            stacked = jax.vmap(one_group)(gkeys)
            params["segments"].append(stacked)
            # prepend the scan ("layers") axis to every leaf spec
            specs["segments"].append(jax.tree.map(
                lambda axes: ("layers",) + tuple(axes),
                spec_cell, is_leaf=lambda x: isinstance(x, tuple)))
        kidx += 1
    return params, specs


def abstract_init(cfg: ArchConfig, seed: int = 0):
    """(ShapeDtypeStruct params, specs) without allocating anything."""
    cell = {}

    def f(key):
        p, s = init(key, cfg)
        cell["specs"] = s
        return p

    shapes = jax.eval_shape(f, jax.random.PRNGKey(seed))
    return shapes, cell["specs"]


def _embed_inputs(params, cfg: ArchConfig, batch) -> tuple[Array, Array]:
    """Build the input hidden sequence + positions from the batch dict."""
    parts = []
    if "prefix_embeds" in batch:     # vlm patch embeddings (stub frontend)
        parts.append(batch["prefix_embeds"])
    if "embeds" in batch:            # audio frame embeddings (stub frontend)
        parts.append(batch["embeds"])
    if "tokens" in batch:
        e = params["embed"]["w"][batch["tokens"]]
        parts.append(e)
    h = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    if cfg.embed_scale:
        h = h * jnp.asarray(cfg.d_model ** 0.5, h.dtype)
    b, t = h.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
    h = C.lsc(h, "batch", None, None)
    return h, positions


def hidden_states(params, cfg: ArchConfig, batch, *,
                  remat: bool = False) -> tuple[Array, Array]:
    """Run the decoder stack; returns (hidden (B,T,d), aux_loss).

    remat=True checkpoints each scan step (one layer period): activation
    memory becomes O(n_groups * layer_io) instead of O(full stack).
    """
    x, positions = _embed_inputs(params, cfg, batch)
    aux_total = jnp.zeros((), jnp.float32)
    for (kind, plan, n_groups), seg_params in zip(segments(cfg),
                                                  params["segments"]):
        if kind == "unroll":
            for (mix, f), p in zip(plan, seg_params):
                x, aux = _apply_layer(p, cfg, mix, f, x, positions,
                                      cfg.precision)
                aux_total += aux
        else:
            def body(carry, gp):
                xc, auxc = carry
                for li, (mix, f) in enumerate(plan):
                    xc, a = _apply_layer(gp[f"l{li}"], cfg, mix, f, xc,
                                         positions, cfg.precision)
                    auxc = auxc + a
                return (xc, auxc), None

            if remat:
                policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                          if cfg.remat_policy == "dots"
                          else jax.checkpoint_policies.nothing_saveable)
                body = jax.checkpoint(body, policy=policy)
            (x, aux_total), _ = jax.lax.scan(body, (x, aux_total), seg_params)
    x = C.norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    return x, aux_total


def _head_matrix(params, cfg: ArchConfig) -> Array:
    return params["embed"]["w"].T if cfg.tie_embeddings else params["head"]["w"]


def logits_fn(params, cfg: ArchConfig, batch) -> Array:
    h, _ = hidden_states(params, cfg, batch)
    return jnp.einsum("btd,dv->btv", h, _head_matrix(params, cfg))


def loss_fn(params, cfg: ArchConfig, batch, *, loss_chunk: int = 2048,
            aux_weight: float = 0.01, remat: bool = False):
    """Chunked next-token cross entropy (never materializes (B,T,V))."""
    h, aux = hidden_states(params, cfg, batch, remat=remat)
    labels = batch["labels"]
    # loss applies to the token tail of the sequence (prefix embeds are
    # conditioning only)
    t_lab = labels.shape[1]
    h = h[:, -t_lab:]
    head = _head_matrix(params, cfg)

    b, t, d = h.shape
    loss_chunk = min(loss_chunk, t)
    pad = (-t) % loss_chunk
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    nch = (t + pad) // loss_chunk
    h = h.reshape(b, nch, loss_chunk, d).transpose(1, 0, 2, 3)
    labels = labels.reshape(b, nch, loss_chunk).transpose(1, 0, 2)

    def chunk_loss(carry, hl):
        hc, lc = hl
        logits = jnp.einsum("btd,dv->btv", hc, head).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(lc, 0)[..., None], axis=-1)[..., 0]
        mask = (lc >= 0).astype(jnp.float32)
        nll = (logz - gold) * mask
        tot, cnt = carry
        return (tot + jnp.sum(nll), cnt + jnp.sum(mask)), None

    (tot, cnt), _ = jax.lax.scan(
        chunk_loss, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (h, labels))
    loss = tot / jnp.maximum(cnt, 1.0)
    return loss + aux_weight * aux, {"xent": loss, "aux": aux}


# ---------------------------------------------------------------------------
# decode


def init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.float32):
    caches = []
    for kind, plan, n_groups in segments(cfg):
        if kind == "unroll":
            caches.append([_init_cache_layer(cfg, mix, batch, max_len, dtype)
                           for (mix, f) in plan])
        else:
            cell = {f"l{li}": _init_cache_layer(cfg, mix, batch, max_len, dtype)
                    for li, (mix, f) in enumerate(plan)}
            caches.append(jax.tree.map(
                lambda a: jnp.zeros((n_groups,) + a.shape, a.dtype), cell))
    return caches


def _cache_spec_layer(mix: str):
    """Logical sharding axes matching _init_cache_layer layouts."""
    if mix == "gqa":
        return {"k": ("batch", None, "kv_heads_dim", "head_dim"),
                "v": ("batch", None, "kv_heads_dim", "head_dim")}
    if mix == "mla":
        return {"c_kv": ("batch", None, "kv_lora"),
                "k_rope": ("batch", None, None)}
    return {"h": ("batch", "ssm_heads", None, None),
            "conv": ("batch", None, "ssm_inner")}


def cache_specs(cfg: ArchConfig):
    """Spec tree mirroring init_cache's structure."""
    out = []
    for kind, plan, n_groups in segments(cfg):
        if kind == "unroll":
            out.append([_cache_spec_layer(mix) for (mix, f) in plan])
        else:
            cell = {f"l{li}": _cache_spec_layer(mix)
                    for li, (mix, f) in enumerate(plan)}
            out.append(jax.tree.map(
                lambda axes: ("layers",) + tuple(axes), cell,
                is_leaf=lambda x: isinstance(x, tuple)))
    return out


# ---------------------------------------------------------------------------
# paged decode / chunked prefill (continuous-batching serving path; see
# repro/serving/engine.py)
#
# Every mixer kind exposes the same three entry points
# (init_paged_state / paged_decode_step / prefill_chunk) over its own
# state layout — paged KV blocks (gqa), paged compressed latents (mla),
# or a per-request recurrent slot (ssm); sliding-window configs run
# their block tables as ring buffers (ring=True).  The functions below
# dispatch per layer through layer_plan, so heterogeneous stacks
# (hybrid ssm+attention) mix layouts freely.


def init_paged_state(cfg: ArchConfig, num_blocks: int, block_size: int,
                     num_slots: int = 0, dtype=jnp.float32):
    """Flat per-layer list of mixer-state pools (layer order == plan
    order): block pools for attention layers, slot pools for SSM."""
    states = []
    for mix, _f in layer_plan(cfg):
        if mix == "gqa":
            states.append(attn_block.init_paged_state(
                cfg, num_blocks, block_size, dtype))
        elif mix == "mla":
            states.append(mla.init_paged_state(
                cfg, num_blocks, block_size, dtype))
        else:
            assert num_slots >= 2, (cfg.name, num_slots)
            states.append(mamba2.init_paged_state(cfg, num_slots, dtype))
    return states


def _iter_layers(cfg: ArchConfig, params):
    """Yield (mix, ffn_kind, layer_params) in plan order, unrolling
    scan-stacked segments (static indexing — paged serving runs the
    stack unrolled so each layer's pool buffer aliases in place)."""
    for (kind, plan, n_groups), seg_params in zip(segments(cfg),
                                                  params["segments"]):
        if kind == "unroll":
            for (mix, f), p in zip(plan, seg_params):
                yield mix, f, p
        else:
            for gi in range(n_groups):
                gp = jax.tree.map(
                    lambda a: jax.lax.index_in_dim(a, gi, 0, keepdims=False),
                    seg_params)
                for li, (mix, f) in enumerate(plan):
                    yield mix, f, gp[f"l{li}"]


def _paged_ffn(params, cfg: ArchConfig, f: str, x, precision):
    if f == "none":
        return x
    with jax.named_scope("ffn"):
        h = C.norm(x, params["norm2"], cfg.norm, cfg.norm_eps)
        if f == "moe":
            # drop-free routing: a finite capacity makes logits depend on
            # chunk width / bucket padding (jamba divergence root cause)
            y, _ = moe.forward(params["ffn"], h, top_k=cfg.top_k,
                               kind=cfg.act, capacity_factor=0.0,
                               precision=precision)
        else:
            y = ffn.forward(params["ffn"], h, cfg.act, precision)
        return x + y


def _paged_head(params, cfg: ArchConfig, x):
    """Final norm and the (tied) vocabulary projection."""
    with jax.named_scope("head"):
        x = C.norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
        return jnp.einsum("btd,dv->btv", x, _head_matrix(params, cfg))


def paged_decode_step(params, cfg: ArchConfig, tokens: Array, caches,
                      block_table: Array, lengths: Array,
                      active: Array | None = None,
                      slots: Array | None = None, *, ring: bool = False,
                      attn_impl: str = "auto"):
    """One decode token per row against the paged mixer-state pools.

    tokens (B, 1) int32; block_table (B, max_blocks); lengths (B,)
    per-row cache fill; active (B,) masks padded batch slots; slots (B,)
    recurrent slot ids for SSM layers; ring=True runs attention block
    tables as sliding-window ring buffers.
    Returns (logits (B, 1, V), new_caches).
    """
    x = params["embed"]["w"][tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    new_caches = []
    for li, (mix, f, p) in enumerate(_iter_layers(cfg, params)):
        with jax.named_scope("attn"):
            h = C.norm(x, p["norm1"], cfg.norm, cfg.norm_eps)
            if mix == "gqa":
                y, nc = attn_block.paged_decode_step(
                    p["attn"], cfg, h, caches[li], block_table, lengths,
                    precision=cfg.precision, active=active, ring=ring,
                    attn_impl=attn_impl)
            elif mix == "mla":
                y, nc = mla.paged_decode_step(
                    p["attn"], cfg, h, caches[li], block_table, lengths,
                    precision=cfg.precision, active=active, ring=ring,
                    attn_impl=attn_impl)
            else:
                y, nc = mamba2.paged_decode_step(
                    p["attn"], cfg, h, caches[li], slots,
                    precision=cfg.precision, active=active)
        new_caches.append(nc)
        x = _paged_ffn(p, cfg, f, x + y, cfg.precision)
    return _paged_head(params, cfg, x), new_caches


def prefill_chunk(params, cfg: ArchConfig, tokens: Array, caches,
                  block_table: Array, lengths: Array, n_valid: Array,
                  slots: Array | None = None, *, ring: bool = False,
                  attn_impl: str = "auto"):
    """Jitted chunked prefill: append a chunk of C tokens per row.

    tokens (B, C) int32 (padded past n_valid); lengths (B,) tokens
    already cached; n_valid (B,) real tokens in this chunk; slots (B,)
    recurrent slot ids for SSM layers.
    Returns (logits (B, C, V), new_caches) — logits cover every chunk
    position, so the caller reads position n_valid-1 for the first
    generated token and can check logit equivalence at all positions.
    """
    x = params["embed"]["w"][tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    new_caches = []
    for li, (mix, f, p) in enumerate(_iter_layers(cfg, params)):
        with jax.named_scope("attn"):
            h = C.norm(x, p["norm1"], cfg.norm, cfg.norm_eps)
            if mix == "gqa":
                y, nc = attn_block.prefill_chunk(
                    p["attn"], cfg, h, caches[li], block_table, lengths,
                    n_valid, precision=cfg.precision, ring=ring,
                    attn_impl=attn_impl)
            elif mix == "mla":
                y, nc = mla.prefill_chunk(
                    p["attn"], cfg, h, caches[li], block_table, lengths,
                    n_valid, precision=cfg.precision, ring=ring,
                    attn_impl=attn_impl)
            else:
                y, nc = mamba2.prefill_chunk(
                    p["attn"], cfg, h, caches[li], slots, n_valid,
                    precision=cfg.precision)
        new_caches.append(nc)
        x = _paged_ffn(p, cfg, f, x + y, cfg.precision)
    return _paged_head(params, cfg, x), new_caches


def snapshot_slot_state(cfg: ArchConfig, caches, slots: Array) -> list:
    """Per-layer pre-step snapshots of the recurrent slots (None for
    block-family layers).  Block layouts roll back by rewinding
    ``lengths`` — stale writes past the committed length are masked —
    but an SSM slot folds every verified token into its state, so the
    only rollback is restore-and-re-advance from this snapshot."""
    return [mamba2.snapshot_slots(caches[li], slots) if mix == "ssm" else None
            for li, (mix, _f) in enumerate(layer_plan(cfg))]


def restore_slot_state(cfg: ArchConfig, caches, slots: Array, snaps: list):
    """Write slot snapshots back (speculative rollback), block-family
    layers untouched."""
    return [caches[li] if snap is None
            else mamba2.restore_slots(caches[li], slots, snap)
            for li, snap in enumerate(snaps)]


def spec_verify(params, cfg: ArchConfig, tokens: Array, caches,
                block_table: Array, lengths: Array, n_valid: Array,
                slots: Array | None = None, *, ring: bool = False,
                attn_impl: str = "auto"):
    """Multi-token speculative verify: one prefill-shaped forward over
    ``[last_token, draft...]`` rows scores every draft position at once.

    Same contract as ``prefill_chunk`` (logits at all C positions,
    per-row lengths/n_valid), plus pre-step recurrent-slot snapshots so
    the caller can roll back rejected suffixes: block/ring layouts
    rewind by committing only ``lengths + accepted``, slot layouts
    restore the snapshot and re-advance by the accepted prefix
    (``restore_slot_state`` + a masked ``prefill_chunk``).
    Returns (logits (B, C, V), new_caches, slot_snapshots).
    """
    snaps = snapshot_slot_state(cfg, caches, slots)
    logits, caches = prefill_chunk(params, cfg, tokens, caches, block_table,
                                   lengths, n_valid, slots, ring=ring,
                                   attn_impl=attn_impl)
    return logits, caches, snaps


def decode_step(params, cfg: ArchConfig, tokens: Array, caches, length, *,
                unroll: bool | None = None):
    """tokens (B, 1) int32; length: scalar int32 current cache fill.
    Returns (logits (B,1,V), new_caches).

    unroll=True iterates the layer stack in Python instead of lax.scan:
    a scan's carried/stacked cache outputs cannot alias its inputs, so
    the scanned form double-buffers the ENTIRE KV cache (+17 GB/device
    at 32k x bs128) — unrolled, XLA aliases each layer's donated cache
    buffer in place.  Default: unroll only when the plan carries
    attention KV caches (SSM states are small and scan compiles much
    faster).  See EXPERIMENTS.md §Perf (decode cell).
    """
    if unroll is None:
        unroll = any(mix in ("gqa", "mla") for mix, _ in layer_plan(cfg))
    x = params["embed"]["w"][tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    new_caches = []
    for (kind, plan, n_groups), seg_params, seg_cache in zip(
            segments(cfg), params["segments"], caches):
        if kind == "unroll":
            ncs = []
            for (mix, f), p, c in zip(plan, seg_params, seg_cache):
                x, nc = _decode_layer(p, cfg, mix, f, x, c, length,
                                      cfg.precision)
                ncs.append(nc)
            new_caches.append(ncs)
        elif unroll:
            stacked = seg_cache
            for gi in range(n_groups):
                gp = jax.tree.map(lambda a: a[gi], seg_params)
                gc = jax.tree.map(
                    lambda a: jax.lax.index_in_dim(a, gi, 0, keepdims=False),
                    stacked)
                ngc = {}
                for li, (mix, f) in enumerate(plan):
                    x, ngc[f"l{li}"] = _decode_layer(
                        gp[f"l{li}"], cfg, mix, f, x, gc[f"l{li}"], length,
                        cfg.precision)
                # write the group's caches back in place (aliasable DUS
                # chain on the single stacked buffer)
                stacked = jax.tree.map(
                    lambda full, new: jax.lax.dynamic_update_index_in_dim(
                        full, new, gi, 0), stacked, ngc)
            new_caches.append(stacked)
        else:
            def body(xc, pc):
                gp, gc = pc
                ngc = {}
                for li, (mix, f) in enumerate(plan):
                    xc, ngc[f"l{li}"] = _decode_layer(
                        gp[f"l{li}"], cfg, mix, f, xc, gc[f"l{li}"], length,
                        cfg.precision)
                return xc, ngc

            x, nc = jax.lax.scan(body, x, (seg_params, seg_cache))
            new_caches.append(nc)
    x = C.norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    logits = jnp.einsum("btd,dv->btv", x, _head_matrix(params, cfg))
    return logits, new_caches
