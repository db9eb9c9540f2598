"""Decoder-only LM assembly: pattern-cycled layers, scan-over-groups, caches.

Layers are stacked in groups of ``len(cfg.layer_pattern)`` and scanned with
``lax.scan`` so compile time and HLO size are independent of depth (26-60
layer configs compile as one group body).  A non-divisible remainder (e.g.
recurrentgemma: 26 = 8*(rec,rec,attn) + 2) runs as unscanned tail layers.

Layer kinds: "global" / "local" (GQA or MLA attention), "recurrent" (RG-LRU),
"ssm" (Mamba2).  The MLP is dense or MoE per config; Mamba2 blocks carry no
separate MLP (pure mixer stack, d_ff = 0).
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tracing import count, span
from repro.models import layers, moe
from repro.models.attention_layer import gqa_apply, gqa_init, init_kv_cache
from repro.models.mla_layer import (
    init_latent_cache,
    mla_absorbed_queries,
    mla_apply,
    mla_init,
    mla_latents,
    mla_scale,
    mla_unabsorb_output,
)
from repro.models.recurrent import (
    init_rglru_cache,
    rglru_block_apply,
    rglru_block_init,
)
from repro.models.ssm import init_ssm_cache, mamba2_block_apply, mamba2_block_init
from repro.runtime.kv_cache import OutOfPagesError, plan_rows, scatter_layer_rows


def _has_mlp(cfg, kind):
    return kind != "ssm" and (cfg.d_ff > 0 or cfg.n_experts > 0)


def layer_init(key, cfg, kind):
    ks = jax.random.split(key, 4)
    p: dict[str, Any] = {"ln1": layers.rmsnorm_init(cfg.d_model)}
    if kind in ("global", "local"):
        p["attn"] = mla_init(ks[0], cfg) if cfg.mla else gqa_init(ks[0], cfg)
    elif kind == "recurrent":
        p["rec"] = rglru_block_init(ks[0], cfg)
    elif kind == "ssm":
        p["ssm"] = mamba2_block_init(ks[0], cfg)
    else:
        raise ValueError(kind)
    if _has_mlp(cfg, kind):
        p["ln2"] = layers.rmsnorm_init(cfg.d_model)
        p["mlp"] = (
            moe.moe_init(ks[1], cfg)
            if cfg.n_experts
            else layers.mlp_init(ks[1], cfg.d_model, cfg.d_ff)
        )
    if cfg.post_norms:
        p["post_ln1"] = layers.rmsnorm_init(cfg.d_model)
        if _has_mlp(cfg, kind):
            p["post_ln2"] = layers.rmsnorm_init(cfg.d_model)
    return p


def layer_cache_init(cfg, kind, batch, max_len, dtype=jnp.bfloat16):
    if kind == "global":
        if cfg.mla:
            return init_latent_cache(cfg, batch, max_len, dtype)
        return init_kv_cache(cfg, batch, max_len, dtype)
    if kind == "local":
        eff = min(max_len, cfg.window or max_len)
        # window caches could be ring buffers; we keep linear for simplicity
        if cfg.mla:
            return init_latent_cache(cfg, batch, max_len, dtype)
        return init_kv_cache(cfg, batch, max_len, dtype)
    if kind == "recurrent":
        return init_rglru_cache(cfg, batch)
    if kind == "ssm":
        return init_ssm_cache(cfg, batch)
    raise ValueError(kind)


def layer_apply(
    params, x, *, cfg, kind, positions, cache=None, cache_len=None,
    dtype=jnp.bfloat16,
):
    """Pre-norm residual block.  Returns (x, new_cache, aux_loss)."""
    aux = jnp.float32(0.0)
    h = layers.rmsnorm(params["ln1"], x, eps=cfg.norm_eps)
    if kind in ("global", "local"):
        window = cfg.window if kind == "local" else None
        if cfg.mla:
            y, new_cache = mla_apply(
                params["attn"], h, cfg=cfg, positions=positions,
                cache=cache, cache_len=cache_len, dtype=dtype,
            )
        else:
            y, new_cache = gqa_apply(
                params["attn"], h, cfg=cfg, positions=positions, window=window,
                cache=cache, cache_len=cache_len, dtype=dtype,
            )
    elif kind == "recurrent":
        y, new_cache = rglru_block_apply(
            params["rec"], h, cfg=cfg, cache=cache, dtype=dtype
        )
    elif kind == "ssm":
        y, new_cache = mamba2_block_apply(
            params["ssm"], h, cfg=cfg, cache=cache, dtype=dtype
        )
    else:
        raise ValueError(kind)
    if cfg.post_norms:
        y = layers.rmsnorm(params["post_ln1"], y, eps=cfg.norm_eps)
    x = x + y

    if _has_mlp(cfg, kind):
        h = layers.rmsnorm(params["ln2"], x, eps=cfg.norm_eps)
        if cfg.n_experts:
            y, aux = moe.moe_apply(params["mlp"], h, cfg=cfg, dtype=dtype)
        else:
            y = layers.mlp(params["mlp"], h, act=cfg.act, dtype=dtype)
        if cfg.post_norms:
            y = layers.rmsnorm(params["post_ln2"], y, eps=cfg.norm_eps)
        x = x + y
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Whole-model init / apply
# ---------------------------------------------------------------------------
def _pattern_split(cfg):
    period = len(cfg.layer_pattern)
    return cfg.n_layers // period, cfg.n_layers % period


def lm_init(key, cfg):
    n_groups, rem = _pattern_split(cfg)
    keys = jax.random.split(key, 3)
    params: dict[str, Any] = {
        "embed": layers.embed_init(keys[0], cfg.vocab_size, cfg.d_model),
        "final_norm": layers.rmsnorm_init(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = layers.embed_init(keys[2], cfg.vocab_size, cfg.d_model)

    def group_init(gkey):
        gp = {}
        for j, kind in enumerate(cfg.layer_pattern):
            gp[f"pos{j}"] = layer_init(jax.random.fold_in(gkey, j), cfg, kind)
        return gp

    gkeys = jax.random.split(keys[1], max(n_groups, 1))
    if n_groups:
        params["groups"] = jax.vmap(group_init)(gkeys)
    params["rem"] = [
        layer_init(
            jax.random.fold_in(keys[1], 10_000 + j),
            cfg,
            cfg.layer_pattern[j % len(cfg.layer_pattern)],
        )
        for j in range(rem)
    ]
    return params


def lm_cache_init(cfg, batch, max_len, dtype=jnp.bfloat16):
    n_groups, rem = _pattern_split(cfg)

    def one_group(_):
        return {
            f"pos{j}": layer_cache_init(cfg, kind, batch, max_len, dtype)
            for j, kind in enumerate(cfg.layer_pattern)
        }

    cache: dict[str, Any] = {}
    if n_groups:
        # Stack per-group caches on a leading axis (mirrors params["groups"]).
        proto = one_group(None)
        cache["groups"] = jax.tree.map(
            lambda leaf: jnp.broadcast_to(leaf, (n_groups,) + leaf.shape).copy(),
            proto,
        )
    cache["rem"] = [
        layer_cache_init(
            cfg, cfg.layer_pattern[j % len(cfg.layer_pattern)], batch, max_len, dtype
        )
        for j in range(rem)
    ]
    return cache


def lm_apply(
    params,
    tokens: jax.Array,  # (B, S) int32
    *,
    cfg,
    positions: jax.Array | None = None,  # (B,S) or (3,B,S); default arange
    cache=None,
    cache_len: jax.Array | None = None,  # (B,)
    embeds_override: jax.Array | None = None,  # (B, S_vis, d) vision stub
    remat: bool = False,
    scan_unroll: bool | int = False,  # unroll layer groups (decode fast path)
    dtype=jnp.bfloat16,
):
    """Returns (hidden (B,S,d), new_cache, aux_loss).  Unembedding is the
    caller's job (chunked CE for training, unembed() for serving)."""
    b, s = tokens.shape
    n_groups, rem = _pattern_split(cfg)
    x = layers.embed(params["embed"], tokens, dtype=dtype)
    if embeds_override is not None:
        nv = embeds_override.shape[1]
        x = jnp.concatenate([embeds_override.astype(dtype), x[:, nv:]], axis=1)
    if getattr(cfg, "embed_scale", False):
        x = x * jnp.asarray(math.sqrt(cfg.d_model), dtype)
    if positions is None:
        base = cache_len if cache_len is not None else jnp.zeros((b,), jnp.int32)
        base = jnp.broadcast_to(jnp.asarray(base), (b,))  # scalar-safe
        positions = base[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]

    aux_total = jnp.float32(0.0)

    def group_body(carry, xs):
        x, aux = carry
        gparams = xs[0]
        gcache = xs[1] if cache is not None else None
        new_gcache = {}
        for j, kind in enumerate(cfg.layer_pattern):
            x, nc, a = layer_apply(
                gparams[f"pos{j}"],
                x,
                cfg=cfg,
                kind=kind,
                positions=positions,
                cache=(gcache[f"pos{j}"] if gcache is not None else None),
                cache_len=cache_len,
                dtype=dtype,
            )
            new_gcache[f"pos{j}"] = nc
            aux = aux + a
        if cache is None:
            return (x, aux), None
        return (x, aux), new_gcache

    body = jax.checkpoint(group_body) if remat else group_body

    new_cache: dict[str, Any] = {"rem": []}
    if n_groups:
        xs = (
            (params["groups"], cache["groups"])
            if cache is not None
            else (params["groups"],)
        )
        (x, aux_total), group_caches = jax.lax.scan(
            body, (x, aux_total), xs, unroll=scan_unroll
        )
        if cache is not None:
            new_cache["groups"] = group_caches
    for j in range(rem):
        kind = cfg.layer_pattern[j % len(cfg.layer_pattern)]
        x, nc, a = layer_apply(
            params["rem"][j],
            x,
            cfg=cfg,
            kind=kind,
            positions=positions,
            cache=(cache["rem"][j] if cache is not None else None),
            cache_len=cache_len,
            dtype=dtype,
        )
        new_cache["rem"].append(nc)
        aux_total = aux_total + a

    x = layers.rmsnorm(params["final_norm"], x, eps=cfg.norm_eps)
    return x, (new_cache if cache is not None else None), aux_total


def lm_logits(params, hidden, *, cfg, dtype=jnp.bfloat16):
    table = params.get("unembed", params["embed"])
    return layers.unembed(table, hidden, dtype=dtype, softcap=cfg.final_softcap)


# ---------------------------------------------------------------------------
# Paged cache backend: decode/prefill over a LayeredPagedKVCache
# ---------------------------------------------------------------------------
#
# The dense path above threads a (B, max_len) cache pytree through lm_apply;
# the paged path instead walks the layer stack host-side so each layer can
# (1) append its 576-wide latent row(s) into the shared page pool and
# (2) attend through the paged kernels with ONE decode schedule built per
# pass and reused by every layer — all L layers share the same block table
# and kv_len, so the (request, kv_block) work queue is identical for each.
# A pass uploads its inputs once; per layer it launches one program up to
# the kernels (the previous layer's post-attention half fused with this
# layer's inputs and cache write), the kernels and the combine, all on the
# stacked (L, P, page, 640) pool.  cfg is static (a frozen dataclass).


def check_paged_compatible(cfg) -> None:
    """Paged serving covers MLA attention-only stacks (the paper's regime).

    Recurrent/SSM layers keep per-slot state outside the latent pages, and
    windowed ("local") attention needs window masking the paged kernels do
    not implement — both serve via the dense backend.
    """
    if cfg.mla is None:
        raise ValueError(
            f"config {cfg.name!r} has no MLA geometry — the paged cache "
            f"backend stores 576-wide latent rows (try deepseek-v2-mla, or "
            f"serve this arch with the dense backend)"
        )
    kinds = set(cfg.layer_kinds())
    if kinds != {"global"}:
        raise ValueError(
            f"paged serving needs an all-'global' attention stack; config "
            f"{cfg.name!r} has layer kinds {sorted(kinds)}"
        )


def per_layer_params(params, cfg) -> list[dict]:
    """Unstack scanned group params into an L-element per-layer list.

    The scan-over-groups layout ((n_groups, ...) leaves) is what training
    wants; the paged decode path walks layers host-side, so it slices each
    layer's subtree out once (do this at session init, not per step).
    """
    n_groups, rem = _pattern_split(cfg)
    period = len(cfg.layer_pattern)
    out: list[dict] = []
    for g in range(n_groups):
        gp = jax.tree.map(lambda a: a[g], params["groups"])
        out.extend(gp[f"pos{j}"] for j in range(period))
    out.extend(params["rem"])
    return out


def _cfg_dtype(cfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def _paged_embed(embed_params, tokens, *, cfg):
    x = layers.embed(embed_params, tokens, dtype=_cfg_dtype(cfg))
    if getattr(cfg, "embed_scale", False):
        x = x * jnp.asarray(math.sqrt(cfg.d_model), _cfg_dtype(cfg))
    return x


def _paged_attn_inputs(p_l, x, positions, *, cfg):
    """Pre-attention half of one layer: latent rows + absorbed queries."""
    dtype = _cfg_dtype(cfg)
    h = layers.rmsnorm(p_l["ln1"], x, eps=cfg.norm_eps)
    lat = mla_latents(p_l["attn"], h, cfg=cfg, positions=positions, dtype=dtype)
    q = mla_absorbed_queries(
        p_l["attn"], h, cfg=cfg, positions=positions, dtype=dtype
    )
    return lat, q


def _paged_layer_post(p_l, x, attn, *, cfg):
    """Post-attention half: un-absorb, residual, MLP (mirrors layer_apply).

    ``attn`` holds the combine's ``(B, S*h, Dv)`` output of each head
    chunk, in head order."""
    dtype = _cfg_dtype(cfg)
    s = x.shape[1]
    parts = [a.reshape(a.shape[0], s, -1, a.shape[-1]) for a in attn]
    attn = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=2)
    y = mla_unabsorb_output(p_l["attn"], attn.astype(dtype), cfg=cfg, dtype=dtype)
    if cfg.post_norms:
        y = layers.rmsnorm(p_l["post_ln1"], y, eps=cfg.norm_eps)
    x = x + y
    if _has_mlp(cfg, "global"):
        h = layers.rmsnorm(p_l["ln2"], x, eps=cfg.norm_eps)
        if cfg.n_experts:
            y, _ = moe.moe_apply(p_l["mlp"], h, cfg=cfg, dtype=dtype)
        else:
            y = layers.mlp(p_l["mlp"], h, act=cfg.act, dtype=dtype)
        if cfg.post_norms:
            y = layers.rmsnorm(p_l["post_ln2"], y, eps=cfg.norm_eps)
        x = x + y
    return x


def _query_rows(q, positions, *, lanes, compute_dtype, head_shards):
    """The queue kernel's query rows: per head chunk ``(B, S*h, lanes)`` in
    the compute dtype (bf16 unless given), zero lanes past Dk (they add
    nothing to the scores); and each row's absolute position ``(B, S*h)``,
    which every head of a token shares.  A row attends causally up to its
    own position: the token's own latent is in the cache before the
    kernel runs, so a 1-row decode step's position is ``kv_len - 1``."""
    b, s, hq, dk = q.shape
    h = hq // head_shards
    dtype = jnp.bfloat16 if compute_dtype is None else compute_dtype
    parts = tuple(
        jnp.pad(
            q[:, :, i * h : (i + 1) * h].reshape(b, s * h, dk).astype(dtype),
            ((0, 0), (0, 0), (0, lanes - dk)),
        )
        for i in range(head_shards)
    )
    return parts, jnp.repeat(positions, h, axis=1)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "compute_dtype", "head_shards"),
    donate_argnames=("pages", "scales"),
)
def _paged_layer_in(
    embed, p_post, p_l, x, attn, dense, pages, scales, layer, *, cfg,
    compute_dtype, head_shards,
):
    """One program for everything of a layer up to its attention kernels.

    The first layer embeds the pass's tokens (``embed``); a later one runs
    the previous layer's post-attention half (``p_post``) on the residual
    ``x`` and that layer's kernel output ``attn``.  Then this layer's
    latent rows and absorbed queries, the latent write into its plane of
    the donated layered pool (``layer``; int8 pools quantize into their
    scale pool), and the kernels' query rows and positions.  ``dense``
    stacks the pass's ``(B, S)`` int32 tokens, positions and per-row write
    pages and offsets.  Optimization barriers after the residual and after
    the attention inputs keep XLA from fusing across them, so their values
    are rounded to their dtype there whatever else the program holds (the
    exact-parity tests compare against unfused forward passes).
    Returns ``(x, query row chunks, row positions, pages, scales)``.
    """
    tokens, positions, pids, offs = dense
    if p_post is None:
        x = _paged_embed(embed, tokens, cfg=cfg)
    else:
        x = _paged_layer_post(p_post, x, attn, cfg=cfg)
    x = jax.lax.optimization_barrier(x)
    lat, q = jax.lax.optimization_barrier(
        _paged_attn_inputs(p_l, x, positions, cfg=cfg)
    )
    pages, scales = scatter_layer_rows(
        pages, scales, lat.reshape(-1, lat.shape[-1]), layer,
        pids.reshape(-1), offs.reshape(-1),
    )
    q_rows, rows_pos = _query_rows(
        q, positions, lanes=pages.shape[-1], compute_dtype=compute_dtype,
        head_shards=head_shards,
    )
    return x, q_rows, rows_pos, pages, scales


@functools.partial(jax.jit, static_argnames=("cfg",))
def _paged_head(params, p_post, x, attn, idx, *, cfg):
    """The last layer's post-attention half, the final norm and the
    unembedding, in one program: logits ``(B, S, vocab)`` at every row, or
    ``(B, vocab)`` at row ``idx`` of each sequence (a traced index, so a
    ragged tail chunk does not retrace)."""
    x = jax.lax.optimization_barrier(_paged_layer_post(p_post, x, attn, cfg=cfg))
    x = layers.rmsnorm(params["final_norm"], x, eps=cfg.norm_eps)
    if idx is not None:
        x = jax.lax.dynamic_slice_in_dim(x, idx, 1, axis=1)
    logits = lm_logits(params, x, cfg=cfg, dtype=_cfg_dtype(cfg))
    return logits if idx is None else logits[:, 0]


def _paged_pass(
    params, layers_p, cache, host, schedule, *, cfg, block_k, variant,
    interpret, compute_dtype, head_shards,
):
    """One pass of the layer walk: a decode step, or one prefill chunk.

    ``host`` holds every array the pass needs, as host numpy: ``dense``
    (:func:`_paged_layer_in`), the block table ``bt`` and ``kv_len`` of the
    live requests, the schedule's ``ops.schedule_arrays`` under ``sched``
    and, for a prefill, the logits row ``idx``.  They go to the pool's
    device in one transfer; the geometry and positions were checked on the
    host, so nothing is read back.  Per layer the walk then launches
    :func:`_paged_layer_in` and, per head chunk, the queue kernel (plus the
    group-prefix kernel under a prefix schedule) and the combine, on the
    stacked pool with no per-layer copy.  Returns the device arrays and
    what :func:`_paged_head` needs: the last layer's params, ``x`` and
    ``attn``.  The programs launched count as ``walk_dispatches``.
    """
    from repro.kernels import ops

    dev = jax.device_put(host, cache.device)
    layer_ids = cache.layer_ids()
    kw = dict(
        d_v=cfg.mla.d_latent, variant=variant, scale=mla_scale(cfg),
        block_k=block_k, interpret=interpret,
    )
    kernels_per_chunk = 3 if "prefix" in dev["sched"] else 2
    p_post = x = attn = None
    for l, p_l in enumerate(layers_p):
        x, q_rows, rows_pos, cache.pages, cache.scales = _paged_layer_in(
            params["embed"] if p_post is None else None, p_post, p_l, x,
            attn, dev["dense"], cache.pages, cache.scales, layer_ids[l],
            cfg=cfg, compute_dtype=compute_dtype, head_shards=head_shards,
        )
        attn = tuple(
            ops.queue_attention(
                q, rows_pos, cache.pages, dev["bt"], dev["kv_len"], schedule,
                dev["sched"], layer=layer_ids[l], kv_scales=cache.scales,
                **kw,
            )[0]
            for q in q_rows
        )
        count("walk_dispatches", 1 + kernels_per_chunk * len(q_rows))
        p_post = p_l
    return dev, p_post, x, attn


def _check_pass(cfg, cache, bt, kv_len, block_k, head_shards, s, positions=None):
    """Host-side checks of a pass's attention geometry (and, for a
    multi-row step, its query positions) -- once per pass, on host numpy."""
    from repro.kernels import ops

    if cfg.n_heads % head_shards:
        raise ValueError(
            f"head_shards={head_shards} must divide n_heads={cfg.n_heads}"
        )
    q_like = jax.ShapeDtypeStruct(
        (bt.shape[0], s, cfg.n_heads // head_shards, cache.width), jnp.bfloat16
    )
    ops.validate_paged_call(
        q_like, cache.pages, bt, kv_len, block_k=block_k,
        kv_scales=cache.scales, q_positions=positions,
    )


def lm_prefill_paged(
    params,
    tokens,  # (S,) prompt token ids (host ints / 1D array)
    *,
    cfg,
    cache,  # runtime.kv_cache.LayeredPagedKVCache
    rid: int,
    start_pos: int = 0,
    chunk: int = 32,
    table_width: int | None = None,
    block_k: int | None = None,
    variant: str = "amla",
    interpret: bool = False,
    layer_params: list | None = None,
    compute_dtype=None,
    head_shards: int = 1,
    need_logits: bool = True,
) -> jax.Array | None:
    """Chunked prefill-into-pages; returns last-token logits ``(1, vocab)``.

    The prompt is processed in fixed-size chunks of ``chunk`` tokens (the
    tail chunk is zero-padded, so every chunk compiles to one shape): each
    chunk is one pass of the layer walk (:func:`_paged_pass`), which writes
    its latents into ``rid``'s pages layer by layer and attends each
    layer's ``chunk * H`` query rows over the request's pages with per-row
    causal positions — which is also what makes a forked request work:
    ``start_pos > 0`` (an ``admit_with_prefix`` suffix) scores the new rows
    against the aliased prefix pages it never re-computes.

    Only the final chunk runs the last layer's post-attention half and the
    unembedding (an earlier chunk's output past its cache writes is never
    used).  ``need_logits=False`` skips even that and returns None — the
    budgeted-interleaving path uses it for intermediate slices of a prompt
    whose first-token logits come from a *later* call: cache rows are
    written identically either way, so a prompt prefilled in several
    ``start_pos``-advancing chunk-aligned calls is bit-identical to one
    monolithic call.
    """
    from repro.kernels import decode_schedule as _sched
    from repro.kernels import ops

    check_paged_compatible(cfg)
    tokens = np.asarray(tokens, np.int32).reshape(-1)
    s_total = int(tokens.shape[0])
    if s_total < 1:
        raise ValueError("prefill needs at least one token")
    layers_p = layer_params if layer_params is not None else per_layer_params(
        params, cfg
    )
    tw = table_width or cache.num_pages
    if block_k is None:
        block_k = ops.default_paged_block_k(cache.page_size, tw)
    logits = None
    for s0 in range(0, s_total, chunk):
        valid = min(chunk, s_total - s0)
        abs0 = start_pos + s0
        with span("serve.schedule"):
            plan = cache.reserve(rid, valid)
            bt, kv_len = cache.block_table([rid], width=tw)
            # One schedule per chunk, shared by all L layers (same block
            # table and kv_len everywhere); kv_len is host numpy.
            schedule = _sched.build_schedule(
                kv_len, block_k=block_k, num_splits=1
            )
        _check_pass(cfg, cache, bt, kv_len, block_k, head_shards, chunk)
        dense = np.zeros((4, 1, chunk), np.int32)
        dense[0, 0, :valid] = tokens[s0 : s0 + valid]
        dense[1, 0] = abs0 + np.arange(chunk, dtype=np.int32)
        dense[2:, 0] = plan_rows([plan], chunk, cache.num_pages)
        host = {"dense": dense, "bt": bt, "kv_len": kv_len,
                "sched": ops.schedule_arrays(schedule),
                "idx": np.int32(valid - 1)}
        dev, p_last, x, attn = _paged_pass(
            params, layers_p, cache, host, schedule, cfg=cfg,
            block_k=block_k, variant=variant, interpret=interpret,
            compute_dtype=compute_dtype, head_shards=head_shards,
        )
        if need_logits and s0 + chunk >= s_total:
            logits = _paged_head(params, p_last, x, attn, dev["idx"], cfg=cfg)
            count("walk_dispatches")
    return logits


def lm_decode_step_paged(
    params,
    tokens,  # (B, S) int32 — S new tokens per live request, rid order
    *,
    cfg,
    cache,  # runtime.kv_cache.LayeredPagedKVCache
    rids: list[int],
    scheduler=None,  # kernels.decode_schedule.DecodeScheduler (memoized)
    prefix_sharing: bool = False,
    extra_key=None,
    table_width: int | None = None,
    block_k: int | None = None,
    num_splits: int = 1,
    variant: str = "amla",
    interpret: bool = False,
    layer_params: list | None = None,
    compute_dtype=None,
    head_shards: int = 1,
    batch_rows: int | None = None,
) -> jax.Array:
    """One paged full-model decode step; returns logits ``(B', S, vocab)``:
    ``B' = B`` live rows, or the padded batch under ``batch_rows``.

    Appends are atomic (OutOfPagesError raised before any page is claimed),
    then one pass of the layer walk (:func:`_paged_pass`) appends each
    layer's latent row(s) and attends.  The decode schedule is built **once
    per step** — every layer shares the block table and kv_len, so one
    (request, kv_block) work queue serves all L attention calls (pass
    ``scheduler`` to also memoize it across steps; its hit/rebuild counters
    then count steps, not layers — the scheduler-stats acceptance check).

    ``S > 1`` is the speculative **verify** step: all S rows (the pending
    token plus S-1 draft tokens) append, then attend in one fused call with
    explicit per-row positions — the same page DMAs feed S query rows,
    which is the whole point.  Causal masking makes the batched forward
    exactly the sequential one, so row j's logits are valid whenever drafts
    1..j matched greedy; the caller accepts the longest matching prefix and
    rolls the cache back with ``LayeredPagedKVCache.truncate``.

    ``batch_rows`` fixes the batch the dense per-layer programs run on: the
    B live rows are padded to a multiple of it (pad rows are token 0 at
    position 0; only the kernel and the cache writes see the live rows).
    XLA compiles and fuses a program differently per batch size, which
    moves bf16 roundings, so a request's logits are independent of how many
    others share its step only while the padded batch stays the same: up to
    ``batch_rows`` live requests, every step and every session with the same
    ``batch_rows`` runs one program.  None runs the live batch as it is.
    The logits keep the pad rows after the B live ones, so no program
    slices them off; a caller reads the first B.
    """
    from repro.kernels import decode_schedule as _sched
    from repro.kernels import ops

    check_paged_compatible(cfg)
    if len(rids) == 0:
        raise ValueError("decode step needs at least one live request")
    tokens = np.asarray(tokens, np.int32)
    if tokens.ndim != 2 or tokens.shape[0] != len(rids):
        raise ValueError(
            f"tokens must be (B={len(rids)}, S); got {tokens.shape}"
        )
    s = int(tokens.shape[1])
    if s < 1:
        raise ValueError("decode step needs at least one token per request")
    layers_p = layer_params if layer_params is not None else per_layer_params(
        params, cfg
    )
    tw = table_width or cache.num_pages
    if block_k is None:
        block_k = ops.default_paged_block_k(cache.page_size, tw)

    with span("serve.schedule"):
        start = np.asarray([cache.seq_len(r) for r in rids], np.int32)
        positions = start[:, None] + np.arange(s, dtype=np.int32)[None, :]
        need = sum(cache.pages_needed_for_append(r, s) for r in rids)
        if need > cache.num_free_pages:
            raise OutOfPagesError(
                f"decode step needs {need} new pages for {len(rids)} appends "
                f"of {s} row(s); only {cache.num_free_pages} free — evict "
                f"and retry"
            )
        plans = [cache.reserve(r, s) for r in rids]
        bt, kv_len = cache.block_table(rids, width=tw)

        # One schedule per step, shared by all L layers (they see the same
        # block tables): the memoizing scheduler additionally reuses it
        # across steps until a request crosses a block_k boundary or the
        # live set changes (extra_key).
        if scheduler is not None:
            if prefix_sharing:
                schedule = scheduler.schedule_prefix(
                    kv_len, bt, page_size=cache.page_size, extra_key=extra_key
                )
            else:
                schedule = scheduler.schedule(kv_len, extra_key=extra_key)
        elif prefix_sharing:
            schedule = _sched.build_prefix_schedule(
                kv_len, bt, page_size=cache.page_size, block_k=block_k,
                num_splits=num_splits,
            )
        else:
            schedule = _sched.build_schedule(
                kv_len, block_k=block_k, num_splits=num_splits
            )

    _check_pass(
        cfg, cache, bt, kv_len, block_k, head_shards, s,
        positions=positions if s > 1 else None,
    )
    b = len(rids)
    b_pad = b if batch_rows is None else -(-b // batch_rows) * batch_rows
    dense = np.zeros((4, b_pad, s), np.int32)
    dense[0, :b] = tokens
    dense[1, :b] = positions
    # Rows past the b*s live ones (the pad rows) are not written.
    dense[2:] = np.reshape(
        plan_rows(plans, b_pad * s, cache.num_pages), (2, b_pad, s)
    )
    host = {"dense": dense, "bt": bt, "kv_len": kv_len,
            "sched": ops.schedule_arrays(schedule, rows=b_pad)}
    _, p_last, x, attn = _paged_pass(
        params, layers_p, cache, host, schedule, cfg=cfg, block_k=block_k,
        variant=variant, interpret=interpret, compute_dtype=compute_dtype,
        head_shards=head_shards,
    )
    logits = _paged_head(params, p_last, x, attn, None, cfg=cfg)
    count("walk_dispatches")
    return logits
