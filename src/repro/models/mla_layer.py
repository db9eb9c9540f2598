"""Multi-head Latent Attention layer (DeepSeek-V2 geometry) — the paper's
native attention.

The KV cache stores only the shared latent ``[c (d_c=512) ; k_rope (d_r=64)]``
per token (576 numbers regardless of head count).  Queries are used in the
*absorbed* form: the per-head no-rope query (d_n=128) is premultiplied by
W_uk so scores are taken directly against the latent — exactly the
``Q' c^T`` trick of paper §2.2, giving the kernel its G x 576 x 512 shape.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core.attention import mla_attention
from repro.kernels.mla_decode import lane_pad
from repro.models import layers


def mla_init(key, cfg):
    m = cfg.mla  # MLAConfig
    d = cfg.d_model
    h = cfg.n_heads
    ks = jax.random.split(key, 7)
    s = 1.0 / math.sqrt(d)
    return {
        "wq_nope": layers.truncnorm(ks[0], (d, h, m.d_nope), s),
        "wq_rope": layers.truncnorm(ks[1], (d, h, m.d_rope), s),
        "w_uk": layers.truncnorm(ks[2], (h, m.d_nope, m.d_latent), 1.0 / math.sqrt(m.d_nope)),
        "wkv_down": layers.dense_init(ks[3], d, m.d_latent),
        "wk_rope": layers.dense_init(ks[4], d, m.d_rope),
        "w_uv": layers.truncnorm(ks[5], (h, m.d_latent, m.d_vhead), 1.0 / math.sqrt(m.d_latent)),
        "wo": layers.dense_init(ks[6], h * m.d_vhead, d, std=1.0 / math.sqrt(h * m.d_vhead)),
    }


def _mla_apply_expanded(
    params, x, *, cfg, positions, causal=True, dtype=jnp.bfloat16
):
    """Non-absorbed MLA (training/prefill): K/V expanded per head.

    Mathematically identical to the absorbed form:
        score_h = [q_nope W_uk ; q_rope] . [c ; k_rope]
                = [q_nope ; q_rope] . [c W_uk^T ; k_rope]      (per head)
        out_h   = P (c W_uv)                                    (per head)
    """
    from repro.core.attention import multi_head_attention

    m = cfg.mla
    b, s, d = x.shape
    h = cfg.n_heads
    xd = x.astype(dtype)

    c = layers.dense(params["wkv_down"], x, dtype=dtype)  # (B, S, Dc)
    k_rope = layers.dense(params["wk_rope"], x, dtype=dtype)
    k_rope = layers.rope(
        k_rope[:, :, None, :], positions, theta=cfg.rope_theta
    )  # (B, S, 1, Dr)

    q_nope = jnp.einsum("bsd,dhn->bshn", xd, params["wq_nope"].astype(dtype))
    q_rope = jnp.einsum("bsd,dhr->bshr", xd, params["wq_rope"].astype(dtype))
    q_rope = layers.rope(q_rope, positions, theta=cfg.rope_theta)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)  # (B, S, H, Dn+Dr)

    k_nope = jnp.einsum(
        "bsc,hnc->bshn", c, params["w_uk"].astype(dtype),
        preferred_element_type=jnp.float32,
    ).astype(dtype)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, s, h, m.d_rope))], axis=-1
    )
    v = jnp.einsum(
        "bsc,hcv->bshv", c, params["w_uv"].astype(dtype),
        preferred_element_type=jnp.float32,
    ).astype(dtype)  # (B, S, H, d_vhead)

    attn = multi_head_attention(
        q, k, v,
        variant=cfg.attn_variant, impl=cfg.attn_impl, causal=causal,
        scale=1.0 / math.sqrt(m.d_nope + m.d_rope),
    )
    y = layers.dense(
        params["wo"], attn.reshape(b, s, h * m.d_vhead), dtype=dtype
    )
    return y, None


def init_latent_cache(cfg, batch, max_len, dtype=jnp.bfloat16):
    """Dense latent cache ``{"c": (batch, max_len, W)}``.

    ``W`` is the 576-wide latent row padded to whole 128-lane tiles (640),
    as the paged pool pads it: the decode kernel's DMAs move whole tiles,
    and a TPU lays a 576-wide array out in 640 lanes anyway.  Writes fill
    the first 576 lanes; the pad lanes stay zero."""
    m = cfg.mla
    width = lane_pad(m.d_latent + m.d_rope)
    return {"c": jnp.zeros((batch, max_len, width), dtype)}


def mla_scale(cfg) -> float:
    """Attention scale: pre-absorption per-head width (d_nope + d_rope)."""
    return 1.0 / math.sqrt(cfg.mla.d_nope + cfg.mla.d_rope)


# --------------------------------------------------------------------------- #
# Absorbed-form decode pieces.  The decode path factors into three reusable
# stages so cache backends can interleave their own storage between them:
# ``mla_latents`` produces the 576-wide rows a latent cache stores (dense
# slabs or pages alike), ``mla_absorbed_queries`` the Q' rows scored against
# that cache (``ops.mla_decode_paged`` or ``core.attention.mla_attention``),
# and ``mla_unabsorb_output`` projects attention output back to d_model.
# ``mla_apply`` below composes exactly these for the dense path.
# --------------------------------------------------------------------------- #


def mla_latents(params, x, *, cfg, positions, dtype=jnp.bfloat16):
    """Latent cache rows ``[c ; RoPE(k_rope)]`` — (B, S, d_latent + d_rope).

    This is the only tensor a latent-KV backend stores per token (shared by
    all heads); its width is 576 at DeepSeek-V2 geometry.
    """
    c = layers.dense(params["wkv_down"], x, dtype=dtype)  # (B, S, d_latent)
    k_rope = layers.dense(params["wk_rope"], x, dtype=dtype)  # (B, S, d_rope)
    k_rope = layers.rope(
        k_rope[:, :, None, :], positions, theta=cfg.rope_theta
    )[:, :, 0]
    return jnp.concatenate([c, k_rope], axis=-1)


def mla_absorbed_queries(params, x, *, cfg, positions, dtype=jnp.bfloat16):
    """Absorbed queries ``q' = [q_nope W_uk ; RoPE(q_rope)]`` — (B, S, H,
    d_latent + d_rope), scored directly against the latent rows (§2.2)."""
    xd = x.astype(dtype)
    q_nope = jnp.einsum("bsd,dhn->bshn", xd, params["wq_nope"].astype(dtype))
    q_rope = jnp.einsum("bsd,dhr->bshr", xd, params["wq_rope"].astype(dtype))
    q_rope = layers.rope(q_rope, positions, theta=cfg.rope_theta)
    q_c = jnp.einsum(
        "bshn,hnc->bshc", q_nope, params["w_uk"].astype(dtype),
        preferred_element_type=jnp.float32,
    ).astype(dtype)
    return jnp.concatenate([q_c, q_rope], axis=-1)


def mla_unabsorb_output(params, attn, *, cfg, dtype=jnp.bfloat16):
    """Un-absorb values (per-head latent -> d_vhead) and merge heads.

    ``attn`` is (B, S, H, d_latent) attention output over latent values.
    """
    m = cfg.mla
    b, s, h = attn.shape[:3]
    o = jnp.einsum(
        "bshc,hcv->bshv", attn.astype(dtype), params["w_uv"].astype(dtype),
        preferred_element_type=jnp.float32,
    ).astype(dtype)
    return layers.dense(params["wo"], o.reshape(b, s, h * m.d_vhead), dtype=dtype)


def mla_apply(
    params,
    x: jax.Array,  # (B, S, d)
    *,
    cfg,
    positions: jax.Array,  # (B, S)
    cache=None,
    cache_len: jax.Array | None = None,
    causal: bool = True,
    dtype=jnp.bfloat16,
):
    m = cfg.mla
    b, s, d = x.shape
    h = cfg.n_heads

    # Training/prefill fast path: NON-absorbed attention.  The absorbed form
    # scores against the 576-wide latent — per-layer attention FLOPs scale
    # with H*(Dc+Dr+Dc)=1088/head; expanding K/V per head scores at
    # (d_nope+d_rope)+d_vhead = 320/head, a 3.4x reduction that dominates
    # S^2 terms (dry-run: 8423s -> see EXPERIMENTS.md §Perf cell D).  Decode
    # keeps the absorbed form — that is the paper's compute-bound regime.
    if cache is None and getattr(cfg, "mla_absorbed_train", False) is False:
        return _mla_apply_expanded(
            params, x, cfg=cfg, positions=positions, causal=causal, dtype=dtype
        )

    # Latent KV: c = x W_down ; k_rope = RoPE(x W_kr)  (shared across heads).
    c_full = mla_latents(
        params, x, cfg=cfg, positions=positions, dtype=dtype
    )  # (B, S, 576)

    # Absorbed queries: q' = [q_nope W_uk ; RoPE(q_rope)]  (B, S, H, 576).
    q_full = mla_absorbed_queries(
        params, x, cfg=cfg, positions=positions, dtype=dtype
    )

    if cache is not None:
        assert cache_len is not None
        if jnp.ndim(cache_len) == 0:  # uniform-position fast path (no scatter)
            cache = {
                "c": jax.lax.dynamic_update_slice(
                    cache["c"], c_full.astype(cache["c"].dtype),
                    (0, cache_len, 0),
                )
            }
        else:

            def upd(buf, new, idx):
                return jax.lax.dynamic_update_slice(
                    buf, new.astype(buf.dtype), (idx, 0)
                )

            cache = {"c": jax.vmap(upd)(cache["c"], c_full, cache_len)}
        c_all = cache["c"]
        kv_len = jnp.broadcast_to(jnp.asarray(cache_len + s), (b,))
        q_offset = jnp.broadcast_to(jnp.asarray(cache_len), (b,))
    else:
        c_all = c_full
        kv_len = jnp.full((b,), s, jnp.int32)
        q_offset = jnp.zeros((b,), jnp.int32)

    # Scale uses the pre-absorption per-head width (d_nope + d_rope).
    scale = mla_scale(cfg)
    attn = mla_attention(
        q_full,
        c_all,
        d_v=m.d_latent,
        variant=cfg.attn_variant,
        impl=cfg.attn_impl,
        causal=causal,
        scale=scale,
        kv_len=kv_len,
        q_offset=q_offset,
    )  # (B, S, H, d_latent)

    # Un-absorb values: per-head projection latent -> d_vhead, then merge.
    y = mla_unabsorb_output(params, attn, cfg=cfg, dtype=dtype)
    return y, cache
