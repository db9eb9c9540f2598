"""Public attention API: one entry point for every model in the zoo.

Shapes follow the (batch, seq, heads, head_dim) convention:
    q: (B, Sq, Hq, Dh)      k/v: (B, Sk, Hkv, Dh[v])    with Hq % Hkv == 0.

``variant`` selects the paper's algorithm: "base" (Algorithm 1, FP32-multiply
rescale) or "amla" (Algorithm 2, MUL-by-ADD rescale).  ``impl`` selects the
execution path:

    "xla"               blockwise scan in pure jnp (CPU-executable, dry-run
                        path; identical math to the kernels)
    "naive"             full-softmax einsum (test oracle only)
    "pallas"            Mosaic TPU kernels from repro.kernels
    "pallas_interpret"  same kernels, interpreter mode (CPU-validatable)

MLA (the paper's native geometry) enters through :func:`mla_attention`, where
K and V are two views of a single latent cache (Dk = 576 = 512 latent + 64
rope, Dv = 512) — this is what makes MLA decode compute-bound.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.amla import flash_attention_amla
from repro.core.flash import flash_attention_base

XLA_BLOCK = 512  # paper's KV block size; kernels choose their own tiling


def _flash_fn(variant: str):
    if variant == "base":
        return flash_attention_base
    if variant == "amla":
        return flash_attention_amla
    raise ValueError(f"unknown attention variant: {variant}")


def _naive_attention(q, k, v, *, scale, causal, window, softcap, kv_len, q_offset):
    """Full-softmax oracle (FP32). Same signature contract as the flash path."""
    b, sq, hq, dh = q.shape
    _, sk, hkv, _ = k.shape
    group = hq // hkv
    qh = q.reshape(b, sq, hkv, group, dh).astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qh, k.astype(jnp.float32)) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    kpos = jnp.arange(sk)[None, None, None, None, :]
    qpos = (q_offset[:, None] + jnp.arange(sq)[None, :]) if q_offset is not None else (
        jnp.zeros((b, 1), jnp.int32) + jnp.arange(sq)[None, :]
    )
    qpos = qpos[:, None, None, :, None]
    mask = jnp.ones(s.shape, bool)
    if kv_len is not None:
        mask &= kpos < kv_len[:, None, None, None, None]
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(b, sq, hq, v.shape[-1])


def multi_head_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    variant: str = "amla",
    impl: str = "xla",
    causal: bool = False,
    window: int | None = None,
    softcap: float | None = None,
    scale: float | None = None,
    kv_len: jax.Array | None = None,  # (B,) valid key count per example
    q_offset: jax.Array | None = None,  # (B,) absolute position of q[:, 0]
    block_size: int = XLA_BLOCK,
) -> jax.Array:
    """GQA/MQA/MHA attention.  Returns (B, Sq, Hq, Dv) in q.dtype."""
    b, sq, hq, dh = q.shape
    _, sk, hkv, dv = v.shape
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    if scale is None:
        scale = 1.0 / (dh**0.5)
    if q_offset is None:
        # Decode convention: queries are the last `sq` positions of the kv.
        base = (kv_len - sq) if kv_len is not None else jnp.full((b,), sk - sq)
        q_offset = jnp.maximum(base, 0).astype(jnp.int32)

    if impl == "naive":
        out = _naive_attention(
            q, k, v, scale=scale, causal=causal, window=window,
            softcap=softcap, kv_len=kv_len, q_offset=q_offset,
        )
        return out.astype(q.dtype)

    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels import ops  # lazy: Mosaic not importable everywhere

        return ops.gqa_attention(
            q, k, v, variant=variant, interpret=(impl == "pallas_interpret"),
            causal=causal, window=window, softcap=softcap, scale=scale,
            kv_len=kv_len, q_offset=q_offset,
        )

    if impl != "xla":
        raise ValueError(f"unknown attention impl: {impl}")

    fn = _flash_fn(variant)

    # Fold the query positions of a kv-head group into flash "rows":
    # (B, Sq, Hkv, group, Dh) -> rows (Sq * group) per (B, Hkv) program.
    qr = q.reshape(b, sq, hkv, group, dh).transpose(0, 2, 1, 3, 4)
    qr = qr.reshape(b, hkv, sq * group, dh)
    kr = k.transpose(0, 2, 1, 3)  # (B, Hkv, Sk, Dh)
    vr = v.transpose(0, 2, 1, 3)

    # Long-prefill memory bound: process query rows in chunks via lax.map so
    # the (rows x Dv) accumulator and (rows x block) score tiles stay small
    # regardless of Sq (sequential chunks, like the kernels' q-block grid).
    rows = sq * group
    q_row_chunk = 4096
    n_chunks = max(rows // q_row_chunk, 1) if rows % q_row_chunk == 0 else 1

    def per_head(qh, kh, vh, klen, qoff):
        q_pos = jnp.repeat(qoff + jnp.arange(sq, dtype=jnp.int32), group)

        def run(q_rows, pos_rows):
            return fn(
                q_rows, kh, vh, scale=scale, block_size=min(block_size, sk),
                q_pos=pos_rows, kv_len=klen, causal=causal, window=window,
                softcap=softcap,
            )

        if n_chunks == 1:
            return run(qh, q_pos)
        qc = qh.reshape(n_chunks, rows // n_chunks, dh)
        pc = q_pos.reshape(n_chunks, rows // n_chunks)
        out = jax.lax.map(lambda t: run(t[0], t[1]), (qc, pc))
        return out.reshape(rows, -1)

    kv_len_b = kv_len if kv_len is not None else jnp.full((b,), sk, jnp.int32)
    out = jax.vmap(  # over batch
        jax.vmap(per_head, in_axes=(0, 0, 0, None, None)),  # over kv heads
        in_axes=(0, 0, 0, 0, 0),
    )(qr, kr, vr, kv_len_b, q_offset)
    # (B, Hkv, Sq*group, Dv) -> (B, Sq, Hq, Dv)
    out = out.reshape(b, hkv, sq, group, dv).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, sq, hq, dv).astype(q.dtype)


def mla_attention(
    q: jax.Array,  # (B, Sq, Hq, Dk)  absorbed queries (Dk = Dc + Dr = 576)
    c_kv: jax.Array,  # (B, Sk, >=Dk) shared latent cache (rope part included)
    *,
    d_v: int = 512,  # latent value width (Dv = Dc)
    variant: str = "amla",
    impl: str = "xla",
    causal: bool = False,
    scale: float | None = None,
    kv_len: jax.Array | None = None,
    q_offset: jax.Array | None = None,
    block_size: int = XLA_BLOCK,
) -> jax.Array:
    """Multi-head Latent Attention (paper §2.2): K and V are views of one
    latent cache shared by all heads => MQA-like memory, MLA compute.

    Returns (B, Sq, Hq, d_v).
    """
    b, sq, hq, dk = q.shape
    # Cache rows may be lane-padded past Dk (init_latent_cache).
    k = c_kv[:, :, None, :dk]  # Hkv = 1
    v = c_kv[:, :, None, :d_v]
    if scale is None:
        scale = 1.0 / (dk**0.5)
    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels import ops

        return ops.mla_decode(
            q, c_kv, d_v=d_v, variant=variant,
            interpret=(impl == "pallas_interpret"), scale=scale,
            kv_len=kv_len, causal=causal, q_offset=q_offset,
        )
    return multi_head_attention(
        q, k, v, variant=variant, impl=impl, causal=causal, scale=scale,
        kv_len=kv_len, q_offset=q_offset, block_size=block_size,
    )
