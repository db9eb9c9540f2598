"""Pallas TPU kernel: GQA/MQA/MHA decode attention with AMLA rescaling.

The paper's MUL-by-ADD rescaling is not MLA-specific — it applies to any
FlashAttention-style online softmax.  This kernel serves the decode step of
the assigned GQA-family architectures.  Layout is cache-native:

    q: (B, Hkv, G, Dh)   with G = S_q * group   (group = Hq // Hkv)
    k: (B, Hkv, S, Dh)
    v: (B, Hkv, S, Dh)

so the serving KV cache ((L, B, Hkv, S, Dh)) feeds the kernel with zero
transposition.  Each (b, h) program keeps a (G, Dh) FP32 accumulator in VMEM
scratch across KV blocks; ``variant="amla"`` replaces the per-block FP32
rescale multiply with the skippable INT32 exponent add.

Sliding-window layers (gemma2 local, recurrentgemma local) additionally skip
whole KV blocks outside the window — the dominant saving for long contexts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.compat import CompilerParams

from repro.core import numerics

DEFAULT_BLOCK_K = 512


def _gqa_decode_kernel(
    kv_len_ref,  # (B,) int32
    q_pos_min_ref,  # (B,) int32 earliest query position per request
    q_ref,  # (G, Dh)
    q_pos_ref,  # (G, 1) int32 absolute position per query row (VMEM)
    k_ref,  # (Bk, Dh)
    v_ref,  # (Bk, Dh)
    o_ref,  # (G, Dh)
    acc_ref,
    m_ref,
    l_ref,
    n_ref,
    gamma_ref,
    s16_ref,
    *,
    scale: float,
    variant: str,
    block_k: int,
    softcap: float | None,
    window: int | None,
):
    b = pl.program_id(0)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, numerics.M_INIT)
        l_ref[...] = jnp.zeros_like(l_ref)
        n0, inv_r0 = numerics.round_scale_to_pow2(
            jnp.full_like(m_ref, numerics.M_INIT)
        )
        n_ref[...] = n0
        gamma_ref[...] = jnp.ones_like(gamma_ref)
        s16_ref[...] = numerics.bf16_round(inv_r0)

    k_len = kv_len_ref[b]
    start = i * block_k
    needed = start < k_len
    if window is not None:
        # Whole-block skip outside the sliding window (min query position
        # bounds the earliest key any row can see).
        needed &= (start + block_k) > (q_pos_min_ref[b] - window + 1)

    @pl.when(needed)
    def _compute():
        s = jax.lax.dot_general(
            q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        s = s * jnp.float32(scale)
        if softcap is not None:
            s = numerics.softcap(s, softcap)
        s = jnp.clip(s, -numerics.M_CLAMP, numerics.M_CLAMP)

        k_pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        q_pos = q_pos_ref[...]
        mask = (k_pos < k_len) & (k_pos <= q_pos)
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, -jnp.inf)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * jnp.exp(m_prev - m_new) + jnp.sum(
            p, axis=1, keepdims=True
        )
        m_ref[...] = m_new

        if variant == "amla":
            n_new, inv_r32 = numerics.round_scale_to_pow2(m_new)
            s16 = numerics.bf16_round(inv_r32)
            gamma_new = inv_r32 / s16
            eps = gamma_ref[...] / gamma_new - 1.0
            inc = numerics.pow2_int_increment(n_new - n_ref[...], eps)
            n_ref[...] = n_new
            gamma_ref[...] = gamma_new
            s16_ref[...] = s16
            p_mm = (p * s16).astype(q_ref.dtype)

            @pl.when(jnp.any(inc != 0))
            def _rescale():
                acc_ref[...] = numerics.apply_int_increment(acc_ref[...], inc)

        else:
            alpha = jnp.exp(m_prev - m_new)
            acc_ref[...] = acc_ref[...] * alpha
            p_mm = p.astype(q_ref.dtype)

        t = jax.lax.dot_general(
            p_mm, v_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] + t

    @pl.when(i == pl.num_programs(2) - 1)
    def _finalize():
        l = l_ref[...]
        denom = l * s16_ref[...] if variant == "amla" else l
        safe = jnp.where(denom > 0, denom, 1.0)
        out = jnp.where(denom > 0, acc_ref[...] / safe, 0.0)
        o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "variant", "scale", "block_k", "softcap", "window", "interpret",
    ),
)
def gqa_decode_rows(
    q: jax.Array,  # (B, Hkv, G, Dh)
    k: jax.Array,  # (B, Hkv, S, Dh)
    v: jax.Array,  # (B, Hkv, S, Dh)
    kv_len: jax.Array,  # (B,)
    q_pos: jax.Array,  # (B, G)
    *,
    variant: str = "amla",
    scale: float,
    block_k: int = DEFAULT_BLOCK_K,
    softcap: float | None = None,
    window: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    b, hkv, g, dh = q.shape
    s = k.shape[2]
    block_k = min(block_k, max(s, 128))
    pad = (-s) % block_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    n_blocks = k.shape[2] // block_k

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, n_blocks),
        in_specs=[
            pl.BlockSpec((None, None, g, dh), lambda bb, hh, ii, *_: (bb, hh, 0, 0)),
            pl.BlockSpec((None, g, 1), lambda bb, hh, ii, *_: (bb, 0, 0)),
            pl.BlockSpec(
                (None, None, block_k, dh), lambda bb, hh, ii, *_: (bb, hh, ii, 0)
            ),
            pl.BlockSpec(
                (None, None, block_k, dh), lambda bb, hh, ii, *_: (bb, hh, ii, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (None, None, g, dh), lambda bb, hh, ii, *_: (bb, hh, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((g, v.shape[-1]), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.int32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _gqa_decode_kernel,
        scale=scale,
        variant=variant,
        block_k=block_k,
        softcap=softcap,
        window=window,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, v.shape[-1]), jnp.float32),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(
        kv_len.astype(jnp.int32),
        jnp.min(q_pos, axis=1).astype(jnp.int32),
        q,
        q_pos.astype(jnp.int32)[..., None],
        k,
        v,
    )
