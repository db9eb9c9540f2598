"""Operations and bytes that the served tokens need, from the config's
widths: the yardstick for ``amla_roofline`` and ``step_mfu``.

Counts follow the configuration as it is run (see the config file's
``assumed``): absorbed MLA attention over the 576-wide latent rows
(``Dk = kv_lora_rank + qk_rope_head_dim``, ``Dv = kv_lora_rank``), and a
dense SwiGLU FFN.  Nothing here reads a program counter, so the yardstick
stays the same whatever implements the kernel.
"""

from __future__ import annotations

import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
BF16 = 2


def peaks(device_kind: str) -> dict:
    """Peak rates of one chip, by ``device_kind``; unknown kinds are an
    error, never a default."""
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(
            f"no peak rates for device kind {device_kind!r}; add it to peaks.json"
        )
    return table[device_kind]


def widths(conf: dict) -> dict:
    return {
        "h": conf["num_attention_heads"],
        "dk": conf["kv_lora_rank"] + conf["qk_rope_head_dim"],
        "dv": conf["kv_lora_rank"],
        "layers": conf["num_hidden_layers"],
    }


def attention_ops(h: int, dk: int, dv: int, keys: int) -> int:
    """Operations of one query token over ``keys`` latent rows, all heads:
    ``2 * H * S_k * (Dk + Dv)`` (QK^T and PV)."""
    return 2 * h * keys * (dk + dv)


def attention_bytes(h: int, dk: int, dv: int, kv_rows: int, q_tokens: int) -> int:
    """Least bytes for one request's attention in one pass: each latent row
    read once, and each query token's rows in and out."""
    return BF16 * (kv_rows * dk + q_tokens * h * (dk + dv))


def decode_attention(conf: dict, contexts) -> tuple[int, int]:
    """(ops, bytes) over all layers for one decode token per request, each
    attending ``contexts[i]`` keys (its own row included)."""
    w = widths(conf)
    ops = sum(attention_ops(w["h"], w["dk"], w["dv"], n) for n in contexts)
    nbytes = sum(attention_bytes(w["h"], w["dk"], w["dv"], n, 1) for n in contexts)
    return ops * w["layers"], nbytes * w["layers"]


def prefill_attention(conf: dict, start: int, n: int) -> tuple[int, int]:
    """(ops, bytes) over all layers for prompt tokens at positions
    ``start .. start + n - 1`` (token j attends j + 1 keys)."""
    w = widths(conf)
    keys = n * (2 * start + n + 1) // 2  # sum of (j + 1)
    ops = attention_ops(w["h"], w["dk"], w["dv"], 1) * keys
    nbytes = attention_bytes(w["h"], w["dk"], w["dv"], start + n, n)
    return ops * w["layers"], nbytes * w["layers"]


def dense_ops_per_token(conf: dict) -> int:
    """Matmul operations of one token through every layer (no attention
    scores, no unembedding)."""
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    dn, dr = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"]
    c, dvh = conf["kv_lora_rank"], conf["v_head_dim"]
    per_layer = (
        d * h * (dn + dr)  # queries
        + d * (c + dr)  # latent and RoPE key
        + h * dn * c  # W_uk
        + h * c * dvh  # W_uv
        + h * dvh * d  # output projection
        + 3 * d * conf["intermediate_size"]  # SwiGLU FFN
    )
    return 2 * per_layer * conf["num_hidden_layers"]


def unembed_ops(conf: dict) -> int:
    return 2 * conf["hidden_size"] * conf["vocab_size"]
