"""DeepSeek-V2 family: the configuration as the program runs it, weights
made from the seed, and the plain float32 reference.

The reference follows the published attention (arXiv:2405.04434, section
2.1) in its plain, non-absorbed form: per-head keys ``c W_uk`` and values
``c W_uv`` are expanded from the latent ``c``, with a shared RoPE key.  It
departs from the published model where the configuration file's
``assumed`` list says the program does.  It imports nothing of the program:
it reads the weights that :func:`make_params` made, by their names.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
# Standard deviation of the unembedding, in units of 1/sqrt(hidden): logits
# then spread by about this much, as a trained model's do.
LOGIT_STD = 3.0
# Query rows per block of the reference's attention.
ROW_BLOCK = 128
# Reference sequence lengths are padded to a multiple of this, so a few
# programs serve every sample.
LENGTH_BUCKET = 1024


def program_config(conf: dict):
    """The program's ModelConfig for a DeepSeek-V2-family config file."""
    from repro.configs.base import MLAConfig, ModelConfig

    return ModelConfig(
        name=conf["name"],
        family="mla",
        n_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=1,
        head_dim=conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"],
        d_ff=conf["intermediate_size"],
        vocab_size=conf["vocab_size"],
        mla=MLAConfig(
            d_latent=conf["kv_lora_rank"],
            d_rope=conf["qk_rope_head_dim"],
            d_nope=conf["qk_nope_head_dim"],
            d_vhead=conf["v_head_dim"],
        ),
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        act=conf["hidden_act"],
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        dtype=conf["dtype"],
    )


def _std(path: tuple[str, ...], shape, conf) -> float:
    """Init scale of one weight: 1/sqrt(fan-in), by its name."""
    name = path[-1]
    if name == "scale":
        return 0.1
    if path[0] == "embed":
        return 1.0
    if path[0] == "unembed":
        return LOGIT_STD / math.sqrt(conf["hidden_size"])
    if name in ("wq_nope", "wq_rope"):
        return conf["hidden_size"] ** -0.5
    if name == "w_uk":
        return conf["qk_nope_head_dim"] ** -0.5
    if name == "w_uv":
        return conf["kv_lora_rank"] ** -0.5
    if name == "w":  # dense (.., d_in, d_out)
        return shape[-2] ** -0.5
    raise KeyError(f"no init rule for weight {'/'.join(path)}")


def _path_names(path) -> tuple[str, ...]:
    out = []
    for k in path:
        out.append(str(getattr(k, "key", getattr(k, "idx", k))))
    return tuple(out)


def make_params(model, conf: dict, seed: int):
    """Random weights from ``seed`` in the program's layout, made on the
    device in one jitted call, in the serving dtype.

    Only the shapes come from the program (``jax.eval_shape`` of its init);
    every value is drawn here.  Norm scales are small random offsets from
    1, so a reference that dropped a norm weight would disagree."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    dtype = jnp.bfloat16 if conf["dtype"] == "bfloat16" else jnp.float32
    specs = [(_path_names(p), s.shape) for p, s in leaves]

    def init(key):
        out = []
        for i, (names, shape) in enumerate(specs):
            k = jax.random.fold_in(key, i)
            std = _std(names, shape, conf)
            out.append((std * jax.random.normal(k, shape, jnp.float32)).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(jax.random.fold_in(key, seed >> 31), seed & 0x7FFFFFFF)
    return jax.block_until_ready(jax.jit(init)(key))


# --------------------------------------------------------------------------- #
# reference
# --------------------------------------------------------------------------- #


def _q8(x, axes):
    """Symmetric int8 rounding along ``axes`` (the control's precision)."""
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _ein(eq, a, b, qa, qb, int8):
    if int8:
        a, b = _q8(a, qa), _q8(b, qb)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + scale.astype(jnp.float32))


def _rope(x, pos, theta):
    """NeoX RoPE on the last axis; x (S, ..., D), pos (S,)."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) / (d // 2))
    ang = pos.astype(jnp.float32)[:, None] * freqs
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _layer(p, x, n_valid, conf, int8):
    """One decoder layer over x (S, d) float32; keys past n_valid masked."""
    f32 = lambda a: a.astype(jnp.float32)
    s = x.shape[0]
    dn, dr = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"]
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    pos = jnp.arange(s)
    a = p["attn"]
    h = _rms(x, p["ln1"]["scale"], eps)
    c = _ein("sd,dc->sc", h, f32(a["wkv_down"]["w"]), -1, 0, int8)
    k_r = _rope(_ein("sd,dr->sr", h, f32(a["wk_rope"]["w"]), -1, 0, int8), pos, theta)
    q_n = _ein("sd,dhn->shn", h, f32(a["wq_nope"]), -1, 0, int8)
    q_r = _rope(_ein("sd,dhr->shr", h, f32(a["wq_rope"]), -1, 0, int8), pos, theta)
    k_n = _ein("sc,hnc->shn", c, f32(a["w_uk"]), -1, 2, int8)
    v = _ein("sc,hcv->shv", c, f32(a["w_uv"]), -1, 1, int8)
    scale = 1.0 / math.sqrt(dn + dr)
    blocks = s // ROW_BLOCK

    def attend(i):
        rows = i * ROW_BLOCK + jnp.arange(ROW_BLOCK)
        qn = jax.lax.dynamic_slice_in_dim(q_n, i * ROW_BLOCK, ROW_BLOCK, 0)
        qr = jax.lax.dynamic_slice_in_dim(q_r, i * ROW_BLOCK, ROW_BLOCK, 0)
        sc = _ein("qhn,khn->hqk", qn, k_n, -1, -1, int8)
        sc = (sc + _ein("qhr,kr->hqk", qr, k_r, -1, -1, int8)) * scale
        mask = (pos[None, :] <= rows[:, None]) & (pos[None, :] < n_valid)
        sc = jnp.where(mask[None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        return _ein("hqk,khv->qhv", pr, v, -1, 0, int8)

    o = jax.lax.map(attend, jnp.arange(blocks)).reshape(s, -1)
    x = x + _ein("sv,vd->sd", o, f32(a["wo"]["w"]), -1, 0, int8)
    h2 = _rms(x, p["ln2"]["scale"], eps)
    m = p["mlp"]
    g = _ein("sd,df->sf", h2, f32(m["gate"]["w"]), -1, 0, int8)
    u = _ein("sd,df->sf", h2, f32(m["up"]["w"]), -1, 0, int8)
    return x + _ein("sf,fd->sd", jax.nn.silu(g) * u, f32(m["down"]["w"]), -1, 0, int8)


def _layers_of(params):
    """(stacked per-layer weights, number of layers) in the program's layout."""
    stacked = params["groups"]["pos0"]
    n = jax.tree.leaves(stacked)[0].shape[0]
    if params.get("rem"):
        raise ValueError("reference expects every layer in the scanned group")
    return stacked, n


def _forward(params, tokens, n_valid, idx, conf, int8):
    """Logits (len(idx), vocab) at positions ``idx`` of a padded sequence."""
    stacked, n_layers = _layers_of(params)
    x = params["embed"]["table"][tokens].astype(jnp.float32)

    def body(x, l):
        p = jax.tree.map(lambda a: a[l], stacked)
        return _layer(p, x, n_valid, conf, int8), None

    x, _ = jax.lax.scan(body, x, jnp.arange(n_layers))
    x = _rms(x[idx], params["final_norm"]["scale"], conf["rms_norm_eps"])
    table = params["unembed"]["table"].astype(jnp.float32)
    return _ein("nd,vd->nv", x, table, -1, -1, int8)


_FORWARD = jax.jit(_forward, static_argnames=("conf", "int8"))


class _Frozen(dict):
    """A hashable view of the config for jit's static arguments."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def reference_logits(params, conf: dict, tokens, positions, *, int8=False,
                     pad_to: int = 0):
    """Reference logits at ``positions`` of the token sequence ``tokens``
    (each logit row predicts the token after its position), float32 at the
    highest matmul precision; ``int8=True`` is the control, with every
    matmul's operands rounded to int8 first.  Sequences are padded to
    ``pad_to`` (at least), so one compiled program serves a whole sample."""
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    padded = -(-max(n, pad_to) // LENGTH_BUCKET) * LENGTH_BUCKET
    tok = np.zeros((padded,), np.int32)
    tok[:n] = tokens
    idx = np.asarray(positions, np.int32)
    width = -(-len(idx) // 64) * 64
    idx_p = np.zeros((width,), np.int32)
    idx_p[: len(idx)] = idx
    keys = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "rms_norm_eps", "rope_theta")
    frozen = _Frozen({k: conf[k] for k in keys})
    out = _FORWARD(
        params, jnp.asarray(tok), jnp.int32(n), jnp.asarray(idx_p),
        conf=frozen, int8=bool(int8),
    )
    return np.asarray(out)[: len(idx)]
