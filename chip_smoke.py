"""Bring-up check: paged AMLA serving of deepseek-v2-mla on one TPU chip.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chips  # 4x1 mesh vs one chip, same stream

The one-chip run serves 8 requests (prompts of 256-2048 tokens, 32
generated tokens each, batch 8) through the launcher's own
``PagedServingSession`` path (``repro.launch.serve._build_session`` /
``_serve_stream``) at the model's full width (d_model 5120, 128 heads, a
576-wide latent, vocab 102400) with the depth cut to 4 layers so the random
bf16 weights fit in 16 GB.  It fails unless every request completes, the
teardown sweep is clean, the kernels are compiled (not interpreted), and
one layer's queue-kernel output on the live pool at the last step agrees
with ``kernels.ref.mla_decode_ref`` in float32 within the bf16 bound below.

``--four-chips`` runs only the same stream on a ``4x1`` mesh
(``ShardedPagedServingSession``) and on one chip, and fails unless the
greedy tokens are identical, each shard's page pool and params replica sit
on their own chip, and the chip ``model.init`` ran on holds no extra copy.

The last line of standard output is a JSON object naming the device.  With
no TPU the script exits nonzero before printing it.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.models.mla_layer import mla_scale  # noqa: E402
from repro.models.model_zoo import build_model  # noqa: E402
from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402

LAYERS = 4
REQUESTS = BATCH = 8
GEN = 32
PROMPT_LENS = (256, 2048)
PAGE_SIZE = 32  # the launcher's default page
PAGES_PER_POOL = 640  # 8 requests x ceil((2048 + 32) / 32) = 520, plus slack
SEED = 0
# The kernel rounds the softmax weights to bf16 for the PV matmul (relative
# error <= 2^-9 each), so its output may differ from the float32 reference
# by at most 2^-9 * max|V|; the check allows 4x that.
BOUND_REL_TO_MAX_V = 2.0**-7
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Seconds jax spent in backend compiles (a persistent-cache hit
    counts only its retrieval), read through jax.monitoring."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name == BACKEND_COMPILE:
            self.seconds += secs
            self.count += 1


def init_params(model, seed: int):
    """Random weights from ``seed``, stored in the model dtype (bf16)."""

    def init(key):
        return jax.tree.map(
            lambda a: a.astype(model.dtype)
            if jnp.issubdtype(a.dtype, jnp.floating)
            else a,
            model.init(key),
        )

    return jax.block_until_ready(jax.jit(init)(jax.random.PRNGKey(seed)))


def make_prompts(cfg, seed: int) -> list[list[int]]:
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, size=REQUESTS)
    lens[0], lens[-1] = PROMPT_LENS  # both ends of the range
    return [rng.integers(2, cfg.vocab_size, size=int(n)).tolist() for n in lens]


def launcher_args(mesh: str | None = None, extra=()) -> argparse.Namespace:
    """The launcher's own options for this stream (its parser, its
    defaults), so the session is built exactly as ``serve`` builds it."""
    argv = [
        "--cache", "paged",
        "--requests", str(REQUESTS),
        "--gen-len", str(GEN),
        "--batch", str(BATCH),
        "--page-size", str(PAGE_SIZE),
        "--num-pages", str(PAGES_PER_POOL * (4 if mesh else 1)),
    ]
    if mesh:
        argv += ["--mesh", mesh]
    return serve.build_parser().parse_args(argv + list(extra))


def check_live_pool(sess, cfg, seed: int) -> float:
    """Queue kernel vs float32 reference on one layer of the live pool.

    Returns the error over the bound; raises if it is above 1."""
    cache = sess.cache
    layer = cfg.n_layers - 1
    rids = list(sess.active)
    bt, kv_len = cache.block_table(rids, width=sess.table_width)
    h, dk, dv = cfg.n_heads, cfg.mla.d_latent + cfg.mla.d_rope, cfg.mla.d_latent
    q = jax.random.normal(jax.random.PRNGKey(seed), (len(rids), 1, h, dk))
    q = q.astype(jnp.bfloat16)
    got = ops.mla_decode_paged(
        q,
        cache.pages,
        jnp.asarray(bt),
        jnp.asarray(kv_len),
        kv_scales=cache.scales,
        layer=layer,
        d_v=dv,
        scale=mla_scale(cfg),
        block_k=sess.block_k,
    )
    s_max = int(kv_len.max())
    rows = jnp.stack([
        jnp.pad(
            cache.gather_contiguous(r, layer=layer).astype(jnp.float32),
            ((0, s_max - int(n)), (0, 0)),
        )
        for r, n in zip(rids, kv_len)
    ])
    q_pos = jnp.repeat(jnp.asarray(kv_len - 1)[:, None], h, axis=1)
    want = ref.mla_decode_ref(
        q.reshape(len(rids), h, dk).astype(jnp.float32),
        rows,
        jnp.asarray(kv_len),
        q_pos,
        d_v=dv,
        scale=mla_scale(cfg),
    )
    err = float(jnp.max(jnp.abs(got.reshape(want.shape) - want)))
    bound = BOUND_REL_TO_MAX_V * float(jnp.max(jnp.abs(rows[..., :dv])))
    print(
        f"reference check (layer {layer}, {len(rids)} live requests, "
        f"kv_len {kv_len.min()}..{kv_len.max()}): max|kernel - ref| = {err:.6g}"
        f" <= bound {bound:.6g} (2^-7 * max|V|)"
    )
    if not err <= bound:
        raise SystemExit(f"queue kernel disagrees with the reference: {err} > {bound}")
    return err / bound


def serve_stream(sess, prompts, *, on_last_step=None) -> dict[int, list[int]]:
    """The launcher's admit/step/finish loop; ``on_last_step(sess)`` runs
    once, after the step that completes every request, while the pool is
    still live."""
    if on_last_step is not None:
        step = sess.step

        def step_then_check():
            step()
            if sess.active and all(
                len(sess.outputs[r]) >= GEN + 1 for r in sess.active
            ):
                on_last_step(sess)

        sess.step = step_then_check
    results, tokens_out, dt = serve._serve_stream(sess, list(prompts), GEN, REQUESTS)
    print(f"served {len(results)} requests, {tokens_out} decode tokens in {dt:.1f}s")
    if len(results) != REQUESTS or any(
        len(out) != GEN + 1 for out in results.values()
    ):
        raise SystemExit(
            f"incomplete stream: {[len(o) for o in results.values()]} tokens "
            f"for {len(results)}/{REQUESTS} requests"
        )
    return results


def one_chip(cfg, model, params, prompts) -> None:
    args = launcher_args()
    sess = serve._build_session(args, cfg, model, params)
    if sess.interpret:
        raise SystemExit("session runs the kernels in interpret mode")
    checked = []
    serve_stream(
        sess, prompts, on_last_step=lambda s: checked.append(check_live_pool(s, cfg, SEED))
    )
    if len(checked) != 1:
        raise SystemExit(f"reference check ran {len(checked)} times, not once")
    sweep = sess.close()
    if sweep["live_sequences"] or sweep["live_pages"]:
        raise SystemExit(f"teardown sweep not clean: {sweep}")
    work = sess.work_stats()
    print(
        f"layers {cfg.n_layers}; decode steps {work['decode_steps']}; prefill "
        f"chunks {work['prefill_chunks']}; teardown sweep clean "
        f"({sweep['free_pages']} pages free)"
    )


def _devices_of(tree) -> set:
    return {d for leaf in jax.tree.leaves(tree) for d in leaf.devices()}


def four_chips(cfg, model, params, prompts, clock) -> None:
    devices = jax.devices()
    if len(devices) != 4:
        raise SystemExit(f"--four-chips needs 4 chips; jax sees {len(devices)}")
    init_dev = _devices_of(params)
    if len(init_dev) != 1:
        raise SystemExit(f"params span {init_dev} after init")
    (init_dev,) = init_dev
    param_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(params))

    single = serve._build_session(launcher_args(), cfg, model, params)
    ref_tokens = serve_stream(single, prompts)
    single.close()
    del single
    gc.collect()
    print(f"one chip: {clock.seconds:.1f}s compiling so far")

    sess = serve._build_session(launcher_args(mesh="4x1"), cfg, model, params)
    if any(s.interpret for s in sess.shards):
        raise SystemExit("sharded session runs the kernels in interpret mode")
    homes = []
    for i, shard in enumerate(sess.shards):
        pool = _devices_of([shard.cache.pages])
        replica = _devices_of([shard.params, shard._layers])
        if pool != replica or len(pool) != 1:
            raise SystemExit(f"shard {i}: pool on {pool}, params on {replica}")
        homes.append(next(iter(pool)))
    if len(set(homes)) != 4:
        raise SystemExit(f"shards share chips: {homes}")
    gc.collect()
    used, seen = dict.fromkeys(devices, 0), set()
    for arr in jax.live_arrays():  # arrays may share a buffer: count it once
        for shard in arr.addressable_shards:
            key = (shard.device, shard.data.unsafe_buffer_pointer())
            if key not in seen:
                seen.add(key)
                used[shard.device] += shard.data.nbytes
    others = max(b for d, b in used.items() if d != init_dev)
    extra = used[init_dev] - others
    print(
        f"4x1 mesh: shard pools and params on {[str(d) for d in homes]}; "
        f"init chip {init_dev} holds {used[init_dev] / 2**30:.2f} GiB vs "
        f"<= {others / 2**30:.2f} GiB elsewhere (params {param_bytes / 2**30:.2f} GiB)"
    )
    if extra > param_bytes // 2:
        raise SystemExit(
            f"init chip keeps an extra params copy: {extra} bytes over the "
            "busiest other chip"
        )
    mesh_tokens = serve_stream(sess, prompts)
    sess.close()
    ref_by_prompt = [ref_tokens[r] for r in sorted(ref_tokens)]
    mesh_by_prompt = [mesh_tokens[r] for r in sorted(mesh_tokens)]
    same = sum(a == b for a, b in zip(ref_by_prompt, mesh_by_prompt))
    print(f"greedy tokens identical for {same}/{REQUESTS} requests (4x1 mesh vs one chip)")
    for i, (a, b) in enumerate(zip(ref_by_prompt, mesh_by_prompt)):
        if a != b:
            k = next(j for j, (x, y) in enumerate(zip(a, b)) if x != y)
            print(f"request {i} first differs at token {k}: {a[k]} vs {b[k]}")
    if same != REQUESTS:
        raise SystemExit("4x1 mesh and one chip disagree on greedy tokens")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--four-chips",
        action="store_true",
        help="only the 4x1-mesh vs one-chip parity phase (needs 4 chips)",
    )
    args = ap.parse_args()
    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; jax found {dev.platform}")
    clock = CompileClock()
    t0 = time.perf_counter()
    cfg = dataclasses.replace(get_config("deepseek-v2-mla"), n_layers=LAYERS)
    model = build_model(cfg)
    params = init_params(model, SEED)
    prompts = make_prompts(cfg, SEED)
    print(
        f"deepseek-v2-mla cut to {cfg.n_layers} layers: d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads, vocab {cfg.vocab_size}; prompts "
        f"{[len(p) for p in prompts]}; compile cache {cache_dir}"
    )
    if args.four_chips:
        four_chips(cfg, model, params, prompts, clock)
    else:
        one_chip(cfg, model, params, prompts)
    print(
        f"compile seconds {clock.seconds:.1f} over {clock.count} compiles; "
        f"wall {time.perf_counter() - t0:.1f}s"
    )
    devices = jax.devices()
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }))


if __name__ == "__main__":
    main()
