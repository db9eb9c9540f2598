"""Paged continuous-batching decode demo: one page pool, many requests.

    PYTHONPATH=src python examples/serve_paged_decode.py

Drives runtime.serve_loop.PagedDecodeSession through the full serving story:
admit ragged prompts into a shared page pool, decode a few steps, evict one
request mid-stream, and watch its pages get recycled into a request that
previously could not be admitted.  Latents are synthetic (this demo is about
the cache + kernel, not a model); the final check confirms the paged output
matches the contiguous kernel on the reassembled history.

A CPU demo: the Pallas kernels run in interpret mode.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.runtime.serve_loop import PagedDecodeSession

D_K, D_V, HEADS = 192, 128, 8
PAGE = 32


def main() -> None:
    rng = np.random.default_rng(0)
    lat = lambda n: rng.normal(0, 0.3, (n, D_K)).astype(np.float32)

    sess = PagedDecodeSession(
        num_pages=12, page_size=PAGE, d_k=D_K, d_v=D_V,
        scale=D_K**-0.5, variant="amla", interpret=True,
        dtype=jnp.float32,
    )

    r1 = sess.admit(lat(100))
    r2 = sess.admit(lat(150))
    print(f"admitted r{r1} (100 tok) r{r2} (150 tok); "
          f"free pages {sess.kv.num_free_pages}/12")
    r3 = sess.admit(lat(130))
    print(f"admit 130-tok request -> {r3} (pool full: queued)")

    for step in range(3):
        out = sess.step(
            {r1: lat(HEADS), r2: lat(HEADS)},
            {r1: lat(1)[0], r2: lat(1)[0]},
        )
        print(f"step {step}: outputs " +
              ", ".join(f"r{r}:{tuple(o.shape)}" for r, o in out.items()) +
              f"  kv_len r{r1}={sess.kv.seq_len(r1)} r{r2}={sess.kv.seq_len(r2)}")

    sess.evict(r1)
    print(f"evict r{r1} -> free pages {sess.kv.num_free_pages}")
    r3 = sess.admit(lat(130))
    print(f"re-admit 130-tok request -> r{r3} on recycled pages "
          f"{sess.kv.seq_pages(r3)}")

    q = {r2: lat(HEADS), r3: lat(HEADS)}
    out = sess.step(q, {r2: lat(1)[0], r3: lat(1)[0]})

    # parity check: paged serving output == contiguous kernel on the history
    c = sess.kv.gather_contiguous(r3)[None]
    want = ops.mla_decode(
        jnp.asarray(q[r3])[None, None], c, d_v=D_V, scale=D_K**-0.5,
        kv_len=jnp.asarray([c.shape[1]], jnp.int32), interpret=True,
    )[0, 0]
    err = float(jnp.max(jnp.abs(out[r3] - want)))
    print(f"paged vs contiguous max|diff| on r{r3}: {err:.2e}")
    assert err <= 2e-3
    stats = sess.scheduler_stats
    print(f"work-queue scheduler: {stats['rebuilds']} rebuilds, "
          f"{stats['hits']} reuse hits across steps")
    print("OK")


if __name__ == "__main__":
    main()
