"""Shared-prefix serving demo: fork a system prompt, decode as a group.

    PYTHONPATH=src python examples/serve_shared_prefix.py

The n-best / system-prompt story end to end: one parent request carries a
long prompt prefix; `admit_with_prefix` forks it into siblings that alias
the parent's pages (refcounts, zero copies), each sibling appends its own
divergent suffix (the boundary page quietly copy-on-writes), and every
decode step attends the family's shared blocks ONCE via the group-batched
prefix kernel — page DMAs for the prefix drop ~G x at group size G.  The
final check confirms the shared-path outputs match the contiguous kernel
on each request's reassembled history.

A CPU demo: the Pallas kernels run in interpret mode.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.kernels.decode_schedule import (
    build_prefix_schedule,
    build_schedule,
    prefix_queue_grid_items,
    queue_grid_items,
)
from repro.runtime.serve_loop import PagedDecodeSession

D_K, D_V, HEADS = 192, 128, 8
PAGE, BLOCK_K = 32, 128
PREFIX_LEN, GROUP = 300, 4


def main() -> None:
    rng = np.random.default_rng(0)
    lat = lambda n: rng.normal(0, 0.3, (n, D_K)).astype(np.float32)

    sess = PagedDecodeSession(
        num_pages=48, page_size=PAGE, d_k=D_K, d_v=D_V,
        scale=D_K**-0.5, variant="amla", interpret=True,
        dtype=jnp.float32, block_k=BLOCK_K, prefix_sharing=True,
    )

    parent = sess.admit(lat(PREFIX_LEN))
    pages_after_parent = sess.kv.num_free_pages
    kids = [
        sess.admit_with_prefix(parent, lat(n)) for n in (12, 40, 5)
    ]
    family = [parent] + kids
    print(f"parent r{parent}: {PREFIX_LEN} prompt tokens "
          f"({-(-PREFIX_LEN // PAGE)} pages)")
    print(f"forked {len(kids)} siblings -> pages consumed by forks: "
          f"{pages_after_parent - sess.kv.num_free_pages} "
          f"(suffix + boundary COW only), aliased pages: "
          f"{sess.kv.num_aliased_pages()}")

    for step in range(3):
        q = {r: lat(HEADS) for r in family}
        out = sess.step(q, {r: lat(1)[0] for r in family})
        kv_l = {r: sess.kv.seq_len(r) for r in family}
        print(f"step {step}: decoded {len(out)} requests, kv_len {kv_l}")

    # what did group batching save this step?
    bt, kv_len = sess.kv.block_table(family, width=sess.table_width)
    ps = build_prefix_schedule(kv_len, bt, page_size=PAGE, block_k=BLOCK_K)
    shared = prefix_queue_grid_items(ps, kv_len, PAGE)
    unshared = queue_grid_items(
        build_schedule(kv_len, block_k=BLOCK_K), kv_len, PAGE
    )
    print(f"groups: {shared['num_groups']} "
          f"({shared['grouped_requests']} of {len(family)} requests)")
    print(f"page DMAs per step: {shared['page_dmas']} shared vs "
          f"{unshared['page_dmas']} unshared; prefix blocks fetched once "
          f"per group: {shared['prefix_page_dmas']} vs "
          f"{shared['unshared_prefix_page_dmas']} "
          f"(~{GROUP}x dedup at group size {GROUP})")
    stats = sess.scheduler_stats
    print(f"scheduler: {stats['rebuilds']} rebuilds, {stats['hits']} hits")

    # parity: shared-path serving output == contiguous kernel on history
    q = {r: lat(HEADS) for r in family}
    out = sess.attend(q)
    worst = 0.0
    for r in family:
        c = sess.kv.gather_contiguous(r)[None]
        want = ops.mla_decode(
            jnp.asarray(q[r])[None, None], c, d_v=D_V, scale=D_K**-0.5,
            kv_len=jnp.asarray([c.shape[1]], jnp.int32), interpret=True,
        )[0, 0]
        worst = max(worst, float(jnp.max(jnp.abs(out[r] - want))))
    print(f"shared-prefix vs contiguous max|diff|: {worst:.2e}")
    assert worst <= 2e-3

    # evict the parent mid-stream: children keep their shared pages alive
    sess.evict(parent)
    out = sess.step({r: q[r] for r in kids}, {r: lat(1)[0] for r in kids})
    print(f"evicted parent r{parent}; children still decoding "
          f"({len(out)} outputs), aliased pages now: "
          f"{sess.kv.num_aliased_pages()}")
    print("OK")


if __name__ == "__main__":
    main()
