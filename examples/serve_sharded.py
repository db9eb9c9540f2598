"""Multi-host sharded paged serving on a forced 4-device CPU mesh.

    PYTHONPATH=src python examples/serve_sharded.py

Serves the same ragged request stream — admissions, decode steps, a forked
shared-prefix family, evictions — through the single-host
PagedServingSession and a ShardedPagedServingSession over a real ``(data=2,
model=2)`` jax mesh (4 CPU devices forced via XLA_FLAGS before jax
initializes).  Each data shard owns its own latent page pool + decode work
queue on its own device; the model axis runs 2-way tensor-parallel head
chunks.  The checks:

* greedy outputs match the single-host paged backend **exactly** (routing
  is data-parallel: each request's kernel math is shard-local and
  bit-identical to a single-host batch holding the same request);
* the forked family lands on one shard (page aliasing is pool-local);
* per-shard work proxies are reported with the max/mean imbalance.
"""

import os

# Must precede jax backend initialization (which importing repro triggers).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=4"
).strip()

import dataclasses

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.mesh import make_serving_mesh
from repro.models.model_zoo import build_model
from repro.runtime.serve_loop import PagedServingSession, ShardedPagedServingSession


def main() -> None:
    assert len(jax.devices()) >= 4, jax.devices()
    cfg = get_config("deepseek-v2-mla", smoke=True)
    cfg = dataclasses.replace(cfg, n_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    mesh = make_serving_mesh("2x2")

    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(2, cfg.vocab_size, size=n).tolist()
        for n in (9, 21, 6, 14)
    ]
    suffixes = [
        rng.integers(2, cfg.vocab_size, size=n).tolist() for n in (4, 7)
    ]

    single = PagedServingSession(
        model, params, num_pages=64, page_size=8, interpret=True
    )
    sharded = ShardedPagedServingSession(
        model, params, num_pages=64, mesh=mesh, page_size=8, interpret=True
    )
    print(
        f"mesh 2x2: {sharded.num_shards} data shards x "
        f"{sharded.head_shards}-way TP heads, 32 pages per shard pool"
    )

    def drive(sess):
        rids = [sess.add_request(p) for p in prompts]
        for _ in range(3):
            sess.step()
        # branch a shared-prefix family off the longest prompt
        kids = [
            sess.admit_with_prefix(rids[1], s, prefix_len=16)
            for s in suffixes
        ]
        for _ in range(4):
            sess.step()
        early = sess.finish(rids[2])  # eviction mid-stream
        for _ in range(2):
            sess.step()
        outs = [
            sess.finish(r) for r in rids[:2] + rids[3:] + kids
        ]
        return [early] + outs

    got_single = drive(single)
    got_sharded = drive(sharded)
    assert got_single == got_sharded, (got_single, got_sharded)
    print(f"greedy parity: {len(got_single)} requests match exactly")

    # The forked family must share one pool: re-admit to inspect routing.
    parent = sharded.add_request(prompts[1])
    kids = [
        sharded.admit_with_prefix(parent, s, prefix_len=16) for s in suffixes
    ]
    family_shards = {sharded.shard_of(r) for r in [parent] + kids}
    assert len(family_shards) == 1, family_shards
    aliased = sharded.work_stats()["aliased_pages"]
    assert aliased > 0, "forked family should alias prefix pages"
    print(
        f"forked family on shard {family_shards.pop()} "
        f"({aliased} pages aliased, zero rows copied)"
    )

    work = sharded.work_stats()
    for i, st in enumerate(work["per_shard"]):
        print(
            f"shard {i}: {st['page_dmas']} page DMAs, "
            f"{st['rows_attended']} rows attended, "
            f"{st['free_pages']} pages free"
        )
    bal = work["balance"]
    print(f"shard work balance: max/mean = {bal['imbalance']:.2f}")
    print("OK")


if __name__ == "__main__":
    main()
