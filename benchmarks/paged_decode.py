"""Paged decode scheduling benchmark: work-queue vs padded grid, split-KV.

    PYTHONPATH=src python -m benchmarks.paged_decode [--smoke | --full]

Three numbers matter for serving, and each gets a scenario matrix
(uniform / ragged / long-context straggler batches):

* **work items** — the padded ``(B, W)`` grid pays one page-sized grid
  step per logical table slot of the *longest* request; the flat work
  queue (kernels/decode_schedule) pays one §4.2-block-sized step per KV
  block that intersects ``kv_len``.  ``work_item_ratio`` compares grid
  steps (hierarchical tiling + compaction together, the acceptance gate:
  >= 1.5x on the ragged scenario); ``compaction_ratio`` is the
  granularity-matched view (padded page slots vs live pages), isolating
  pure schedule compaction from the bigger-step win.
* **step latency / tokens/s** — measured per scheduler (on CPU interpret
  both pay a python-level tax, treat as smoke; TPU via --full is the real
  measurement).
* **rescale-skip rate** — the fraction of §4.2-block AMLA updates whose
  MUL-by-ADD increment is exactly zero (the paper's skipped [V2]); tracked
  per scenario so scheduling changes can't silently regress the numerics
  win.

``run()`` returns a JSON-able dict; ``benchmarks/run.py`` persists it as
``BENCH_decode.json`` — the cross-PR perf trajectory.  Output here is CSV
(``name,value,...``) like every other benchmarks/ section.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.amla import rescale_skip_rate
from repro.kernels import ops
from repro.kernels.decode_schedule import (
    build_prefix_schedule,
    build_schedule,
    padded_grid_items,
    prefix_queue_grid_items,
    queue_grid_items,
)
from repro.runtime.kv_cache import CacheSpec, PagedKVCache


def require_tpu() -> None:
    """A timed run measures the chip or nothing: no CPU fallback."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"no TPU found (jax sees {dev.platform}); pass --interpret for "
            "a CPU validation run of the work proxies"
        )


def _time(fn, iters: int) -> float:
    """Min-of-iters wall time in ms (min, not mean: the regression gate
    compares runs across processes/machines, and the minimum is the
    standard noise-robust estimate of the true cost)."""
    fn()  # compile / warm
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def _geometry(tier: str) -> dict:
    """Scenario matrix per tier.  kv_lens are per-request context lengths;
    the ragged tier-`full` scenario is the ISSUE-2 acceptance geometry
    (B=8, kv_len in [256, 16384]).  ``prefix_scenarios`` are fork families
    ``(group_size, prefix_len, mean suffix_len)``: group sizes {1, 4, 16}
    (ISSUE-3 acceptance) crossed with prefix:suffix ratios — the shared
    prefix dominates at 16:1 (system-prompt / n-best traffic), 4:1 keeps a
    meaningful per-request tail."""
    if tier == "full":  # serving scale (TPU)
        g = dict(hq=128, dk=576, dv=512, page=128, block_k=512, iters=20)
        rng = np.random.default_rng(7)
        g["scenarios"] = {
            "uniform": [8192] * 8,
            "ragged": [int(x) for x in rng.integers(256, 16384, 8)],
            "straggler": [1024] * 7 + [32768],
        }
        g["prefix_scenarios"] = {
            "g1_p16": (1, 8192, 512),
            "g4_p16": (4, 8192, 512),
            "g4_p4": (4, 2048, 512),
            "g16_p16": (16, 8192, 512),
            "g16_p4": (16, 2048, 512),
        }
    elif tier == "smoke":  # CI: interpret-mode, tiny shapes
        # iters=5 + min-of-iters timing: the CI regression gate compares
        # these numbers across runs, so single-shot noise is not acceptable.
        g = dict(hq=4, dk=128, dv=128, page=32, block_k=128, iters=5)
        g["scenarios"] = {
            "uniform": [96, 96, 96],
            "ragged": [16, 250, 60, 130],
            "straggler": [20, 20, 20, 300],
        }
        g["prefix_scenarios"] = {
            "g1_p8": (1, 260, 33),
            "g4_p8": (4, 260, 33),
            "g16_p8": (16, 260, 33),
        }
    else:  # default: interpret-friendly but paper-geometry rows
        g = dict(hq=8, dk=576, dv=512, page=128, block_k=512, iters=2)
        rng = np.random.default_rng(7)
        g["scenarios"] = {
            "uniform": [1024] * 4,
            "ragged": [int(x) for x in rng.integers(128, 2048, 4)],
            "straggler": [256] * 3 + [2048],
        }
        g["prefix_scenarios"] = {
            "g1_p16": (1, 1024, 64),
            "g4_p16": (4, 1024, 64),
            "g4_p4": (4, 1024, 256),
            "g16_p16": (16, 1024, 64),
        }
    return g


def _measure_rescale_skip(q_rows, c, kv_lens, scale, block_k) -> float:
    """Mean per-request fraction of §4.2-block updates whose AMLA increment
    is zero (running max stays inside one power-of-two bin)."""
    rates = []
    for r, l in enumerate(kv_lens):
        nb = -(-int(l) // block_k)
        if nb < 2:  # no transitions to measure
            continue
        qr = np.asarray(q_rows[r], np.float32)
        cr = np.asarray(c[r, :l], np.float32)
        m = np.full((qr.shape[0],), -1.0e5, np.float32)
        trace = []
        for i in range(nb):
            s = (qr @ cr[i * block_k : min((i + 1) * block_k, l)].T) * scale
            m = np.maximum(m, s.max(axis=-1))
            trace.append(m.copy())
        rates.append(float(rescale_skip_rate(jnp.asarray(np.stack(trace)))))
    return float(np.mean(rates)) if rates else 1.0


def _run_scenario(name, kv_lens, *, hq, dk, dv, page, block_k, iters,
                  interpret, num_splits) -> dict:
    b = len(kv_lens)
    max_len = max(kv_lens)
    rng = np.random.default_rng(0)
    scale = 1.0 / dk**0.5
    q = jnp.asarray(rng.normal(0, 0.3, (b, 1, hq, dk)), jnp.bfloat16)
    c = jnp.asarray(rng.normal(0, 0.3, (b, max_len, dk)), jnp.bfloat16)
    kv_len = jnp.asarray(kv_lens, jnp.int32)

    kv = PagedKVCache(
        num_pages=sum(-(-l // page) for l in kv_lens) + 1,
        page_size=page,
        width=dk,
    )
    for rid, l in enumerate(kv_lens):
        kv.alloc(rid)
        kv.append(rid, c[rid, :l])
    bt, _ = kv.block_table(list(range(b)))
    bt = jnp.asarray(bt)
    w = bt.shape[1]

    schedule = build_schedule(kv_lens, block_k=block_k, num_splits=num_splits)
    padded_work = padded_grid_items(kv_lens, w, page)
    queue_work = queue_grid_items(schedule, kv_lens, page)

    def contiguous():
        return ops.mla_decode(
            q, c, d_v=dv, scale=scale, kv_len=kv_len, interpret=interpret
        )

    def padded():
        return ops.mla_decode_paged(
            q, kv.pages, bt, kv_len, d_v=dv, scale=scale,
            interpret=interpret, scheduler="padded",
        )

    def queue():
        return ops.mla_decode_paged(
            q, kv.pages, bt, kv_len, d_v=dv, scale=scale,
            interpret=interpret, scheduler="queue",
            block_k=block_k, schedule=schedule,
        )

    max_abs_queue = float(jnp.max(jnp.abs(queue() - contiguous())))
    max_abs_padded = float(jnp.max(jnp.abs(padded() - contiguous())))
    ms_padded = _time(padded, iters)
    ms_queue = _time(queue, iters)
    skip = _measure_rescale_skip(
        np.asarray(q[:, 0], np.float32), c, kv_lens, scale, block_k
    )

    # memory: rows resident on device to serve this batch
    paged_rows = kv.num_pages * page
    used_rows = sum(kv_lens)

    return {
        "b": b,
        "kv_lens": list(map(int, kv_lens)),
        "table_width": int(w),
        "ms_per_step_padded": ms_padded,
        "ms_per_step_queue": ms_queue,
        "tokens_per_s_padded": b / (ms_padded / 1e3),
        "tokens_per_s_queue": b / (ms_queue / 1e3),
        "rescale_skip_rate": skip,
        "grid_steps_padded": padded_work["grid_steps"],
        "grid_steps_queue": queue_work["grid_steps"],
        "executed_items_queue": queue_work["executed_items"],
        "page_dmas_padded": padded_work["page_dmas"],
        "page_dmas_queue": queue_work["page_dmas"],
        # dtype-aware traffic: DMA count x bytes one page moves at this
        # pool's storage layout (decode MLA is bandwidth-bound, so *bytes*
        # — not counts — are what the cache-dtype lever changes).
        "page_dma_bytes_padded": padded_work["page_dmas"]
        * kv.spec.bytes_per_page(page, dk),
        "page_dma_bytes_queue": queue_work["page_dmas"]
        * kv.spec.bytes_per_page(page, dk),
        # grid-step ratio: fewer, bigger steps (§4.2 block granularity vs
        # page granularity) *and* schedule compaction
        "work_item_ratio": padded_work["grid_steps"]
        / max(queue_work["grid_steps"], 1),
        # granularity-matched: page slots walked by the padded grid vs live
        # pages touched by the queue — pure compaction, no tiling credit
        "compaction_ratio": padded_work["page_slots"]
        / max(queue_work["live_pages"], 1),
        "num_splits": num_splits,
        "max_abs_diff_vs_contiguous_queue": max_abs_queue,
        "max_abs_diff_vs_contiguous_padded": max_abs_padded,
        "pool_util": used_rows / paged_rows,
    }


def _run_prefix_scenario(name, group_size, prefix_len, suffix_mean, *,
                         hq, dk, dv, page, block_k, iters, interpret) -> dict:
    """Fork family: one parent prefix aliased by ``group_size`` members with
    ragged suffixes; shared-prefix path vs the plain per-request queue."""
    rng = np.random.default_rng(3)
    scale = 1.0 / dk**0.5
    suffix_lens = [
        int(x) for x in rng.integers(max(suffix_mean // 2, 1),
                                     2 * suffix_mean, group_size)
    ]
    num_pages = (
        -(-prefix_len // page) + group_size
        + sum(-(-n // page) for n in suffix_lens) + 2
    )
    kv = PagedKVCache(num_pages=num_pages, page_size=page, width=dk)
    kv.alloc(0)
    kv.append(0, jnp.asarray(rng.normal(0, 0.3, (prefix_len, dk)),
                             jnp.bfloat16))
    for rid in range(1, group_size):
        kv.fork(0, rid, prefix_len)
    for rid, n in enumerate(suffix_lens):
        kv.append(rid, jnp.asarray(rng.normal(0, 0.3, (n, dk)),
                                   jnp.bfloat16))
    rids = list(range(group_size))
    bt_np, kv_lens = kv.block_table(rids)
    bt = jnp.asarray(bt_np)
    kv_len = jnp.asarray(kv_lens)
    q = jnp.asarray(rng.normal(0, 0.3, (group_size, 1, hq, dk)),
                    jnp.bfloat16)

    ps = build_prefix_schedule(kv_lens, bt_np, page_size=page,
                               block_k=block_k)
    plain = build_schedule(kv_lens, block_k=block_k)
    shared_work = prefix_queue_grid_items(ps, kv_lens, page)
    plain_work = queue_grid_items(plain, kv_lens, page)

    def shared():
        return ops.mla_decode_paged(
            q, kv.pages, bt, kv_len, d_v=dv, scale=scale,
            interpret=interpret, block_k=block_k, schedule=ps,
        )

    def unshared():
        return ops.mla_decode_paged(
            q, kv.pages, bt, kv_len, d_v=dv, scale=scale,
            interpret=interpret, block_k=block_k, schedule=plain,
        )

    max_abs = float(jnp.max(jnp.abs(shared() - unshared())))
    ms_shared = _time(shared, iters)
    ms_unshared = _time(unshared, iters)
    pdma = shared_work["prefix_page_dmas"]
    return {
        "group_size": group_size,
        "prefix_len": prefix_len,
        "suffix_lens": suffix_lens,
        "num_groups": shared_work["num_groups"],
        "ms_per_step_shared": ms_shared,
        "ms_per_step_unshared": ms_unshared,
        "tokens_per_s_shared": group_size / (ms_shared / 1e3),
        "tokens_per_s_unshared": group_size / (ms_unshared / 1e3),
        "page_dmas_shared": shared_work["page_dmas"],
        "page_dmas_unshared": plain_work["page_dmas"],
        "prefix_page_dmas": pdma,
        "unshared_prefix_page_dmas": shared_work["unshared_prefix_page_dmas"],
        # the headline: shared prefix pages fetched once per group
        "prefix_dma_reduction": (
            shared_work["unshared_prefix_page_dmas"] / pdma if pdma else 1.0
        ),
        "executed_items_shared": shared_work["executed_items"],
        "executed_items_unshared": plain_work["executed_items"],
        "max_abs_diff_shared_vs_unshared": max_abs,
    }


def _run_int8_scenario(name, kv_lens, *, hq, dk, dv, page, block_k, iters,
                       interpret) -> dict:
    """Int8-vs-bf16 storage row: the same ragged batch decoded through a
    bf16 pool and through an int8+scales pool (fused in-pipeline dequant).

    The headline is ``dma_bytes_reduction_vs_bf16``: identical schedules
    fetch identical page *counts*, but each int8 page moves about half the
    bytes (ISSUE-5 acceptance: >= 1.9x) — with |int8 − bf16| <= 3e-2
    fp32-combined parity riding along.
    """
    b = len(kv_lens)
    rng = np.random.default_rng(0)
    scale = 1.0 / dk**0.5
    q = jnp.asarray(rng.normal(0, 0.3, (b, 1, hq, dk)), jnp.bfloat16)
    num_pages = sum(-(-l // page) for l in kv_lens) + 1
    pools = {
        "bf16": PagedKVCache(num_pages=num_pages, page_size=page, width=dk),
        "int8": PagedKVCache(num_pages=num_pages, page_size=page, width=dk,
                             spec=CacheSpec(dtype=jnp.int8)),
    }
    for rid, l in enumerate(kv_lens):
        data = rng.normal(0, 0.3, (l, dk)).astype(np.float32)
        for kv in pools.values():
            kv.alloc(rid)
            kv.append(rid, data)
    kv_len = jnp.asarray(kv_lens, jnp.int32)
    schedule = build_schedule(kv_lens, block_k=block_k)
    work = queue_grid_items(schedule, kv_lens, page)

    def decode(kv):
        bt, _ = kv.block_table(list(range(b)))
        return ops.mla_decode_paged(
            q, kv.pages, jnp.asarray(bt), kv_len, kv_scales=kv.scales,
            d_v=dv, scale=scale, interpret=interpret, block_k=block_k,
            schedule=schedule,
        )

    max_abs = float(jnp.max(jnp.abs(decode(pools["int8"])
                                    - decode(pools["bf16"]))))
    ms = {k: _time(lambda kv=kv: decode(kv), iters)
          for k, kv in pools.items()}
    dma_bytes = {
        k: work["page_dmas"] * kv.spec.bytes_per_page(page, dk)
        for k, kv in pools.items()
    }
    return {
        "b": b,
        "kv_lens": list(map(int, kv_lens)),
        "ms_per_step_bf16": ms["bf16"],
        "ms_per_step_int8": ms["int8"],
        "tokens_per_s_int8": b / (ms["int8"] / 1e3),
        "page_dmas_queue": work["page_dmas"],
        "page_dma_bytes_bf16": dma_bytes["bf16"],
        "page_dma_bytes_int8": dma_bytes["int8"],
        "dma_bytes_reduction_vs_bf16": dma_bytes["bf16"] / dma_bytes["int8"],
        "max_abs_diff_int8_vs_bf16": max_abs,
    }


def run(
    full: bool = False,
    smoke: bool = False,
    num_splits: int = 2,
    interpret: bool = False,
) -> dict:
    """``interpret`` runs the kernels in Pallas interpret mode on the CPU
    (mode ``cpu-interpret``); otherwise a TPU is required."""
    if not interpret:
        require_tpu()
    tier = "full" if full else ("smoke" if smoke else "default")
    g = _geometry(tier)
    mode = "cpu-interpret" if interpret else "tpu"

    report = {
        "bench": "paged_decode",
        "mode": mode,
        "tier": tier,
        "hq": g["hq"],
        "page_size": g["page"],
        "block_k": g["block_k"],
        "scenarios": {},
    }
    print(
        f"paged_decode,mode,{mode},tier,{tier},hq,{g['hq']},"
        f"page,{g['page']},block_k,{g['block_k']}"
    )
    for name, kv_lens in g["scenarios"].items():
        res = _run_scenario(
            name,
            kv_lens,
            hq=g["hq"],
            dk=g["dk"],
            dv=g["dv"],
            page=g["page"],
            block_k=g["block_k"],
            iters=g["iters"],
            interpret=interpret,
            num_splits=num_splits,
        )
        report["scenarios"][name] = res
        print(
            f"paged_decode,scenario,{name},b,{res['b']},"
            f"ms_padded,{res['ms_per_step_padded']:.3f},"
            f"ms_queue,{res['ms_per_step_queue']:.3f},"
            f"tokens_per_s_queue,{res['tokens_per_s_queue']:.1f}"
        )
        print(
            f"paged_decode,scenario,{name},"
            f"grid_steps_padded,{res['grid_steps_padded']},"
            f"grid_steps_queue,{res['grid_steps_queue']},"
            f"work_item_ratio,{res['work_item_ratio']:.2f},"
            f"compaction_ratio,{res['compaction_ratio']:.2f},"
            f"page_dmas_padded,{res['page_dmas_padded']},"
            f"page_dmas_queue,{res['page_dmas_queue']},"
            f"page_dma_bytes_queue,{res['page_dma_bytes_queue']}"
        )
        print(
            f"paged_decode,scenario,{name},"
            f"rescale_skip_rate,{res['rescale_skip_rate']:.3f},"
            f"max_abs_queue,{res['max_abs_diff_vs_contiguous_queue']:.3e},"
            f"max_abs_padded,{res['max_abs_diff_vs_contiguous_padded']:.3e}"
        )
    report["prefix_scenarios"] = {}
    for name, (gsz, plen, smean) in g.get("prefix_scenarios", {}).items():
        res = _run_prefix_scenario(
            name, gsz, plen, smean,
            hq=g["hq"], dk=g["dk"], dv=g["dv"], page=g["page"],
            block_k=g["block_k"], iters=g["iters"], interpret=interpret,
        )
        report["prefix_scenarios"][name] = res
        print(
            f"paged_decode,prefix_scenario,{name},group,{gsz},"
            f"prefix_len,{plen},"
            f"ms_shared,{res['ms_per_step_shared']:.3f},"
            f"ms_unshared,{res['ms_per_step_unshared']:.3f},"
            f"tokens_per_s_shared,{res['tokens_per_s_shared']:.1f}"
        )
        print(
            f"paged_decode,prefix_scenario,{name},"
            f"prefix_dma_reduction,{res['prefix_dma_reduction']:.2f},"
            f"page_dmas_shared,{res['page_dmas_shared']},"
            f"page_dmas_unshared,{res['page_dmas_unshared']},"
            f"items_shared,{res['executed_items_shared']},"
            f"items_unshared,{res['executed_items_unshared']},"
            f"max_abs,{res['max_abs_diff_shared_vs_unshared']:.3e}"
        )

    # int8-vs-bf16 storage row: same ragged batch, both cache dtypes.
    report["int8_scenarios"] = {}
    res = _run_int8_scenario(
        "ragged_int8", g["scenarios"]["ragged"],
        hq=g["hq"], dk=g["dk"], dv=g["dv"], page=g["page"],
        block_k=g["block_k"], iters=g["iters"], interpret=interpret,
    )
    report["int8_scenarios"]["ragged_int8"] = res
    print(
        f"paged_decode,int8_scenario,ragged_int8,b,{res['b']},"
        f"ms_bf16,{res['ms_per_step_bf16']:.3f},"
        f"ms_int8,{res['ms_per_step_int8']:.3f},"
        f"page_dma_bytes_bf16,{res['page_dma_bytes_bf16']},"
        f"page_dma_bytes_int8,{res['page_dma_bytes_int8']},"
        f"dma_bytes_reduction,{res['dma_bytes_reduction_vs_bf16']:.2f},"
        f"max_abs_int8_vs_bf16,{res['max_abs_diff_int8_vs_bf16']:.3e}"
    )
    # ISSUE-5 acceptance: >= 1.9x byte reduction at <= 3e-2 parity.
    int8_ok = (
        res["dma_bytes_reduction_vs_bf16"] >= 1.9
        and res["max_abs_diff_int8_vs_bf16"] <= 3e-2
    )
    print(
        f"paged_decode,acceptance_int8_bytes,"
        f"{res['dma_bytes_reduction_vs_bf16']:.2f},target,1.9,"
        f"parity,{res['max_abs_diff_int8_vs_bf16']:.3e},pass,{int(int8_ok)}"
    )

    ragged = report["scenarios"]["ragged"]
    ok = ragged["work_item_ratio"] >= 1.5
    print(
        f"paged_decode,acceptance_ragged_work_ratio,"
        f"{ragged['work_item_ratio']:.2f},"
        f"compaction_ratio,{ragged['compaction_ratio']:.2f},pass,{int(ok)}"
    )
    # ISSUE-3 acceptance: shared-prefix DMA dedup ~G x at group size G
    # (within 10%), and the shared path does strictly less work (the
    # interpret-mode tokens/s proxy) at group size >= 4.
    for name, res in report["prefix_scenarios"].items():
        gsz = res["group_size"]
        if gsz < 4:
            continue
        dma_ok = abs(res["prefix_dma_reduction"] - gsz) / gsz <= 0.10
        work_ok = (
            res["executed_items_shared"] < res["executed_items_unshared"]
            and res["page_dmas_shared"] < res["page_dmas_unshared"]
        )
        print(
            f"paged_decode,acceptance_prefix,{name},"
            f"dma_reduction,{res['prefix_dma_reduction']:.2f},"
            f"target,{gsz},pass,{int(dma_ok and work_ok)}"
        )
    return report


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--full",
        action="store_true",
        help="serving-scale geometry (TPU); default is an interpret-safe tier",
    )
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="tiny interpret-mode shapes for CI (keeps benchmark code green)",
    )
    ap.add_argument("--num-splits", type=int, default=2)
    ap.add_argument(
        "--interpret",
        action="store_true",
        help="CPU validation: Pallas interpret mode, no TPU",
    )
    args = ap.parse_args()
    run(
        full=args.full,
        smoke=args.smoke,
        num_splits=args.num_splits,
        interpret=args.interpret,
    )
