"""Benchmark harness: one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--skip accuracy]

Sections:
  [T2]  arithmetic intensity (paper Table 2 / Fig. 1)
  [T3/T4] accuracy vs golden (paper Tables 3-4) + compensation ablations
  [T5]  kernel FLOPS-utilisation model (paper Table 5 / Fig. 10)
  [PAGED] decode scheduling: work-queue vs padded grid, split-KV,
          shared-prefix group batching
  [MODEL-SERVE] full-model serving: dense vs paged cache backend
          (tokens/s + page-DMA / row-read proxies, schedule reuse)
  [ROOFLINE] per-(arch x shape x mesh) dry-run roofline table (assignment)

Each section prints CSV (``name,value,...``) so downstream tooling can diff.
The [PAGED] section additionally persists its per-scenario report
(tokens/s, ms/step, work items, rescale-skip rate) as ``BENCH_decode.json``
— the machine-readable perf trajectory diffed across PRs — and **appends** a
compact summary of every run, keyed by git SHA, to ``BENCH_history.json``
(never overwritten: the longitudinal record survives baseline refreshes;
re-runs at the same SHA replace that SHA's entry so local iteration can't
bloat the trajectory file).

``--check-regression`` turns the [PAGED] section into a CI gate: before the
baseline file is overwritten, the freshly-measured scenarios are compared
against the committed report (same tier and mode only — a TPU run is never
judged against an interpret baseline) and the process exits non-zero on a
regression beyond ``--regression-tolerance`` (default 10%).  On TPU the
gated metric is tokens/s; in interpret mode (CI) it is the deterministic
work proxies — page DMAs, executed items, prefix DMA reduction — because
interpret wall time is Python-dispatch noise (see ``check_regression``).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import time


def section(name):
    print(f"\n===== [{name}] =====", flush=True)


def _git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def _summarize(report: dict) -> dict:
    """Compact per-run record for the longitudinal history file."""
    out = {
        "mode": report.get("mode"),
        "tier": report.get("tier"),
        "scenarios": {},
        "prefix_scenarios": {},
    }
    def pick(name, res, required, optional=()):
        # Loud on missing *required* metrics: a renamed/dropped benchmark
        # field must crash the run here, not silently vanish from the
        # history and un-gate its regression check downstream.
        missing = [k for k in required if k not in res]
        if missing:
            raise KeyError(
                f"benchmark scenario {name!r} stopped emitting gated "
                f"metrics {missing} — update the benchmark or this summary"
            )
        keys = tuple(required) + tuple(optional)
        return {k: res[k] for k in keys if k in res}

    for name, res in report.get("scenarios", {}).items():
        out["scenarios"][name] = pick(name, res, (
            "tokens_per_s_queue",
            "work_item_ratio",
            "page_dmas_queue",
            "page_dma_bytes_queue",
            "rescale_skip_rate",
        ))
    for name, res in report.get("prefix_scenarios", {}).items():
        out["prefix_scenarios"][name] = pick(name, res, (
            "tokens_per_s_shared",
            "tokens_per_s_unshared",
            "prefix_dma_reduction",
            "page_dmas_shared",
        ))
    if report.get("int8_scenarios"):
        out["int8_scenarios"] = {}
        for name, res in report["int8_scenarios"].items():
            out["int8_scenarios"][name] = pick(name, res, (
                "tokens_per_s_int8",
                "page_dma_bytes_bf16",
                "page_dma_bytes_int8",
                "dma_bytes_reduction_vs_bf16",
                "max_abs_diff_int8_vs_bf16",
            ))
    if report.get("model_serve"):
        out["model_serve"] = {}
        for name, res in report["model_serve"].items():
            # dense-twin and dtype-comparison metrics are optional: the
            # int8_vs_bf16 row has no dense session and the dense-vs-paged
            # rows no dtype twin — but what a row measures, it must keep.
            out["model_serve"][name] = pick(name, res, (
                "tokens_per_s_paged",
                "page_dmas_paged",
                "page_dma_bytes_paged",
                "schedule_rebuilds",
            ), optional=(
                "tokens_per_s_dense",
                "dma_bytes_reduction_vs_bf16",
                "greedy_match_vs_bf16",
                "read_reduction_vs_dense",
                "greedy_match_vs_single",
                "shard_imbalance",
                "accepted_tokens_per_step",
                "page_dma_bytes_per_accepted_token",
                "greedy_match_vs_off",
                "dma_per_token_vs_off",
                "completed_fraction",
                "greedy_match_vs_nofault",
                "replay_token_overhead",
                "replay_mismatches",
                "leaked_pages",
                "dma_bytes_reduction_vs_off",
                "dma_bytes_reduction_vs_off_int8",
                "greedy_match_vs_off_int8",
                "prefix_hit_rate",
                "prefix_tokens_reused_per_admission",
                "trie_evicted_pages",
                "sweep_clean",
                "greedy_match_vs_phased",
                "greedy_match_vs_phased_int8",
                "ttft_interactive_p99_phased",
                "ttft_interactive_p99_interleaved",
                "ttft_interactive_p99_improvement",
                "tpot_units_p99_phased",
                "tpot_units_p99_interleaved",
                "tpot_p99_improvement",
                "prefill_stall_steps_interleaved",
                "units_per_token_ratio",
                "deadline_abandons",
            ))
    return out


def append_history(report: dict, path: str) -> None:
    """Append (never overwrite) this run to the per-PR history, SHA-keyed."""
    history = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                history = json.load(f)
            if not isinstance(history, list):
                history = []
        except (json.JSONDecodeError, OSError):
            history = []  # corrupt history must not kill the benchmark
    entry = {
        "sha": _git_sha(),
        "time": time.strftime("%Y-%m-%dT%H:%M:%S"),
        **_summarize(report),
    }
    # One entry per SHA, keep-latest: repeated local runs at the same
    # commit would otherwise append forever and swamp the per-PR diff.
    history = [h for h in history if h.get("sha") != entry["sha"]]
    history.append(entry)
    with open(path, "w") as f:
        json.dump(history, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"paged_decode,history,{path},entries,{len(history)}")


def merge_baseline_sections(report: dict, baseline_path: str) -> dict:
    """Carry like-for-like baseline sections a partial run didn't produce.

    ``--skip paged`` / ``--skip model-serve`` would otherwise overwrite the
    committed baseline with empty sections — and every later
    ``--check-regression`` against it would pass vacuously (missing
    reference metrics are skipped), silently un-gating that section.
    """
    if not os.path.exists(baseline_path):
        return report
    try:
        with open(baseline_path) as f:
            base = json.load(f)
    except (json.JSONDecodeError, OSError):
        return report
    if (base.get("tier"), base.get("mode")) != (
        report.get("tier"), report.get("mode")
    ):
        return report
    for key in ("scenarios", "prefix_scenarios", "int8_scenarios",
                "model_serve"):
        if not report.get(key) and base.get(key):
            report[key] = base[key]
            print(f"paged_decode,baseline_carryover,{key},from,{baseline_path}")
    return report


def check_regression(report: dict, baseline_path: str, tol: float) -> list:
    """Compare per-scenario perf against the committed baseline.

    Only like-for-like runs gate (same tier AND same mode); otherwise the
    check is skipped with a notice.  What gates depends on the mode:

    * ``tpu`` — **tokens/s**, the real measurement: fail when any scenario
      dropped more than ``tol``.
    * ``cpu-interpret`` (CI) — interpret-mode wall time is dominated by
      Python dispatch on a shared runner and jitters far beyond any usable
      threshold, so tokens/s prints *informationally* and the gate runs on
      the **deterministic work proxies** instead (page DMAs, executed work
      items, prefix DMA-reduction factor): fail when the schedule does
      more work than the baseline at equal output.  This is the ISSUE-3
      acceptance proxy, and it is exactly reproducible.

    Returns the list of failures.
    """
    if not os.path.exists(baseline_path):
        print(f"paged_decode,regression_check,skipped,no baseline at "
              f"{baseline_path}")
        return []
    with open(baseline_path) as f:
        base = json.load(f)
    if (base.get("tier"), base.get("mode")) != (
        report.get("tier"), report.get("mode")
    ):
        print(
            f"paged_decode,regression_check,skipped,baseline is "
            f"{base.get('tier')}/{base.get('mode')} vs current "
            f"{report.get('tier')}/{report.get('mode')}"
        )
        return []
    on_tpu = report.get("mode") == "tpu"
    failures = []
    # metric, section, lower-is-better, gated-in-this-mode
    checks = [
        ("scenarios", "tokens_per_s_queue", False, on_tpu),
        ("prefix_scenarios", "tokens_per_s_shared", False, on_tpu),
        ("scenarios", "page_dmas_queue", True, not on_tpu),
        ("scenarios", "grid_steps_queue", True, not on_tpu),
        # dtype-aware traffic: page-DMA *bytes* are the bandwidth proxy the
        # cache-dtype lever moves; gated so a storage-layout regression
        # (e.g. silently falling back to bf16) fails CI.
        ("scenarios", "page_dma_bytes_queue", True, not on_tpu),
        ("int8_scenarios", "page_dma_bytes_int8", True, not on_tpu),
        ("int8_scenarios", "dma_bytes_reduction_vs_bf16", False, not on_tpu),
        ("prefix_scenarios", "page_dmas_shared", True, not on_tpu),
        ("prefix_scenarios", "executed_items_shared", True, not on_tpu),
        ("prefix_scenarios", "prefix_dma_reduction", False, not on_tpu),
        # [MODEL-SERVE]: real tokens/s on TPU; deterministic schedule work
        # (page DMAs/bytes, rebuild count, dense-read reduction) in CI.
        ("model_serve", "tokens_per_s_paged", False, on_tpu),
        ("model_serve", "page_dmas_paged", True, not on_tpu),
        ("model_serve", "page_dma_bytes_paged", True, not on_tpu),
        ("model_serve", "dma_bytes_reduction_vs_bf16", False, not on_tpu),
        ("model_serve", "schedule_rebuilds", True, not on_tpu),
        ("model_serve", "read_reduction_vs_dense", False, not on_tpu),
        # [MODEL-SERVE] sharded row: exact greedy parity with the
        # single-host backend and the max/mean shard work split are both
        # deterministic, so they gate in CI like the other work proxies.
        ("model_serve", "greedy_match_vs_single", False, not on_tpu),
        ("model_serve", "shard_imbalance", True, not on_tpu),
        # [MODEL-SERVE] speculative row: acceptance rate, the per-accepted-
        # token DMA proxy, and exact greedy parity with --speculate off are
        # all deterministic in interpret mode, so they gate like the rest.
        ("model_serve", "accepted_tokens_per_step", False, not on_tpu),
        ("model_serve", "page_dma_bytes_per_accepted_token", True, not on_tpu),
        ("model_serve", "dma_per_token_vs_off", False, not on_tpu),
        ("model_serve", "greedy_match_vs_off", False, not on_tpu),
        # [MODEL-SERVE] failure_recovery row: completion and greedy parity
        # under a seeded shard loss are deterministic; replay overhead is
        # the recovery cost (lower is better — more overhead = regression).
        ("model_serve", "completed_fraction", False, not on_tpu),
        ("model_serve", "greedy_match_vs_nofault", False, not on_tpu),
        ("model_serve", "replay_token_overhead", True, not on_tpu),
        # [MODEL-SERVE] multi_tenant row: the prefix-trie DMA dedup factor
        # and the trie hit rate are deterministic work proxies — a trie
        # regression (missed matches, broken retention) shrinks both.
        ("model_serve", "dma_bytes_reduction_vs_off", False, not on_tpu),
        ("model_serve", "prefix_hit_rate", False, not on_tpu),
        # [MODEL-SERVE] multi_tenant_sla row: the interactive-class p99
        # TTFT improvement, per-token latency improvement, and interleaved
        # stall count are deterministic on the work-unit clock — a
        # scheduling regression (budget starvation, lost interleaving)
        # shrinks the improvements or revives stall steps (0 baseline:
        # any nonzero count fails outright).
        ("model_serve", "ttft_interactive_p99_improvement", False, not on_tpu),
        ("model_serve", "tpot_p99_improvement", False, not on_tpu),
        ("model_serve", "units_per_token_ratio", False, not on_tpu),
        ("model_serve", "prefill_stall_steps_interleaved", True, not on_tpu),
    ]
    for section_key, metric, lower_better, gated in checks:
        for name, res in report.get(section_key, {}).items():
            ref = base.get(section_key, {}).get(name, {}).get(metric)
            if ref is None or metric not in res:
                continue
            now = res[metric]
            if ref == 0:
                # A zero baseline is a real reference (e.g. a work proxy
                # that must stay at zero), not a missing one: no ratio
                # exists, so require equal-or-better outright.
                worse = now > 0 if lower_better else now < 0
                drop = float("inf") if worse else 0.0
            else:
                drop = (now - ref) / ref if lower_better else (ref - now) / ref
            bad = gated and drop > tol
            status = "fail" if bad else ("ok" if gated else "info")
            print(
                f"paged_decode,regression,{name},{metric},"
                f"baseline,{ref:.1f},now,{now:.1f},"
                f"worse,{100 * drop:.1f}%,{status}"
            )
            if bad:
                failures.append((name, metric, ref, now))
    return failures


# Hard acceptance floors checked against constants, not the committed
# baseline — a baseline refresh can ratchet a relative gate downward, but
# these invariants must hold outright in every deterministic run:
# speculation accepts at least the one token a plain step would (>= 1.0 by
# construction — below it the accounting itself is broken), stays
# token-exact vs --speculate off, and never costs more page-DMA bytes per
# accepted token than non-speculative decode (dma_per_token_vs_off is
# off/spec, so >= 1.0 means at-or-below baseline).
ABSOLUTE_FLOORS = [
    ("model_serve", "speculative", "accepted_tokens_per_step", 1.0),
    ("model_serve", "speculative", "greedy_match_vs_off", 1.0),
    ("model_serve", "speculative", "dma_per_token_vs_off", 1.0),
    # Fault tolerance is pass/fail, never relative: after a seeded shard
    # loss every request must complete (1.0), with tokens bit-identical to
    # the fault-free run (1.0).  A baseline refresh must not be able to
    # ratchet either below exact.
    ("model_serve", "failure_recovery", "completed_fraction", 1.0),
    ("model_serve", "failure_recovery", "greedy_match_vs_nofault", 1.0),
    # Prefix-trie admission must be invisible in the tokens (bit-exact
    # greedy vs trie-off, both cache dtypes), dominate the off path by at
    # least 2x in page-DMA bytes at equal output, and tear down leak-free
    # after eviction churn (sweep_clean is 1.0 only when every page of
    # every session returned to the free list).
    ("model_serve", "multi_tenant", "greedy_match_vs_off", 1.0),
    ("model_serve", "multi_tenant", "greedy_match_vs_off_int8", 1.0),
    ("model_serve", "multi_tenant", "dma_bytes_reduction_vs_off", 2.0),
    ("model_serve", "multi_tenant", "dma_bytes_reduction_vs_off_int8", 2.0),
    ("model_serve", "multi_tenant", "sweep_clean", 1.0),
    ("model_serve", "multi_tenant", "sweep_clean_int8", 1.0),
    # Token-budgeted interleaving must be invisible in the tokens (budgeted
    # chunk slices land on the same chunk boundaries as monolithic prefill,
    # both cache dtypes), beat phased admission on the interactive-class
    # p99 TTFT proxy at equal-or-better units/token (>= 1.0 means at or
    # below phased on both), and tear down leak-free with pending
    # mid-prefill rows in flight.
    ("model_serve", "multi_tenant_sla", "greedy_match_vs_phased", 1.0),
    ("model_serve", "multi_tenant_sla", "greedy_match_vs_phased_int8", 1.0),
    ("model_serve", "multi_tenant_sla", "ttft_interactive_p99_improvement", 1.0),
    ("model_serve", "multi_tenant_sla", "tpot_p99_improvement", 1.0),
    ("model_serve", "multi_tenant_sla", "units_per_token_ratio", 1.0),
    ("model_serve", "multi_tenant_sla", "sweep_clean", 1.0),
]


def check_floors(report: dict) -> list:
    """Gate ABSOLUTE_FLOORS (deterministic modes only; info on TPU, where
    greedy near-ties can shift acceptance run to run).  Returns failures."""
    gated = report.get("mode") != "tpu"
    failures = []
    for section_key, name, metric, floor in ABSOLUTE_FLOORS:
        res = report.get(section_key, {}).get(name, {})
        if metric not in res:
            continue
        now = res[metric]
        bad = gated and now < floor
        status = "fail" if bad else ("ok" if gated else "info")
        print(
            f"paged_decode,floor,{name},{metric},min,{floor},"
            f"now,{now:.2f},{status}"
        )
        if bad:
            failures.append((name, metric, floor, now))
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip", nargs="*", default=[],
                    choices=["accuracy", "intensity", "kernel", "roofline",
                             "paged", "model-serve"])
    ap.add_argument("--dryrun-dir", default="experiments/dryrun")
    ap.add_argument(
        "--decode-json",
        default="BENCH_decode.json",
        help="where the [PAGED] section writes its machine-readable report",
    )
    ap.add_argument(
        "--history-json",
        default="BENCH_history.json",
        help="per-PR [PAGED] history (appended, keyed by git SHA)",
    )
    ap.add_argument(
        "--full",
        action="store_true",
        help="serving-scale [PAGED] geometry (TPU)",
    )
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="tiny interpret-mode [PAGED] geometry (CI)",
    )
    ap.add_argument(
        "--interpret",
        action="store_true",
        help="CPU validation run: Pallas interpret mode (mode "
        "cpu-interpret); without it [PAGED]/[MODEL-SERVE] need a TPU",
    )
    ap.add_argument(
        "--check-regression",
        action="store_true",
        help="fail (exit 1) if [PAGED] tokens/s regressed vs the committed "
        "baseline (like-for-like tier+mode only)",
    )
    ap.add_argument("--regression-tolerance", type=float, default=0.10)
    args = ap.parse_args()

    t0 = time.time()
    if "intensity" not in args.skip:
        from benchmarks import arithmetic_intensity

        section("T2 arithmetic intensity")
        arithmetic_intensity.run()

    if "kernel" not in args.skip:
        from benchmarks import kernel_bench

        section("T5 kernel FU model")
        kernel_bench.run()

    if "accuracy" not in args.skip:
        from benchmarks import accuracy

        section("T3/T4 accuracy vs golden")
        accuracy.run()

    report = None
    if "paged" not in args.skip:
        from benchmarks import paged_decode

        section("PAGED decode scheduling (queue vs padded, shared prefix)")
        report = paged_decode.run(
            full=args.full, smoke=args.smoke, interpret=args.interpret
        )

    if "model-serve" not in args.skip:
        from benchmarks import model_serve

        section("MODEL-SERVE full-model decode (dense vs paged backend)")
        ms = model_serve.run(
            full=args.full, smoke=args.smoke, interpret=args.interpret
        )
        if report is None:  # [PAGED] skipped: still persist/gate this section
            report = {"mode": ms["mode"], "tier": ms["tier"],
                      "scenarios": {}, "prefix_scenarios": {}}
        report["model_serve"] = ms["scenarios"]

    if report is not None:
        failures = []
        if args.check_regression:
            # Gate against the *committed* baseline before overwriting it.
            failures = check_regression(
                report, args.decode_json, args.regression_tolerance
            )
            failures += check_floors(report)
        # Partial runs keep the baseline's other sections (gating integrity).
        report = merge_baseline_sections(report, args.decode_json)
        with open(args.decode_json, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"paged_decode,json,{args.decode_json}")
        append_history(report, args.history_json)
        if failures:
            names = ", ".join(f"{n}:{m}" for n, m, _, _ in failures)
            raise SystemExit(
                f"[PAGED]/[MODEL-SERVE] perf regression beyond "
                f"{100 * args.regression_tolerance:.0f}% vs "
                f"{args.decode_json}: {names}"
            )

    if "roofline" not in args.skip:
        from benchmarks import roofline_bench

        section("ROOFLINE (from dry-run)")
        roofline_bench.run(dryrun_dir=args.dryrun_dir)

    print(f"\nbenchmarks done in {time.time() - t0:.1f}s", flush=True)


if __name__ == "__main__":
    main()
