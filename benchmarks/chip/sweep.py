"""Find a cell's knee: the highest session rate whose backlog does not grow.

    python3 benchmarks/chip/sweep.py --workload v2-agent-8k --seed 11 \\
        --rates 0.3,0.5,0.7,0.9 --seconds 60

One process, one set-up (weights, session, prefix fill), then the
cell's traffic at each rate in turn, each from an empty session, for
``--seconds`` of sending plus a drain.  For each rate it prints one JSON
line: tokens/s offered and delivered in the second half, the backlog at
half time and at the end (requests due and not yet admitted, plus those
admitted whose prompt has not finished its prefill), and the p90 time to
first token of the second half.  The knee is the highest rate whose
backlog does not grow from half time to the end; the cell runs at about
four fifths of it, written into its file.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=60.0)
    args = ap.parse_args(argv)

    import jax

    import harness
    import traffic as traffic_mod
    from repro.models.model_zoo import build_model
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("no accelerator")
    cell = harness.load_json("workloads", args.workload)
    conf = harness.load_json("configs", cell["config"])
    conf["name"] = cell["config"]
    mix = traffic_mod.load_mix(cell["traffic"])
    family = harness.load_module("families", conf["family"])
    model = build_model(family.program_config(conf))
    params = family.make_params(model, conf, args.seed)
    sess = harness.build_session(model, params, conf, mix, interpret=False)
    vocab = conf["vocab_size"]
    sys_prompt = traffic_mod.system_prompt(mix, args.seed, vocab)
    harness.fill_prefix(sess, sys_prompt)
    harness.log(f"set-up {time.monotonic() - T_START:.1f}s")
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        plan = traffic_mod.plan_sessions(mix, rate, args.seconds)
        drv = harness.OpenLoop(sess, mix, plan, args.seed, vocab, sys_prompt,
                             time.monotonic(), stream=(i + 1) << 20)
        half = args.seconds / 2
        drv.run(until=half, send_until=args.seconds)
        q_half = len(drv.queue) + sess.prefill_pending()
        drv.run(until=args.seconds, send_until=args.seconds)
        q_end = len(drv.queue) + sess.prefill_pending()
        offered = sum(
            t.output_tokens for p in plan for t in p.turns
        ) / max(len(plan), 1) * rate
        toks = sum(half <= t < args.seconds for r in drv.requests for t in r.times)
        done = sum(half <= (r.done or -1) < args.seconds for r in drv.requests)
        drv.run(until=args.seconds + 120, send_until=args.seconds, drain_from=0.0)
        late = [r for r in drv.requests if half <= r.due < args.seconds]
        ttft = [((r.times[0] if r.times else args.seconds + 120) - r.due) * 1e3
                for r in late]
        for rid in list(drv.live):  # leave an empty session for the next rate
            sess.finish(rid)
        print(json.dumps({
            "rate": rate,
            "offered_tok_s": offered,
            "out_tok_s": toks / half,
            "requests_done_per_s": done / half,
            "backlog_half": q_half,
            "backlog_end": q_end,
            "live_batch_mean": sum(len(r.times) for r in drv.requests) / max(len(drv.steps), 1),
            "ttft_p90_ms": harness.percentile(ttft, 90) if ttft else None,
            "requests_second_half": len(late),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
