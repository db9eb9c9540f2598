"""Readings that a cell's check limit is set from, on the chip.

    python3 benchmarks/chip/control.py --workload v2-agent-8k \\
        --seeds 101,102,103 --seconds 20

For each seed, one run of the cell as ``run.py`` makes it (at the cell's
load and lead, with the given window), then the check on its sample twice:
the program's served tokens against the float32 reference
(``logit_gap_max``: the lower reading), and the int8 control -- the same
reference with every matmul's operands rounded to int8 -- at the same
positions (``control_gap_max``: the upper reading).  The control's reading
goes through the run's own comparison in the program's place
(``control_correct``, false where the limit catches it).  All seeds run in
this one process.  One JSON line per seed.
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


def control_verdict(readings: dict, limits: dict) -> tuple[dict, bool]:
    """The run's comparison with the control's gap in the program's place."""
    import harness

    return harness.judge({**readings, "logit_gap_max": readings["control_gap_max"]}, limits)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    import harness

    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        result, extra = harness.run_cell(
            args.workload, seed, args.seconds, False, t0, harness.Options(control=True)
        )
        check, control_correct = control_verdict(extra["readings"], extra["limits"])
        print(json.dumps({
            "seed": seed,
            "correct": result["correct"],
            "control_correct": control_correct,
            **extra["readings"],
            "control_check": check,
            "metrics": {k: v["value"] for k, v in result["metrics"].items()},
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
