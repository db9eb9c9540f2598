"""Run one benchmark cell once on the chip this process finds.

    python3 benchmarks/chip/run.py --workload v2-agent-8k --seed 7 \\
        --seconds 40 --trace 0

The last line of standard output is the result: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` with ``--trace 1``),
and last ``check``, each compared number beside its limit.  Progress and
the set-up phases go to standard error.  With no TPU, or fewer chips than
the cell asks for, it exits nonzero and prints no result.
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (HERE.parents[1] / "src" / "repro").is_dir():
        raise SystemExit("the program under test (src/repro) is not in this checkout")
    import harness

    result, _ = harness.run_cell(
        args.workload, args.seed, args.seconds, bool(args.trace), T_START
    )
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
