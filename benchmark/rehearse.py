"""The CPU rehearsals of the on-chip-measurement guide, for every cell.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py [--workload NAME] [--rows 65536]

1. each cell end to end at a tiny size, untraced and traced, through the
   same ``run_cell`` the chip run uses (only the look for the chip is
   skipped); the last line of each has the contract's form with
   ``"rehearsal": true`` and its numbers under ``rehearsal_metrics``, never
   under ``metrics``: a CPU run yields no time;
2. paths that span chips: none of this benchmark's cells has one;
3. compiles at the real size for a described v5e: the repository's
   ``tests/test_chip_compile.py`` holds them (34 kernels); nothing to add.
"""

import time

T_BIRTH = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

os.environ.setdefault("JAX_PLATFORMS", "cpu")  # this script only; run.py has no such line
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append")
    ap.add_argument("--rows", type=int, default=1 << 16)
    ap.add_argument("--seed", type=int, default=2_500_000_011)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), action="append")
    ap.add_argument("--control")
    args = ap.parse_args(argv)

    from harness import cells

    bench = cells.load_benchmark()
    ok = True
    for name in args.workload or [w["name"] for w in bench["workloads"]]:
        for trace in args.trace or (0, 1):
            line = cells.run_cell(name, args.seed, args.seconds, trace, time.monotonic(),
                                  rows=args.rows, control=args.control, trace_s=1.0)
            line["rehearsal"] = True
            line["rehearsal_metrics"] = line.pop("metrics")
            line["metrics"] = {}
            ok &= line["correct"]
            print(json.dumps({"workload": name, "trace": trace, **line}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
