"""A cell with one stated guarantee broken, on the chip at the cell's own
size: the comparison that decides ``correct`` has to fail.

    python3 benchmark/control.py --workload gdelt.analyst --control loose --seed 11 --seed 12

Controls are in ``harness/controls.py``; ``--control none`` arms nothing
(sound windows on many seeds over one store, which have to come out
correct). Exits 0 when every window came out as it should: NOT correct
under a control. The benchmark's own runs never arm a control.
"""

import time

T_BIRTH = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--control", required=True)
    ap.add_argument("--seed", type=int, required=True, action="append",
                    help="the first makes the data; each gives one window of the mix")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("control: no TPU; the CPU form is benchmark/tests/test_run_cell.py", file=sys.stderr)
        return 2
    from harness import cells

    sound = args.control == "none"
    run = cells.prepare(args.workload, args.seed[0])
    as_expected = True
    try:
        for seed in args.seed:  # set-up is long: one store, a window of the mix for each seed
            line = cells.measure(run, seed, args.seconds, 0, T_BIRTH,
                                 control=None if sound else args.control)
            print(json.dumps({"control": args.control, "seed": seed, **line}), flush=True)
            as_expected &= line["correct"] is sound
    finally:
        run["store"].close()
    return 0 if as_expected else 1


if __name__ == "__main__":
    sys.exit(main())
