"""One run of one benchmark cell on the chip.

    python3 benchmark/run.py --workload gdelt.dashboard --seed 7 --seconds 30 --trace 0

Refuses to start (exit 2, no result line) unless JAX's first device is a
TPU and there are as many chips as the cell asks for: there is no CPU
fallback (``benchmark/rehearse.py`` is the CPU rehearsal, and says so in
its line). Progress goes to stdout as one JSON object a line; the LAST
line is the result the contract fixes: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, in a traced run, ``breakdown``.
"""

import time

T_BIRTH = time.monotonic()  # process start, for setup_s

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))  # benchmark/
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))  # the program


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import cells

    cell, _, _ = cells.cell_files(cells.load_benchmark(), args.workload)
    if not os.path.isdir(os.path.join(cells.ROOT, "geomesa_tpu")):
        print("benchmark: the program (geomesa_tpu/) is not in this checkout", file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"benchmark: no TPU (JAX reports {devices[0].platform!r}); refusing to run",
              file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"benchmark: the cell asks for {cell['chips']} chips, JAX reports {len(devices)}",
              file=sys.stderr)
        return 2
    line = cells.run_cell(args.workload, args.seed, args.seconds, args.trace, T_BIRTH)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
