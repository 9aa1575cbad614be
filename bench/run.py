"""Run one benchmark cell once and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of BENCHMARK.json's `workloads`; its configuration
and traffic files are found by name. Refuses to time anywhere but a
TPU with at least the chips the cell asks for: it then exits nonzero
and prints no result. The last line of standard output is one JSON
object (`correct`, `attempted`, `failed`, `metrics`, `device`, with
`--trace 1` also `breakdown`, and last the numbers the check compared
with their limits); everything else goes on earlier lines.

`--control` is for setting and proving the check's limits, never part of
a benchmark run: after the window it computes the lower-precision
control on the served sequences and compares it, in the program's
place, against the cell's limits. Such a run has to print
`"correct": false`; the program's own numbers go under `served`.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
# the TPU runtime's own log files would go to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(1, os.path.join(os.path.dirname(BENCH_DIR), "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    import harness

    _, cell, _, _, _ = harness.load_cell(args.workload)
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"[bench] refusing to time on {devs[0].platform} x{len(devs)}: the cell "
              f"needs {cell['chips']} TPU chip(s)", file=sys.stderr)
        return 2
    out = harness.run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                           t_process=T_PROCESS, control=args.control)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
