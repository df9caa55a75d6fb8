"""Run one benchmark cell on the chip this process is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

Set-up (compile, data from the seed, warm-up of the cell's own shapes) is
timed from process start to the first timed operation as ``setup_s``. The
window then runs for ``--seconds``; with ``--trace 1`` the profiler records
it and the per-layer metrics are reported instead of the end-to-end ones.
After the window the output is checked against the plain reference; each
number compared is printed beside its limit on standard error and in the
result. The last line of standard output is the result, one JSON object.

Without a TPU, with fewer chips than the cell asks for, with a
device-faking override set, or where the factor would not run the compiled
fused kernel, the command exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import harness, peaks  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the recorded .xplane.pb into this directory")
    return ap.parse_args(argv)


def main(argv=None, variant=None) -> int:
    args = parse(argv)
    try:
        line, outcome, _ = harness.run_cell(
            args.workload, seed=args.seed, seconds=args.seconds,
            trace=bool(args.trace), keep_trace=args.keep_trace,
            variant=variant, t_start=T_START)
    except (harness.Refused, peaks.UnknownDevice) as e:
        print(f"refused: {e}", file=sys.stderr, flush=True)
        return 2
    harness.print_checks(outcome)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
