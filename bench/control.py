"""The control of a cell's correctness check: the same run with the
program's lower-precision path switched on (bfloat16 storage of the factor
where the configuration states float32). Its result must read
``"correct": false``; the smallest value it gives of each compared number
over several seeds is that number's upper reading.

    python3 bench/control.py --workload <cell> --seed <n> --seconds <s>

The benchmark's own runs never run this.
"""
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

from bench import run  # noqa: E402

if __name__ == "__main__":
    sys.exit(run.main(variant="control"))
