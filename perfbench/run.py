"""The on-chip benchmark of VELOC-JAX.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the
workload asks for.  The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, and last
``checks``: each number compared beside its limit).  Without a TPU it
exits non-zero and prints no result.
"""
import sys
import time

T_START = time.monotonic()

from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))                 # the harness
sys.path.insert(0, str(HERE.parent / "src"))  # the program under test

from harness import cli  # noqa: E402

if __name__ == "__main__":
    sys.exit(cli.main(sys.argv[1:], t_start=T_START))
