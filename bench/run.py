"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

See ``bench/harness.py``.  Exits non-zero, with no result line, where JAX
finds no TPU or fewer chips than the cell asks for, or where the program
(``src/repro``) is not in the checkout.
"""

import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main() -> int:
    if not (ROOT / "src" / "repro").is_dir():
        print("[bench] error: the program (src/repro) is not in this "
              "checkout; no result", file=sys.stderr)
        return 4
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness

    return harness.main(t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
