"""Run one cell of ``BENCHMARK.json`` on the TPU this process finds.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Prints the result as one JSON object on the last line of standard
output, and each compared number beside its limit as the last lines of
standard error.  Exits non-zero, and prints no result, when JAX finds no
TPU or fewer chips than the cell asks for.
"""
import time

T_PROC0 = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

if __name__ == "__main__":
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # The TPU runtime logs to a fixed /tmp path unless told otherwise:
    # keep its logs inside the checkout.
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(_root, ".chipbench_run", "tpu_logs"))
    os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)
    sys.path.insert(0, _root)
    from chipbench.harness import main
    sys.exit(main(sys.argv[1:], T_PROC0))
