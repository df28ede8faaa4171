"""Readings of the control and of the faults planted in the reference,
for setting a cell's limits (not run by the benchmark's own runs).

    python chipbench/control.py --workload <cell> --seeds 1 2 3

For each seed, the reference in float32 is the truth; beside it run the
reference in fp8 (the precision below the configuration's bfloat16) and
the reference with half of the batch left out.  Each is compared with
the truth by the numbers the cell compares and held to the cell's own
limits (``verdict``: ``correct`` and the numbers over their limits), and
printed as one JSON line.  A state left unchanged reads 1 on
``update_gap`` by definition.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time


def readings(bench, cell_name, seed, variants=("fp8", "half_batch")):
    from chipbench import check, reference
    from chipbench.data import DataConfig, SyntheticTokens
    cell = bench.cell(cell_name)
    config, traffic = bench.config(cell), bench.traffic(cell)
    arch = reference.Arch.from_config(config)
    adam = reference.Adam(**traffic["optimizer"])
    seed = seed % (1 << 32)
    n = traffic["checked_steps"]
    steps = traffic["save_at"] + 2 if traffic["mix"] == "resume" else n
    data = SyntheticTokens(DataConfig(config["vocab_size"],
                                      traffic["seq_len"],
                                      traffic["global_batch"], seed))
    batches = [data.global_batch_shard(s, 0, traffic["global_batch"])
               for s in range(steps)]
    truth = reference.train(arch, adam, seed, batches, change_after=n)
    # A resume cell compares the set-up's steps 0..save_at, then the first
    # step after each resume; a train cell its checked steps.
    m = steps - 1 if traffic["mix"] == "resume" else n
    floor = statistics.median(truth["grad_norms"].values())
    limits = bench.limits(cell)
    out = {"negligible_leaves": sorted(
        k for k, x in truth["grad_norms"].items()
        if x < check.NEGLIGIBLE_GRAD * floor)}
    for v in variants:
        kw = {"precision": "fp8"} if v == "fp8" else {"fault": v}
        got = reference.train(arch, adam, seed, batches, change_after=n, **kw)
        g = check.gaps({**got, "losses": got["losses"][:m]},
                       {**truth, "losses": truth["losses"][:m]})
        if traffic["mix"] == "resume":
            g["resume_loss_gap"] = check.loss_gap([got["losses"][-1]],
                                                  truth["losses"][-1])
        checks = check.verdict(g, limits)
        g["verdict"] = {"correct": check.passes(checks),
                        "over": sorted(k for k, (x, lim) in checks.items()
                                       if not x <= lim)}
        # Per step, so that a cell checking fewer steps of the same
        # batches reads its loss_gap from this run too.
        g["loss_gap_per_step"] = [abs(a - b) / abs(b) for a, b in
                                  zip(got["losses"], truth["losses"])]
        g["update_gap_worst"] = check.details(got, truth)["update_gap"]
        g["grad_gap_worst"] = check.details(got, truth)["grad_gap"]
        out[v] = g
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variants", nargs="+", default=["fp8", "half_batch"],
                    choices=["fp8", "half_batch"])
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(root, ".chipbench_run", "tpu_logs"))
    os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)
    sys.path.insert(0, root)
    sys.path.insert(0, os.path.join(root, "src"))
    from chipbench import harness
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    bench = harness.Bench()
    # The reference runs on one device at the cell's widths and batch
    # (rows in blocks), so one chip reads the control of any cell.
    harness.require_chips(1)
    for seed in args.seeds:
        t = time.perf_counter()
        r = readings(bench, args.workload, seed, tuple(args.variants))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "seconds": time.perf_counter() - t, **r}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
