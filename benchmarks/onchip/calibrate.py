#!/usr/bin/env python3
"""Read the two ends of a cell's correctness limit on the chip, in one
process: for each seed, a run at the cell's own load (the timed path, a
short window) and its widest logit gap against the f32 reference (the
lower reading), and the control on the same prompts and served tokens: the
reference computed in fp8, read by the gap of the token it puts first (the
upper reading).

    python3 benchmarks/onchip/calibrate.py --workload <cell> \\
        --seeds 11,12,13 --seconds 20 [--control-seeds 3]

Prints one JSON line per seed. Not part of a benchmark run: the limits in
the mix files are set from what it prints.
"""
import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)

    import numpy as np

    import harness

    cell = harness.find_cell(harness.load_bench(), args.workload)
    devices = harness.check_devices(cell.chips)
    harness.use_compile_cache()
    peaks = harness.peaks_for(devices[0].device_kind)
    cap = cell.mix["engine"]["request_capacity"]
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        keep = {}
        t0 = time.time()
        out = harness.run(cell, seed, args.seconds, False, t0,
                          devices=devices, peaks=peaks, keep=keep)
        row = {"seed": seed, "correct": harness.is_correct(out.checks),
               "program_gap": out.checks["max_logit_gap"]["value"],
               "served": out.checks["served_tokens_compared"]["value"],
               "requests": len(keep["sample"]),
               "preempted": sum(t.req.preemptions > 0
                                for t in keep["sample"]),
               "failed": out.failed}
        if i < args.control_seeds:
            pairs = [(list(t.req.prompt), list(t.req.generated))
                     for t in keep["sample"]]
            ref = harness.reference_of(cell)
            ctl = ref.control_gaps(keep["params"], keep["m"], cap, pairs)
            g = np.concatenate(ctl)
            row.update(control_gap=float(g.max()),
                       control_gap_p50=float(np.median(g)),
                       control_flips=int((g > 0).sum()))
        row["seconds"] = time.time() - t0
        print(json.dumps(row), flush=True)
        del keep, out
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
