#!/usr/bin/env python3
"""Run one cell of the on-chip serving benchmark and print its result.

    python3 benchmarks/onchip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

One process, one cell, one run: set-up (weights drawn from the seed on the
chip, the engine built and warmed, set-up traffic), a measured window of
``--seconds`` seconds, then the check of the served tokens against the
plain f32 reference. With ``--trace 1`` the last part of the window is
traced and the cell's per-layer metrics are printed instead of its
end-to-end ones. The last line of standard output is one JSON object; the
numbers compared, with their limits, are the last lines of standard error.
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)

    import harness
    bench = harness.load_bench()
    cell = harness.find_cell(bench, args.workload)
    try:
        devices = harness.check_devices(cell.chips)
    except harness.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    dev = devices[0]
    print(f"device: {dev.platform} {dev.device_kind} x {len(devices)}",
          file=sys.stderr, flush=True)
    out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      T_PROCESS, devices=devices,
                      peaks=harness.peaks_for(dev.device_kind))
    line = harness.result(cell, out, bool(args.trace), devices)
    for name, c in out.checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
