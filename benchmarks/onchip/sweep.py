#!/usr/bin/env python3
"""Find a cell's knee: serve its mix at rising rates in one process and
print what each rate gave.

    python3 benchmarks/onchip/sweep.py --workload <cell> --rates 1,2,3 \\
        --seconds 50 [--seed n]

Each rate gets a fresh engine (built from the same weights and warmed),
the mix's warm start and lead-in, then a window of ``--seconds``. A rate
is sustained when the window is in a steady state: the output tokens
served are at least ``--served`` of those the mix offers (rate x mean
reply), and the median TTFT of its second half is at most ``--growth`` times
that of its first half or than ``TTFT_FLOOR_MS``, whichever is larger (a
few requests a half differ that much by their prompts alone: the longest
prompt's own prefill takes 8 chunk steps of about 0.25 s). The sweep
stops after the first rate that is not
sustained; the knee is the highest sustained rate. Each row also gives
the life of the requests that finished (due to last token), which the
lead-in has to cover. Not part of a benchmark run: it fixes the rate
written into the mix file once, when the cell is defined.
"""
import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

TTFT_FLOOR_MS = 2000.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--served", type=float, default=0.9)
    ap.add_argument("--growth", type=float, default=1.5)
    args = ap.parse_args(argv)

    import numpy as np

    import harness
    import traffic as traffic_gen

    cell = harness.find_cell(harness.load_bench(), args.workload)
    devices = harness.check_devices(cell.chips)
    harness.use_compile_cache()
    log = lambda s: print(s, file=sys.stderr, flush=True)  # noqa: E731
    m = harness.model_dims(cell.config)
    mesh = harness.serving_mesh(cell, devices)
    params = harness.weights_of(cell).init(m, args.seed, mesh=mesh)
    peaks = harness.peaks_for(devices[0].device_kind)
    lead_in = cell.mix.get("lead_in_s", 0.0)
    knee = None
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        eng = harness.build_engine(cell, params, m, mesh)
        eng.warmup()
        mix = dict(cell.mix, arrivals=dict(cell.mix["arrivals"],
                                           rate_per_s=rate))
        tr_gen = traffic_gen.generate(mix, args.seed + i, args.seconds,
                                      m["vocab_size"])
        win = harness.Window(eng, cell, m)
        delta = harness.serve_window(win, tr_gen, args.seconds, lead_in,
                                     None, 0, log)
        run = harness.Run(cell.name, args.seconds, 0.0, win.tracks, delta,
                          win.flops, win.bytes, peaks, chips=len(devices))
        row = {"rate_per_s": rate, "warm": len(tr_gen.warm),
               "due": sum(t.in_window for t in win.tracks)}
        row.update(harness.steadiness(run, tr_gen))
        row["served_share"] = row["served_tok_s"] / row["offered_tok_s"]
        for name in ("ttft_p50_ms", "itl_p50_ms", "itl_p99_ms",
                     "queue_wait_p90_ms", "decode_step_ms", "step_mfu",
                     "step_hbm_share"):
            row[name] = harness.reader(name)(run)
        life = [t.times[-1] - t.due for t in win.tracks
                if t.req.status == "done" and t.due >= -lead_in + 1e-9
                and t.times]
        if life:
            row["life_s_p50"] = float(np.median(life))
            row["life_s_mean"] = float(np.mean(life))
            row["life_s_p90"] = float(np.percentile(life, 90))
        steps = delta["decode_steps"]
        row["step_s"] = args.seconds / steps if steps else None
        row["rows_per_step"] = run.tokens / steps if steps else None
        row["sustained"] = bool(
            row["served_share"] >= args.served
            and row["ttft_p50_ms_second_half"]
            <= args.growth * max(row["ttft_p50_ms_first_half"],
                                 TTFT_FLOOR_MS))
        print(json.dumps(row), flush=True)
        del win, eng
        gc.collect()
        log(f"bytes in use after rate {rate}: "
            f"{devices[0].memory_stats().get('bytes_in_use')}")
        if not row["sustained"]:
            break
        knee = rate
    print(json.dumps({"knee_rate_per_s": knee,
                      "device": devices[0].device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
