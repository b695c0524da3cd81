import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this lowers the REAL step function (train_step with optimizer
update, or prefill/decode serve steps) with ShapeDtypeStruct stand-ins — no
array allocation — onto the production mesh, compiles it through the XLA
SPMD partitioner, and records memory_analysis / cost_analysis / collective
bytes (parsed from the HLO) into experiments/dryrun/*.json for the roofline
report.

Usage:
  python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
  python -m repro.launch.dryrun --arch all --shape all            # single-pod
  python -m repro.launch.dryrun --arch all --shape all --multi-pod
"""
import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, SHAPES, get_arch, get_shape, shape_applicable
from repro.launch.mesh import make_production_mesh
from repro.models.api import build_model
from repro.optim.adamw import AdamWConfig
from repro.runtime import steps as S
from repro.utils import hlo_analysis as H
from repro.utils import analytic_cost as AC

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def _mesh_tag(multi_pod: bool) -> str:
    return "pod2x16x16" if multi_pod else "pod16x16"


def lower_cell(arch_name: str, shape_name: str, *, multi_pod: bool,
               act_rules=None, param_rules=None, extra_tag: str = "",
               cache_quant: bool = False, sharded_logits: bool = False):
    """Returns (lowered, compiled, meta dict)."""
    cfg = get_arch(arch_name)
    shape = get_shape(shape_name)
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return None, None, {"skipped": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    api = build_model(cfg, max_seq=shape.seq_len, cache_quant=cache_quant)
    opt_cfg = AdamWConfig()
    abstract = S.abstract_inputs(api, shape)

    t0 = time.time()
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            step = S.make_train_step(api, mesh, opt_cfg, shape,
                                     act_rules=act_rules,
                                     param_rules=param_rules)
            lowered = step.lower(abstract["params"], abstract["opt"],
                                 abstract["batch"], jax.ShapeDtypeStruct((), jnp.int32))
        elif shape.kind == "prefill":
            step = S.make_prefill_step(api, mesh, shape, act_rules=act_rules,
                                       param_rules=param_rules,
                                       sharded_logits=sharded_logits)
            lowered = step.lower(abstract["params"], abstract["batch"])
        else:  # decode
            step = S.make_decode_step(api, mesh, shape, act_rules=act_rules,
                                      param_rules=param_rules,
                                      sharded_logits=sharded_logits)
            lowered = step.lower(abstract["params"], abstract["cache"],
                                 abstract["batch"])
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    meta = {"arch": arch_name, "shape": shape_name,
            "mesh": _mesh_tag(multi_pod), "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1), "tag": extra_tag,
            "cache_quant": cache_quant, "sharded_logits": sharded_logits}
    return lowered, compiled, meta


def analyze(lowered, compiled, meta, cfg, shape, chips: int):
    mem = compiled.memory_analysis()
    raw_cost = H.cost_summary(compiled)       # scan bodies counted ONCE (XLA)
    hlo = compiled.as_text()
    coll = H.collective_bytes(hlo)            # trip-count-aware walk
    coll_total = sum(v for k, v in coll.items() if not k.startswith("_"))

    est = AC.estimate(cfg, shape,
                      cache_bytes=1 if meta.get("cache_quant") else 2,
                      state_bytes=2 if meta.get("cache_quant") else 4)
    roof = H.Roofline(est.flops, est.hbm_bytes, coll_total, chips)

    rec = dict(meta)
    rec.update({
        # memory_analysis reports PER-DEVICE sizes for the SPMD-partitioned
        # executable (verified: command-r decode args = cache+param shard).
        "memory": {
            "argument_gb": mem.argument_size_in_bytes / 1e9,
            "output_gb": mem.output_size_in_bytes / 1e9,
            "temp_gb": mem.temp_size_in_bytes / 1e9,
            "alias_gb": mem.alias_size_in_bytes / 1e9,
            "per_device_gb": (mem.argument_size_in_bytes
                              + mem.temp_size_in_bytes
                              + mem.output_size_in_bytes
                              - mem.alias_size_in_bytes) / 1e9,
        },
        "raw_cost_analysis": raw_cost,
        "collectives": coll,
        "roofline": roof.as_dict(),
        "model_flops": est.model_flops,
        "useful_flops_ratio": est.useful_ratio,
        "tokens": shape.tokens,
        "hlo_lines": len(hlo.splitlines()),
    })
    return rec


def run_cell(arch_name: str, shape_name: str, *, multi_pod: bool,
             save: bool = True, act_rules=None, param_rules=None,
             tag: str = "", cache_quant: bool = False,
             sharded_logits: bool = False) -> dict:
    cfg = get_arch(arch_name)
    shape = get_shape(shape_name)
    chips = 512 if multi_pod else 256
    try:
        lowered, compiled, meta = lower_cell(
            arch_name, shape_name, multi_pod=multi_pod,
            act_rules=act_rules, param_rules=param_rules, extra_tag=tag,
            cache_quant=cache_quant, sharded_logits=sharded_logits)
        if lowered is None:
            rec = {"arch": arch_name, "shape": shape_name,
                   "mesh": _mesh_tag(multi_pod), **meta}
        else:
            rec = analyze(lowered, compiled, meta, cfg, shape, chips)
    except Exception as e:  # record failures: they are bugs to fix
        rec = {"arch": arch_name, "shape": shape_name,
               "mesh": _mesh_tag(multi_pod), "error": str(e),
               "trace": traceback.format_exc()[-2000:]}
    if save:
        os.makedirs(OUT_DIR, exist_ok=True)
        suffix = f"_{tag}" if tag else ""
        fn = f"{arch_name.replace('.', '_')}__{shape_name}__{_mesh_tag(multi_pod)}{suffix}.json"
        with open(os.path.join(OUT_DIR, fn), "w") as f:
            json.dump(rec, f, indent=1, default=float)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--cache-quant", action="store_true")
    ap.add_argument("--sharded-logits", action="store_true")
    ap.add_argument("--sp", action="store_true",
                    help="sequence-parallel residual stream (train)")
    args = ap.parse_args(argv)

    archs = sorted(ARCHS) if args.arch == "all" else [args.arch]
    shapes = sorted(SHAPES) if args.shape == "all" else [args.shape]

    failures = 0
    for a in archs:
        for s in shapes:
            t0 = time.time()
            from repro.sharding import rules as RR
            act = RR.SP_ACT_RULES if args.sp else None
            rec = run_cell(a, s, multi_pod=args.multi_pod, tag=args.tag,
                           cache_quant=args.cache_quant,
                           sharded_logits=args.sharded_logits,
                           act_rules=act)
            dt = time.time() - t0
            if "error" in rec:
                failures += 1
                print(f"FAIL {a:24s} {s:12s} {rec['mesh']}: {rec['error'][:120]}",
                      flush=True)
            elif "skipped" in rec:
                print(f"skip {a:24s} {s:12s}: {rec['skipped'][:80]}", flush=True)
            else:
                r = rec["roofline"]
                print(f"ok   {a:24s} {s:12s} {rec['mesh']} "
                      f"[{dt:5.1f}s] dom={r['dominant']:10s} "
                      f"comp={r['compute_s']:.2e}s mem={r['memory_s']:.2e}s "
                      f"coll={r['collective_s']:.2e}s "
                      f"useful={rec['useful_flops_ratio']:.2f} "
                      f"dev_gb={rec['memory']['per_device_gb']:.2f}", flush=True)
    if failures:
        print(f"{failures} FAILURES", flush=True)
        sys.exit(1)


if __name__ == "__main__":
    main()
