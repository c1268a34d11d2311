import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (brief §MULTI-POD DRY-RUN).

For every (architecture x input shape) lower + compile the appropriate
step (train_step / prefill_step / serve_step) against the production mesh
(16x16 single-pod, 2x16x16 multi-pod) using ShapeDtypeStruct inputs only,
then record memory_analysis / cost_analysis / collective bytes for the
roofline (EXPERIMENTS.md §Dry-run, §Roofline).

Cost accounting: XLA's HloCostAnalysis counts while-loop bodies ONCE, so
the scanned-layer program under-reports FLOPs/bytes/collectives by the
scan trip count.  The dry-run therefore also compiles two small
*unrolled* variants (1 and 2 pattern periods, straight-line HLO) and
linearly extrapolates the exact totals:
    metric(P) = out_of_loop + P * per_period
Memory analysis always comes from the REAL (scanned) executable.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b \
      --shape train_4k [--multi-pod] [--out experiments/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --all
"""
import argparse       # noqa: E402
import dataclasses    # noqa: E402
import json           # noqa: E402
import time           # noqa: E402
import traceback      # noqa: E402

import jax            # noqa: E402

from repro.configs import get_config, list_archs                 # noqa: E402
from repro.fl.distributed import (make_prefill_step,             # noqa: E402
                                  make_serve_step, make_train_step)
from repro.launch.mesh import (PRODUCTION_DEVICE_KIND,          # noqa: E402
                               make_production_mesh)
from repro.launch.shapes import SHAPES, applicable_shapes, CACHE_PAD  # noqa: E402
from repro.launch import shardings as SH                         # noqa: E402
from repro.roofline import (collective_bytes_from_hlo,           # noqa: E402
                            model_flops, roofline_terms)


def _compile(cfg, shape, mesh, multi_pod: bool, opt: bool = False):
    ctx = SH.make_ctx(cfg, mesh, shape, opt=opt)
    params_spec = SH.param_specs(cfg)
    params_sh = SH.param_shardings(params_spec, cfg, ctx)
    batch_spec = SH.input_specs(cfg, shape, federated=multi_pod
                                and shape.kind == "train")
    batch_sh = SH.batch_shardings(batch_spec, ctx)
    with mesh:
        if shape.kind == "train":
            step = make_train_step(cfg, ctx, federated=multi_pod)
            lowered = jax.jit(
                step, in_shardings=(params_sh, batch_sh),
                out_shardings=(params_sh, None),
            ).lower(params_spec, batch_spec)
        elif shape.kind == "prefill":
            step = make_prefill_step(cfg, ctx, shape.seq_len + CACHE_PAD)
            cache_spec = SH.cache_specs(cfg, shape)
            cache_sh = SH.cache_shardings(cache_spec, cfg, ctx)
            lowered = jax.jit(
                step, in_shardings=(params_sh, batch_sh),
                out_shardings=(None, cache_sh),
            ).lower(params_spec, batch_spec)
        else:
            step = make_serve_step(cfg, ctx)
            cache_spec = SH.cache_specs(cfg, shape)
            cache_sh = SH.cache_shardings(cache_spec, cfg, ctx)
            lowered = jax.jit(
                step, in_shardings=(params_sh, cache_sh, batch_sh),
                out_shardings=(None, cache_sh),
            ).lower(params_spec, cache_spec, batch_spec)
        compiled = lowered.compile()
    return compiled, ctx


def _metrics(compiled):
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes_from_hlo(hlo)
    coll_bytes = sum(v for k, v in coll.items() if k != "counts")
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": coll_bytes,
        "collectives": coll,
    }


def counting_pass(cfg, shape, mesh, multi_pod: bool, opt: bool = False):
    """Exact per-device totals via the P=1 / P=2 unrolled fit."""
    period = cfg.pattern_period
    rem = cfg.num_remainder_layers
    p_true = cfg.num_full_periods
    # P=2 vs P=4: GSPMD occasionally makes different global choices for a
    # 1-layer program, which made a (1,2) fit non-monotone; (2,4) is
    # stable, and per-period deltas are clamped at >= 0
    pa, pb = (2, 4) if p_true >= 2 else (1, 2)
    m = []
    for p in (pa, pb):
        c = dataclasses.replace(cfg, num_layers=p * period + rem,
                                unroll_for_costing=True)
        compiled, _ = _compile(c, shape, mesh, multi_pod, opt)
        m.append(_metrics(compiled))
    out = {}
    for key in ("flops", "bytes", "collective_bytes"):
        per_period = max((m[1][key] - m[0][key]) / (pb - pa), 0.0)
        out[key] = max(m[0][key] + (p_true - pa) * per_period, m[0][key])
        out[key + "_per_period"] = per_period
    out["collectives_p1"] = m[0]["collectives"]
    return out


def lower_and_compile(arch: str, shape_name: str, multi_pod: bool,
                      include_hlo: bool = False, counting: bool = True,
                      opt: bool = False):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size

    t0 = time.time()
    compiled, ctx = _compile(cfg, shape, mesh, multi_pod, opt)
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    raw = _metrics(compiled)
    corrected = counting_pass(cfg, shape, mesh, multi_pod, opt) if counting \
        else dict(raw)

    mflops = model_flops(cfg, shape, shape.kind)
    roof = roofline_terms(
        flops_per_device=corrected["flops"],
        bytes_per_device=corrected["bytes"],
        collective_bytes_per_device=corrected["collective_bytes"],
        chips=chips,
        device_kind=PRODUCTION_DEVICE_KIND,
        model_flops=mflops,
    )

    def g(attr):
        return getattr(mem, attr, 0) or 0

    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "kind": shape.kind,
        "tp_mode": ctx.tp,
        "compile_s": round(t_compile, 1),
        "memory_analysis": {
            "argument_size_bytes": g("argument_size_in_bytes"),
            "output_size_bytes": g("output_size_in_bytes"),
            "temp_size_bytes": g("temp_size_in_bytes"),
            "alias_size_bytes": g("alias_size_in_bytes"),
            "peak_bytes_per_device": (
                g("argument_size_in_bytes") + g("temp_size_in_bytes")
                + g("output_size_in_bytes") - g("alias_size_in_bytes")),
        },
        "cost_analysis_raw": {k: raw[k] for k in
                              ("flops", "bytes", "collective_bytes")},
        "cost_analysis_corrected": {
            k: corrected[k] for k in corrected if not k.startswith("coll")},
        "collectives_raw": raw["collectives"],
        "roofline": roof.to_dict(),
    }
    if include_hlo:
        result["hlo"] = compiled.as_text()
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--no-counting", action="store_true")
    ap.add_argument("--opt", action="store_true",
                    help="beyond-paper optimized sharding (§Perf)")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    jobs = []
    if args.all:
        for arch in list_archs():
            cfg = get_config(arch)
            for shape in applicable_shapes(cfg):
                for mp in (False, True):
                    jobs.append((arch, shape.name, mp))
    else:
        jobs.append((args.arch, args.shape, args.multi_pod))

    failures = 0
    for arch, shape_name, mp in jobs:
        tag = f"{arch}_{shape_name}_{'2x16x16' if mp else '16x16'}"
        path = os.path.join(args.out, tag + ".json")
        if args.skip_existing and os.path.exists(path):
            print(f"[skip] {tag}", flush=True)
            continue
        try:
            res = lower_and_compile(arch, shape_name, mp,
                                    counting=not args.no_counting,
                                    opt=args.opt)
            with open(path, "w") as f:
                json.dump(res, f, indent=2)
            r = res["roofline"]
            print(f"[ok]  {tag}: compile={res['compile_s']}s "
                  f"bottleneck={r['bottleneck']} "
                  f"compute={r['compute_s']:.2e}s "
                  f"memory={r['memory_s']:.2e}s "
                  f"collective={r['collective_s']:.2e}s "
                  f"flops_ratio={r['flops_ratio']:.2f} "
                  f"peak_mem={res['memory_analysis']['peak_bytes_per_device']/2**30:.2f}GiB",
                  flush=True)
        except Exception as e:
            failures += 1
            print(f"[FAIL] {tag}: {type(e).__name__}: {e}", flush=True)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{failures} dry-run failures")
    print("dry-run complete")


if __name__ == "__main__":
    main()
