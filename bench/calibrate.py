"""Readings that the limits of ``correct`` are set from, at a cell's own size.

  python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
      [--fault-seeds 4,5,6] [--out readings.jsonl]

For every seed, in one process: the program's checked rounds against
the reference (the lower readings), and the control, the reference
computed in bfloat16 at default precision put in the program's place
(its upper readings).  For every fault seed, the program with each
planted fault (``harness.world.FAULTS``) against the reference.  One
JSON line per reading.  The benchmark's own runs never run this."""
import argparse
import gc
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from harness import cell, check, spec, world  # noqa: E402


def reading(cfg, ref, traffic, seed, fault=None, control=False):
    """The readings of one seed: the program's (with ``fault`` planted)
    and, with ``control``, the bfloat16 reference's in its place."""
    from repro.core import scheduling as S

    wd = world.build(cfg, ref, traffic, seed, fault=fault)
    with check.P1Recorder(S, flip=(fault == "flip_mask")) as p1:
        checked, prog = cell.checked_rounds(wd, p1)
    w0 = jax.device_get(wd.weights)
    inputs, labels = wd.inputs, wd.labels
    del wd
    gc.collect()
    refs = cell.reference_rounds(ref, cfg, traffic, w0, inputs, labels,
                                 checked, p1.solved)
    out = [dict(cell.gaps(w0, prog, refs), seed=seed, fault=fault,
                side="program",
                mask_mismatches=check.mask_mismatches(p1.solved))]
    if control:
        ctrl = cell.reference_rounds(ref, cfg, traffic, w0, inputs, labels,
                                     checked, p1.solved, dtype=jnp.bfloat16,
                                     precision=jax.lax.Precision.DEFAULT)
        per_round = [[c[i] for c in ctrl] for i in range(len(ctrl[0]))]
        out.append(dict(cell.gaps(w0, per_round, refs), seed=seed,
                        fault=None, side="control"))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    bench = spec.load_benchmark()
    w = spec.find_workload(bench, args.workload)
    cfg, ref = spec.load_config(w["config"])
    traffic = spec.load_traffic(w["traffic"])
    device = cell.device_info(w["chips"], require_chip=True)
    cell.enable_cache()
    out = open(args.out, "a") if args.out else None
    seeds = [int(s) for s in args.seeds.split(",") if s]
    fseeds = [int(s) for s in args.fault_seeds.split(",") if s]
    jobs = [(s, None) for s in seeds] + [(s, f) for s in fseeds
                                         for f in world.FAULTS[1:]]
    for seed, fault in jobs:
        t0 = time.perf_counter()
        for r in reading(cfg, ref, traffic, seed, fault,
                         control=fault is None):
            r.update(workload=args.workload, device=device["kind"],
                     seconds=time.perf_counter() - t0)
            line = json.dumps(r)
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
