#!/usr/bin/env python3
"""Readings that set a cell's correctness limits from above (run on the chip).

    python benchmarks/chip/calibrate.py --workload train.danube.m1 --seeds 1,2,3 \
        --variants fp8,half_batch

For each seed, the reference follows the cell's first window at the
cell's size, then once for each variant put in the program's place: the
float8 control (``fp8``) or a planted fault (``half_batch``,
``no_exchange``).  Each variant's numbers against the reference are
printed as one JSON line; a limit lies below the smallest of them.  The
lower readings come from the benchmark's own runs (``run.py`` prints its
numbers on every run).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != HERE]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--variants", default="fp8,half_batch")
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(), "tpu_logs"))
    import jax

    from benchmarks.chip import compare, run

    run.enable_cache()
    cell = run.load_cell(args.workload)
    devices = jax.devices()[:cell.chips]
    ref_mod = run.reference_module(cell.config)
    for seed in map(int, args.seeds.split(",")):
        t = time.perf_counter()
        ref = ref_mod.run(cell.config, cell.traffic, seed, devices=devices,
                          keep_moments=True)
        print(f"seed {seed}: reference {time.perf_counter() - t:.2f} s", file=sys.stderr,
              flush=True)
        for variant in args.variants.split(","):
            kw = {"precision": "fp8"} if variant == "fp8" else {"fault": variant}
            t = time.perf_counter()
            got = ref_mod.run(cell.config, cell.traffic, seed, devices=devices,
                              against=ref["moments"], **kw)
            print(json.dumps({"workload": cell.name, "seed": seed, "variant": variant,
                              "seconds": time.perf_counter() - t,
                              **compare.gaps(got, ref, got["moment_error"])}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
