#!/usr/bin/env python3
"""Readings of the check over many seeds, in one process on the chip.

    python3 chipbench/calibrate.py --workload <cell> --seconds <s> \
        --seeds 1,2,3 [--patch float32|unchanged|half|no_exchange|altered|
                               dropped_newest]

Runs the cell once per seed, as ``run.py`` does, with the timed path
broken by ``--patch`` where given (``faults.py``: ``float32`` is the
check's control), and prints one JSON line per seed with the numbers the
check compared.  A limit in ``configs/<config>.json`` lies between the
largest reading of the program's seeds and the smallest of the control's.
Exits non-zero without a TPU.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    from chipbench import faults, harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True,
                    type=lambda s: [int(v) for v in s.split(",")])
    ap.add_argument("--patch", choices=faults.KINDS + faults.COMBINE_KINDS)
    args = ap.parse_args(argv)

    import jax

    cell = harness.load_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("calibrate: JAX found no TPU", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    for seed in args.seeds:
        patch = faults.patch(args.patch, cell, seed) if args.patch else None
        out = harness.run(cell, seed, args.seconds, False,
                          t_start=time.perf_counter(), patch=patch)
        print(json.dumps({"seed": seed, "patch": args.patch,
                          "correct": out.line["correct"],
                          "checks": out.line["checks"],
                          "window": out.window}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
