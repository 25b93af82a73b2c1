"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (stdout) and writes JSON to
experiments/bench/.  ``--fast`` runs reduced problem sizes; ``--only``
selects one module.
"""

import argparse
import importlib
import json
import os
import sys
import time

MODULES = [
    "straggler_jacobi",   # Table 2 / Fig 1
    "anderson_jacobi",    # Fig 2
    "coupling_threshold", # Fig 3
    "vi_anderson",        # Figs 4-5
    "vi_selection",       # Fig 6
    "vi_straggler",       # Fig 7 / Table 3
    "scf_async",          # Figs 8-9
    "async_dp_lm",        # beyond-paper (EXPERIMENTS §Beyond-paper)
    "kernels_bench",      # kernel micro-bench + agreement
    "real_async",         # measured Table 2 sweep on all real backends
    "perf_hotpath",       # coordinator hot-path gate (BENCH_hotpath.json)
    "accel_offload",      # evaluation-pipeline offload gate (BENCH_offload.json)
    "chaos_scenarios",    # chaos scenario library sweep (BENCH_chaos.json)
    "autoscale",          # closed-loop autoscaling gate (BENCH_autoscale.json)
    "recovery",           # durable-solve gate (BENCH_recovery.json)
]

# ``--smoke`` subset: ~2 min; exercises the real-concurrency thread and
# process backends end to end and asserts the measured >1.5x async-over-sync
# gates (CI gate alongside the tier-1 pytest command and `make docs-check`).
SMOKE_MODULES = ["real_async"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="run the ~2min real-backend smoke subset (implies --fast)")
    ap.add_argument("--out", default="experiments/bench")
    args = ap.parse_args()

    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    if args.smoke:
        args.fast = True
    # --only can name any module (also under --smoke, which then just
    # implies --fast); --smoke alone runs the quick real-backend subset.
    if args.only is not None:
        mods = [m for m in MODULES if m == args.only]
        if not mods:
            raise SystemExit(f"unknown --only {args.only}; choices: {MODULES}")
    else:
        mods = SMOKE_MODULES if args.smoke else MODULES
    os.makedirs(args.out, exist_ok=True)
    print("name,us_per_call,derived")
    failures = 0
    for name in mods:
        mod = importlib.import_module(f"benchmarks.{name}")
        t0 = time.time()
        try:
            rows = mod.run(fast=args.fast)
        except Exception as e:  # noqa: BLE001
            print(f"{name},0,ERROR:{type(e).__name__}:{e}")
            failures += 1
            continue
        for r in rows:
            print(f"{r['name']},{r['us_per_call']},{r['derived']}")
        with open(os.path.join(args.out, f"{name}.json"), "w") as f:
            json.dump({"rows": rows, "seconds": time.time() - t0}, f, indent=1)
        print(f"# {name} done in {time.time()-t0:.0f}s", file=sys.stderr)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
