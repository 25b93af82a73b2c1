#!/usr/bin/env python3
"""Runs of one cell with what the window's host did, to find run-to-run
noise.

    python3 chipbench/sets.py run --diagnose --workload <cell> \
        --seeds 1,2,3 --seconds 51 --label A --out <file>.jsonl
    python3 chipbench/diagnose.py report <file>.jsonl [...]

``child`` (started by ``sets.py run --diagnose``, one process per seed)
makes the run that ``run.py`` makes (``harness.run``, telemetry and
profiler off) and prints, besides its window and result lines, a
``diag`` line: each solve's record timeline (seconds, applied updates; a
record every 4 arrivals), the workers' shares of the updates, the
process's CPU seconds over the window, and the count and seconds of
garbage collections in it.  It exits non-zero without a TPU.  ``report``
prints per run the rate, applied updates in each 10 s of the window, the
three longest times between records and the cores used.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import resource  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _cpu_s() -> float:
    r = resource.getrusage(resource.RUSAGE_SELF)
    return r.ru_utime + r.ru_stime


def child(workload: str, seed: int, seconds: float) -> int:
    """One run, as ``run.py`` makes it, with the window's host readings.

    The window's edges are where ``harness.run`` reads its compile
    counter, just before the first solve and just after the last."""
    from chipbench import harness

    cell = harness.load_cell(workload)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("diagnose: JAX found no TPU", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    edges, gcs, solves = [], {"n": 0, "s": 0.0, "max": 0.0}, []
    counter, checker = harness.compiles, harness.check
    started = [0.0]

    def compiles() -> int:
        edges.append((time.perf_counter(), _cpu_s()))
        return counter()

    def on_gc(phase: str, _info: dict) -> None:
        if phase == "start":
            started[0] = time.perf_counter()
        elif len(edges) == 1:  # inside the window
            d = time.perf_counter() - started[0]
            gcs["n"] += 1
            gcs["s"] += d
            gcs["max"] = max(gcs["max"], d)

    def check(cell_, seed_, problem, rc, window_solves, anderson=None):
        solves.extend(window_solves)
        return checker(cell_, seed_, problem, rc, window_solves, anderson)

    harness.compiles, harness.check = compiles, check
    gc.callbacks.append(on_gc)
    out = harness.run(cell, seed, seconds, False, t_start=T_START)
    (t0, c0), (t1, c1) = edges[:2]
    diag = {"cpu_s": c1 - c0, "window_s": t1 - t0, "gc": gcs,
            "solves": [{"records": [(t, wu) for t, wu, _ in r.history],
                        "shares": r.service_fractions} for r in solves]}
    print(json.dumps({"window": out.window}), flush=True)
    print(json.dumps({"diag": diag}), flush=True)
    print(json.dumps(out.line), flush=True)
    return 0


def report(records) -> list:
    lines = []
    for r in records:
        d, line = r.get("diag"), r.get("line")
        if d is None or line is None:
            lines.append(f"{r['workload']} {r['label']} seed {r['seed']}: "
                         f"rc {r['rc']}, no result")
            continue
        rate = line["metrics"].get("point_updates_per_s", {}).get("value")
        recs = [p for s in d["solves"] for p in s["records"]]
        per10, last = {}, 0
        for t, wu in recs:
            per10[int(t // 10)] = wu
        steps = []
        for k in sorted(per10):
            steps.append(per10[k] - last)
            last = per10[k]
        gaps = sorted((b[0] - a[0] for a, b in zip(recs, recs[1:])),
                      reverse=True)[:3]
        lines.append(
            f"{r['workload']} {r['label']} seed {r['seed']}: rate {rate!r} "
            f"updates per 10 s {steps} longest gaps "
            f"{[round(g, 2) for g in gaps]} cores "
            f"{d['cpu_s'] / d['window_s']:.2f} gc {d['gc']}")
    return lines


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv[:1] == ["child"]:
        return child(argv[1], int(argv[2]), float(argv[3]))
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("cmd", choices=("report",))
    ap.add_argument("files", nargs="+")
    args = ap.parse_args(argv)
    print("\n".join(report([json.loads(t) for f in args.files
                            for t in Path(f).read_text().splitlines()
                            if t.strip()])))
    return 0


if __name__ == "__main__":
    sys.exit(main())
