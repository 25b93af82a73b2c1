#!/usr/bin/env python3
"""Sets of runs of one cell, and the spreads that its bounds come from.

    python3 chipbench/sets.py run --workload <cell> --seeds 1,2,3 \
        --seconds 51 --trace 0 --label A --out <file>.jsonl
    python3 chipbench/sets.py report <file>.jsonl [<file>.jsonl ...]

``run`` starts ``run.py`` once per seed, one process after another (this
process never imports JAX, so each run has the chips to itself), and
appends one JSON line per run to ``--out``: the cell, the label, the seed,
the exit code, the wall seconds, the window line, the result line and the
end of standard error.  With ``--diagnose`` (``--trace 0`` only) each run
is ``diagnose.py``'s, which adds what the window's host did (``diag``).  ``report`` groups the lines by cell, label and
``--trace`` and prints, for each metric, the median and the spread: the
distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median.
"""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path
from typing import Dict, List, Tuple

HERE = Path(__file__).resolve().parent


def spread(values: List[float]) -> float:
    """Interquartile range over the median."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def run_one(workload: str, seed: int, seconds: float, trace: int,
            diagnose: bool = False) -> dict:
    if diagnose:
        cmd = [str(HERE / "diagnose.py"), "child", workload, str(seed),
               str(seconds)]
    else:
        cmd = [str(HERE / "run.py"), "--workload", workload, "--seed",
               str(seed), "--seconds", str(seconds), "--trace", str(trace)]
    t0 = time.perf_counter()
    out = subprocess.run([sys.executable, *cmd], capture_output=True,
                         text=True)
    rec = {"rc": out.returncode, "wall_s": time.perf_counter() - t0,
           "window": None, "line": None, "stderr": out.stderr[-2000:]}
    for text in out.stdout.splitlines():
        if text.startswith('{"window"'):
            rec["window"] = json.loads(text)["window"]
        elif text.startswith('{"diag"'):
            rec["diag"] = json.loads(text)["diag"]
        elif text.startswith('{"correct"'):
            rec["line"] = json.loads(text)
    return rec


def report(records: List[dict]) -> List[str]:
    """Per cell, label and trace: n, the runs' correctness, and each
    metric's median, spread, least and largest reading; each check's
    largest reading; each run's seed, updates, window and set-up."""
    groups: Dict[Tuple[str, str, int], List[dict]] = {}
    for r in records:
        groups.setdefault((r["workload"], r["label"], r["trace"]),
                          []).append(r)
    lines = []
    for (cell, label, trace), rs in sorted(groups.items()):
        done = [r for r in rs if r["line"] is not None]
        lines.append(f"## {cell} set {label} trace {trace}: {len(rs)} runs, "
                     f"correct {[r['line']['correct'] for r in done]}, "
                     f"rc {[r['rc'] for r in rs]}")
        names = sorted({k for r in done for k in r["line"]["metrics"]})
        for k in names:
            v = [r["line"]["metrics"][k]["value"] for r in done
                 if k in r["line"]["metrics"]]
            s = f" spread {spread(v)!r}" if len(v) >= 3 else ""
            lines.append(f"  {k}: median {statistics.median(v)!r}{s} "
                         f"min {min(v)!r} max {max(v)!r}")
        for k in sorted({k for r in done for k in r["line"]["checks"]}):
            v = [r["line"]["checks"][k]["value"] for r in done]
            lines.append(f"  check {k}: max {max(v)!r} min {min(v)!r}")
        for r in rs:
            w, line = r["window"] or {}, r["line"] or {}
            setup = line.get("metrics", {}).get("setup_s", {}).get("value")
            lines.append(
                f"  seed {r['seed']}: updates {w.get('updates')} window "
                f"{w.get('seconds')} compiles {w.get('compiles')} setup "
                f"{setup} wall {r['wall_s']:.1f} peak "
                f"{line.get('device', {}).get('memory_peak_bytes')}")
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    r = sub.add_parser("run")
    r.add_argument("--workload", required=True)
    r.add_argument("--seeds", required=True,
                   type=lambda s: [int(v) for v in s.split(",")])
    r.add_argument("--seconds", type=float, required=True)
    r.add_argument("--trace", type=int, choices=(0, 1), default=0)
    r.add_argument("--label", default="")
    r.add_argument("--out", required=True)
    r.add_argument("--diagnose", action="store_true")
    p = sub.add_parser("report")
    p.add_argument("files", nargs="+")
    args = ap.parse_args(argv)

    if args.cmd == "report":
        records = [json.loads(t) for f in args.files
                   for t in Path(f).read_text().splitlines() if t.strip()]
        print("\n".join(report(records)))
        return 0
    if args.diagnose and args.trace:
        ap.error("--diagnose runs --trace 0 only")
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    for seed in args.seeds:
        rec = {"workload": args.workload, "label": args.label, "seed": seed,
               "seconds": args.seconds, "trace": args.trace,
               **run_one(args.workload, seed, args.seconds, args.trace,
                         args.diagnose)}
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
        print(json.dumps({k: rec[k] for k in ("workload", "label", "seed",
                                               "rc", "wall_s")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
