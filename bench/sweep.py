"""Find the knee of an open-loop cell once, by a sweep on the chip.

    python3 -m bench.sweep --workload robot.serve --cameras 64,96,128 \\
        --seed 5 --seconds 10

In one process, runs the cell's traffic at each camera count: each count
is a cell of its own, made in a scratch checkout from the cell's traffic
file with only ``cameras`` changed, and run through the same harness.
Prints, per count, ``served_p95_ms``, the frames that failed, the
backlog at the window's middle and close, and how late the generator
ran. The knee is the highest count at which ``served_p95_ms`` stays
within the traffic's ``deadline_ms``, nothing fails and the backlog does
not grow; the cell's file then fixes ``cameras`` at four fifths of it.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path


def scratch_checkout(root: Path, workload: str, counts: list):
    """A checkout beside nothing: ``bench`` and ``src`` linked, and
    ``BENCHMARK.json`` with one cell per camera count."""
    d = Path(tempfile.mkdtemp(prefix="bench-sweep-"))
    shutil.copytree(root / "bench", d / "bench")
    os.symlink(root / "src", d / "src")
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cell = next(w for w in manifest["workloads"] if w["name"] == workload)
    params = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    for n in counts:
        name = f"sweep_{n}"
        (d / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps({**params, "cameras": n}))
        manifest["workloads"].append({**cell, "name": f"{workload}.{name}",
                                      "traffic": name})
        for m in manifest["end_to_end"] + manifest["per_layer"]:
            if workload in m.get("workloads", []):
                m["workloads"].append(f"{workload}.{name}")
    (d / "BENCHMARK.json").write_text(json.dumps(manifest))
    return d, params


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--cameras", required=True, help="comma-separated")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    from bench import run

    counts = [int(c) for c in args.cameras.split(",")]
    d, params = scratch_checkout(run.ROOT, args.workload, counts)
    try:
        for n in counts:
            out = io.StringIO()
            try:
                with contextlib.redirect_stdout(out):
                    r = run.run(d, f"{args.workload}.sweep_{n}", args.seed,
                                args.seconds, False,
                                t_start=time.monotonic())
            except run.Refused as e:
                print(f"bench.sweep: refused: {e}", file=sys.stderr)
                return 2
            window = [json.loads(line) for line in
                      out.getvalue().splitlines()
                      if line.startswith('{"phase": "window"')][-1]
            p95 = r["metrics"]["served_p95_ms"]["value"]
            print(json.dumps({
                "cameras": n, "offered_frames_per_s": n * params["fps"],
                "served_p95_ms": p95 if math.isfinite(p95) else None,
                "attempted": r["attempted"], "failed": r["failed"],
                "correct": r["correct"],
                **{k: window[k] for k in ("backlog_mid", "backlog_close",
                                          "late_ms_p95", "late_ms_max",
                                          "errors", "compiles_in_window")},
                "device": r["device"]}), flush=True)
    finally:
        shutil.rmtree(d, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
