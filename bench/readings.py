"""The readings a correctness limit is set from, for one cell.

    python3 -m bench.readings --workload robot.fleet256 \\
        --seeds 201-212 --control-seeds 201-203 --seconds 3

In one process (set-up is paid once), runs the cell's traffic on each
seed through the program and through the control put in the program's
place, and prints each run's widest frame gap (``bench/check.py``)
beside the limit the configuration holds. The control is the reference
at the precision one step below what the configuration states
(``bench/reference.py``, ``"high"``): three bf16 passes per product.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np


def control_hook(sess, cfg, weights):
    """Put the reference at ``"high"`` in the program's place."""
    import jax.numpy as jnp

    from bench import reference

    fn = reference.make(cfg, weights, "high")
    backend = sess.backend

    def predict_batch(x):
        return np.asarray(fn(jnp.asarray(x, jnp.float32)))

    backend.predict_batch = predict_batch


def _seeds(text: str) -> list:
    a, _, b = text.partition("-")
    return list(range(int(a), int(b or a) + 1))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="first-last")
    ap.add_argument("--control-seeds", default="", help="first-last")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    from bench import run

    plan = [("program", s) for s in _seeds(args.seeds)]
    if args.control_seeds:
        plan += [("control", s) for s in _seeds(args.control_seeds)]
    for kind, seed in plan:
        try:
            r = run.run(run.ROOT, args.workload, seed, args.seconds, False,
                        t_start=time.monotonic(),
                        hook=control_hook if kind == "control" else None)
        except run.Refused as e:
            print(f"bench.readings: refused: {e}", file=sys.stderr)
            return 2
        except RuntimeError as e:  # a control that fails outright
            print(json.dumps({"reading": kind, "workload": args.workload,
                              "seed": seed, "error": str(e)}), flush=True)
            continue
        print(json.dumps({"reading": kind, "workload": args.workload,
                          "seed": seed, "correct": r["correct"],
                          **{k: v["value"] for k, v in r["checks"].items()},
                          "limit": r["checks"]["out_gap"]["limit"],
                          "metrics": {k: v["value"] for k, v in
                                      r["metrics"].items()},
                          "device": r["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
