"""Readings for the limits of ``correct``: the program and its control.

    python3 bench/check.py --workload <cell> --seeds 11,12,... --seconds <s>

In one process (one chip start for many seeds), for each seed: a short
window of the cell through the timed path (``run.drive``), the program's
reading (stored partitions whose rows differ from the reference), then the
control's: the reference computed in bfloat16, put in the program's place
and compared with the float32 reference the same way. One JSON line per
seed goes to standard output. The benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import run  # noqa: E402  (puts src on sys.path too)
from bench.reference import Reference, compare, partitions  # noqa: E402


def control_reading(d) -> dict:
    """The bfloat16 reference in the program's place, on the final state."""
    bases = {i: s.final() for i, s in d.sources.items()}
    expected = Reference(d.config).evaluate(bases)
    control = Reference(d.config, precision="bfloat16").evaluate(bases)
    names = [n["name"] for n in d.config["nodes"]]
    P = int(d.config["n_partitions"])
    parts = {(n, p): t for n, full in zip(names, control)
             for p, t in enumerate(partitions(full, P))}
    return compare(expected, lambda mv, p: parts[(mv, p)], names, P)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("check: JAX found no TPU", file=sys.stderr)
        return 2
    quiet = lambda *_: None  # noqa: E731
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        try:
            d = run.drive(args.workload, seed, args.seconds, False, log=quiet)
            res = run.report(d, t0, log=quiet)
            ctl = control_reading(d)
        finally:
            shutil.rmtree(run.WORK_DIR, ignore_errors=True)
        print(json.dumps({
            "seed": seed, "correct": res["correct"],
            "rounds": res["attempted"], "failed": res["failed"],
            "program_bad_entries": res["checks"]["bad_entries"]["value"],
            "control_bad_entries": ctl["bad"], "entries": ctl["total"],
            "refresh_s": res["metrics"].get("refresh_s", {}).get("value"),
            "setup_s": res["metrics"].get("setup_s", {}).get("value"),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
