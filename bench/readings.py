"""Readings of the numbers that decide ``correct``, over many seeds in one
process on the chip: the program as the configuration states it, and the
control.

    python3 bench/readings.py --workload <name> --seeds 1,2,3 \\
        --control-seeds 4,5,6 --seconds <s>

Each seed is one whole run of the cell (``bench/run.py``'s ``run``: data
from the seed, warm-up, a window of ``--seconds``, the reference check).
The control is the program's own lower-precision path: the same run with
the design and targets in bfloat16 (the configuration states float32).
One JSON line per seed, with each number compared and the run's own
readings. The limits in ``bench/limits`` are set from these; the
benchmark's own runs never run this. Like a run, it refuses to read
anything off a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    import jax.numpy as jnp

    from bench import run

    for seeds, dtype in ((args.seeds, None), (args.control_seeds, jnp.bfloat16)):
        for s in filter(None, seeds.split(",")):
            notes: dict = {}
            try:
                out = run.run(["--workload", args.workload, "--seed", s,
                               "--seconds", str(args.seconds), "--trace", "0"],
                              control_dtype=dtype, notes=notes)
            except run.NoDevice as e:
                print(f"readings: {e}; nothing was read", file=sys.stderr)
                return 1
            except Exception as e:  # a control that crashes has failed
                if dtype is None:
                    raise
                print(json.dumps({"seed": int(s), "dtype": "bfloat16",
                                  "crashed": repr(e)[:500]}), flush=True)
                continue
            print(json.dumps({
                "seed": int(s), "dtype": "bfloat16" if dtype else "float32",
                "correct": out["correct"], "answers": out["attempted"],
                "checks": {k: c["value"] for k, c in out["checks"].items()},
                "metrics": {k: m["value"] for k, m in out["metrics"].items()},
                "device": out["device"]["kind"], **notes}), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
