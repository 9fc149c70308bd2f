"""Readings that the limits of ``check.py`` are set from.

    python3 bench/control.py --workload <cell> --seeds 11,12,... \
        --control-seeds 11,12,13 --seconds 8 [--out readings.jsonl]

For each seed, in one process: one run of the cell (a short window at the
cell's own load and sizes) and its numbers against the reference, the
program's reading; for the control seeds also the same numbers of the
control, the reference computed in float8 and put in the program's
place. One JSON line per seed. The benchmark's own runs never run the
control. ``--rehearse`` does the same at cut widths on the CPU.
"""
from __future__ import annotations

import argparse
import json
import os

import run


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    else:
        run.use_compile_cache()
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.run(args.workload, seed, args.seconds, False,
                      rehearse=args.rehearse, control=seed in ctl)
        line = json.dumps({"seed": seed, "correct": res["correct"],
                           "attempted": res["attempted"],
                           "checks": res["checks"],
                           "control": res.get("control"),
                           "metrics": res["metrics"]})
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")


if __name__ == "__main__":
    main()
