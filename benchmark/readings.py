"""Readings that set the correctness limits: a cell's sound runs on many
seeds, and its control and faults on a few, in one process on the chip.

    python3 benchmark/readings.py --workload <name> --seconds <s> \
        --seeds <n,n,...> --faults control,slow --fault-seeds <n,n,...>

Each run is the harness's whole run (benchmark/run.py `run_cell`) at the
cell's own size, with a short window; the control and the faults are the
broken paths of benchmark/faults.py.  One JSON line a run, then a summary
line: for every number compared, the largest reading of the sound runs
and the smallest of each broken path's.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default="control")
    ap.add_argument("--fault-seeds", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [BENCH, os.path.dirname(BENCH)]
    import run
    run.prepare_env()
    import faults

    bench = run.load_json("BENCHMARK.json")
    ref = run.resolve(bench, args.workload)["config"]["reference"]
    plans = [("sound", int(s)) for s in args.seeds.split(",")]
    plans += [(kind, int(s)) for kind in args.faults.split(",") if kind
              for s in args.fault_seeds.split(",") if s]
    worst = {}
    for kind, seed in plans:
        fn, kw_fn, ctx = (None, None, contextlib.nullcontext()) \
            if kind == "sound" else faults.plant(kind, ref)
        with ctx:
            res, _ = run.run_cell(bench, args.workload, seed, args.seconds,
                                  plant=fn, program_kw=kw_fn)
        vals = {k: v["value"] for k, v in res["checks"].items()}
        pick = max if kind == "sound" else min
        w = worst.setdefault(kind, {})
        for k, v in vals.items():
            w[k] = pick(w.get(k, v), v)
        print(json.dumps({"kind": kind, "seed": seed,
                          "correct": res["correct"],
                          "attempted": res["attempted"],
                          "failed": res["failed"], "checks": vals,
                          "sim_s_per_s": res["metrics"]["sim_s_per_s"]
                          ["value"]}), flush=True)
    print(json.dumps({"summary": args.workload,
                      "sound_max": worst.pop("sound", {}),
                      "broken_min": worst}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
