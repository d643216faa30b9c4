#!/usr/bin/env python3
"""Read a cell's compared numbers over many seeds in one process: the
program's (the lower readings), the control's and the planted faults'
(the upper readings) that its limits are set between. Not part of a run.

    python3 benchmark/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 3] [--fault-seeds 3] [--out file.jsonl]

Each seed makes its data, runs the job call once after the first seed's
warm-up and reads the comparison at the cell's own size; the control and
the faults are read on the first seeds asked for. One JSON line a seed.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark import run as harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=0)
    ap.add_argument("--fault-seeds", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    _, cell, config, traffic, family = harness.open_cell(args.workload,
                                                         args.rehearse)
    import jax
    harness.place_compile_cache(jax)
    harness.check_devices(jax, cell["chips"], args.rehearse)
    out = open(args.out, "a") if args.out else None
    try:
        for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            job_seed = seed % (2 ** 31 - 1)
            data = jax.block_until_ready(
                family.make_data(config, seed, cell["chips"]))
            call = family.make_call(config, traffic, data, job_seed)
            if n == 0:
                call()
            t1 = time.perf_counter()
            got = family.outputs(call())
            t2 = time.perf_counter()
            found = family.check(config, traffic, data, job_seed, got,
                                 control=n < args.control_seeds)
            t3 = time.perf_counter()
            line = {"workload": args.workload, "seed": seed,
                    "call_s": t2 - t1, "check_s": t3 - t2,
                    "data_s": t1 - t0, **found}
            if n < args.fault_seeds:
                line["faults"] = {
                    name: family.check(config, traffic, data, job_seed,
                                       broken())["checks"]
                    for name, broken in family.faults(
                        config, traffic, data, job_seed, got).items()}
            text = json.dumps(line, default=harness.printable)
            print(text, flush=True)
            if out:
                out.write(text + "\n")
                out.flush()
            del data, call, got
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except harness.Refused as e:
        print(f"benchmark/calibrate.py: {e}", file=sys.stderr)
        sys.exit(2)
