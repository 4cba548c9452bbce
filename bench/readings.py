"""The readings that the limits of ``correct`` are set from.

    python3 bench/readings.py --workload <cell> --seed <n> \\
        --program 12 --control 3

One process, one set-up. The program: ``--program`` requests through the
cell's timed path (``PoolSession.submit`` -> ``poll`` -> ``result``), each
compared with the plain reference; the largest reading of each number is
its lower reading. The control: the reference itself computed in
bfloat16, the precision below the float32 the configuration states, put
in the program's place for ``--control`` requests; the smallest reading
is the upper one. ``bench/run.py`` never runs the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import ml_dtypes

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import reference  # noqa: E402
from bench import run  # noqa: E402

NUMBERS = ("stat_gap", "p_gap", "verdict_mismatch")


def control(table, config, gen, seed) -> dict:
    """The control's readings for one lane."""
    res = reference.run_request(table, gen, seed, ml_dtypes.bfloat16)
    dec, failed = reference.verdict(res, len(table), config["alpha"])
    return run.lane_gaps(run.Lane(gen, seed, res, dec, failed), table,
                         config["alpha"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--program", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    config, traffic = cell.config, cell.traffic
    rows = {"program": [], "control": []}
    if args.program:
        pool = run.open_pool(cell, args.seed)
        for k in range(args.program):
            req = run.request(config, traffic, args.seed, k)
            rec = run.Record(k, req["seeds"], req["generators"])
            t = time.perf_counter()
            handle = pool.session.submit(pool.RunSpec(**req))
            while handle.pending_rounds:
                handle.poll()
            run.finish(rec, handle, t)
            for lane in rec.lanes:
                g = run.lane_gaps(lane, pool.table, config["alpha"])
                g.update(generator=lane.generator, seed=lane.seed,
                         rounds=rec.rounds, retries=rec.retries,
                         latency_s=rec.latency_s)
                rows["program"].append(g)
                print("program", json.dumps(g), flush=True)
    table = reference.battery(config["battery"], config["scale"])
    for k in range(args.control):
        for gen in traffic["generators"]:
            tag = (f"control-{k}" if len(traffic["generators"]) == 1
                   else f"control-{k}-{gen}")
            seed = run.request_seed(args.seed, tag)
            g = control(table, config, gen, seed)
            g.update(generator=gen, seed=seed)
            rows["control"].append(g)
            print("control", json.dumps(g), flush=True)
    summary = {
        "lower": {n: max((r[n] for r in rows["program"]), default=None)
                  for n in NUMBERS},
        "upper": {n: min((r[n] for r in rows["control"]), default=None)
                  for n in NUMBERS},
        "programs": len(rows["program"]), "controls": len(rows["control"]),
    }
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
