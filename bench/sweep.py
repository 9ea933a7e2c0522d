"""Find an open-loop cell's knee: the highest arrival rate the served path
sustains without a growing backlog. Run once when a cell is defined, on the
chip; its readings go into PERF.md and the rate into the traffic file. Not
part of the benchmark's own runs.

    python3 bench/sweep.py --workload dx_ondemand --seed <n> --seconds <s> --rates 1,2,3

One process: set-up once, then one window per rate, each drained before
the next. Prints one JSON line per rate.
"""
import argparse
import json
import sys
import tempfile
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests per second")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    if cell.traffic["loop"] != "open":
        harness.log(f"{args.workload} is not an open-loop cell")
        return 2
    harness.configure_jax(True, cell.chips)
    sys.path.insert(0, str(harness.ROOT / "src"))
    import source
    from reference import accepted
    from stats import quantile

    studies = source.make_lake(cell.config, cell.traffic, args.seed)
    expected = {s.accession: accepted(s, cell.config) for s in studies}
    cell.traffic = dict(cell.traffic, check_sample=0)
    with tempfile.TemporaryDirectory(prefix="bench-sweep-") as tmp:
        path = harness.ServedPath(cell, Path(tmp), None, None)
        try:
            for s in studies:
                path.lake.put_study(s.accession, source.to_program(s))
            harness._warm_up(path, cell, studies, args.seed)
            rng = np.random.default_rng(int(cell.traffic["arrival_seed"]))
            idx = 0
            for rate in (float(r) for r in args.rates.split(",")):
                schedule = []
                for d in harness.poisson_schedule(rate, args.seconds, rng):
                    s = studies[idx % len(studies)]
                    schedule.append(harness.Request(idx, s, f"S{args.seed}-{idx}",
                                                    harness.project_key(args.seed, idx), d))
                    idx += 1
                consumer = harness.Consumer(path, cell, args.seed, expected)
                t0, t1, reqs = harness.open_loop(path, schedule, args.seconds, consumer)
                lat = [r.done - r.due for r in reqs]
                late = [r.submitted - r.due for r in reqs if r.submitted is not None]
                out = {"rate_per_s": rate, "requests": len(reqs),
                       "pending_at_close": sum(1 for r in reqs if r.done > t1),
                       "done_by_close_per_s": sum(1 for r in reqs if r.done <= t1) / args.seconds,
                       "p50_s": quantile(lat, 0.5), "p95_s": quantile(lat, 0.95),
                       "max_s": max(lat), "lateness_max_s": max(late) if late else 0.0,
                       "failed": sum(1 for r in reqs if not r.ok)}
                print(json.dumps(out), flush=True)
        finally:
            path.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
