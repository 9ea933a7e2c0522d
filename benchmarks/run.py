"""Benchmark harness — one entry per paper table/figure + system extensions.
Prints ``name,us_per_call,derived`` CSV (one line per measurement)."""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from repro.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    from benchmarks import (
        auditbench,
        autoscale,
        catalogbench,
        cohortbench,
        detectbench,
        fleetbench,
        ingestbench,
        kernelbench,
        obsbench,
        roofline,
        slobench,
        table1_throughput,
        table2_rules,
    )

    suites = [
        ("table1_throughput", table1_throughput.main),
        ("table2_rules", table2_rules.main),
        ("cohortbench", cohortbench.main),
        ("catalogbench", catalogbench.main),
        ("detectbench", detectbench.main),
        ("fleetbench", fleetbench.main),
        ("ingestbench", ingestbench.main),
        ("obsbench", obsbench.main),
        ("auditbench", auditbench.main),
        ("slobench", slobench.main),
        ("autoscale", autoscale.main),
        ("kernelbench", kernelbench.main),
        ("roofline", roofline.main),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in suites:
        try:
            for line in fn():
                print(line)
        except Exception:
            failures += 1
            print(f"{name},-1,ERROR")
            traceback.print_exc(file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
