"""Benchmark harness — one module per paper table/figure. Prints
``name,us_per_call,derived`` CSV (brief contract). Every module runs in
this one process (on an accelerator, the process that holds the chips):

  PYTHONPATH=src python -m benchmarks.run
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from repro.common.runtime import cpu_devices
    cpu_devices(8)          # the pool rows' width on a host without chips
    from benchmarks import (accuracy, batch_model, battery_times,
                            campaign, early_stop, elastic, hotpath,
                            kernel_bench, lm_step, submit_overhead)
    rows = []
    for mod in (batch_model, submit_overhead, accuracy, kernel_bench,
                hotpath, battery_times, early_stop, elastic, campaign,
                lm_step):
        try:
            mod.run(rows)
        except Exception:                       # noqa: BLE001
            traceback.print_exc()
            rows.append((f"{mod.__name__}_FAILED", -1.0, "see_stderr"))
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    sys.exit(0)


if __name__ == "__main__":
    main()
