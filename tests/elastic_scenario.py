"""Multi-width elastic re-meshing scenario, run as a SUBPROCESS by
tests/test_elastic.py: the resize invariants need a pool wider than one
device, and the forced host-device count must be set before jax imports,
which the parent test process (already holding an initialized jax) cannot
do for itself.

Covers, on an 8-wide forced-device pool:
  * fixed W=8 vs resized 8 -> 3 -> 8 runs stitching bitwise-identical
    p-values for single-generator, fan-out and over_decompose specs;
  * compile-cache trace counts showing only the new width recompiles;
  * the W=8 -> W=4 checkpoint-resume regression (job-id-keyed v3 layout);
  * the v2 -> v3 checkpoint upgrade path across a width change.

Prints one JSON dict on the last stdout line; the pytest side asserts.
Usage: python tests/elastic_scenario.py <tmpdir>
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import json                                            # noqa: E402
import sys                                             # noqa: E402

import numpy as np                                     # noqa: E402

from repro.ckpt import io as ckpt_io                   # noqa: E402
from repro.core.api import (                           # noqa: E402
    Checkpoint, PoolSession, RunSpec)
from repro.core.policies import OverDecomposePolicy    # noqa: E402

SCALE = 0.0625
tmp = sys.argv[1]
out = {}


def drive_resized(session, spec, shrink_to=3):
    """One run with the pool bouncing 8 -> shrink_to -> 8 mid-battery."""
    handle = session.submit(spec)
    handle.poll()
    session.resize(shrink_to)
    handle.poll()
    session.grow(8 - shrink_to)
    return handle.result()


def keyed(res):
    """{generator: {job: (stat, p)}} for single- and multi-gen results."""
    runs = getattr(res, "runs", None)
    if runs is None:
        return {"_": res.results}
    return {g: r.results for g, r in runs.items()}


fixed = PoolSession(n_workers=8)
elastic = PoolSession(n_workers=8)

# --- 1. single generator: bitwise stitched p-values + trace accounting
spec1 = RunSpec("smallcrush", "splitmix64", 7, scale=SCALE)
out["single_bitwise"] = (keyed(fixed.submit(spec1).result())
                         == keyed(drive_resized(elastic, spec1)))
out["single_trace_widths"] = sorted(
    [k[2], v] for k, v in elastic.trace_counts.items())

# --- 1b. a one-worker pool (a program compiled without partitioning)
# stitches the same bits as the 8-wide one
out["width1_bitwise"] = (keyed(fixed.submit(spec1).result())
                         == keyed(PoolSession(n_workers=1).submit(spec1)
                                  .result()))

# --- 2. multi-generator fan-out (mapped gen_ids axis) across a resize
spec2 = RunSpec("smallcrush", ("splitmix64", "randu"), 7, scale=SCALE)
out["fanout_bitwise"] = (keyed(fixed.submit(spec2).result())
                         == keyed(drive_resized(elastic, spec2)))

# --- 3. over-decomposed sub-streams survive the resize (the cut is a
# function of the battery, never of the width)
od = OverDecomposePolicy(threshold=0.05, max_parts=4)
spec3 = RunSpec("smallcrush", "splitmix64", 7, scale=SCALE, policy=od)
out["overdec_bitwise"] = (keyed(fixed.submit(spec3).result())
                          == keyed(drive_resized(elastic, spec3)))

# --- 4. regression: checkpoint written at W=8 resumes on a W=4 pool
ck = os.path.join(tmp, "w8.ck")
spec_ck = RunSpec("smallcrush", "splitmix64", 7, scale=SCALE,
                  checkpoint_path=ck)
res1 = fixed.submit(spec_ck).result()
Checkpoint.load(ck).drop([2, 8]).save(ck)          # two "node failures"
fixed.resize(4)
run2 = fixed.submit(spec_ck)
status = run2.status()
out["resume_missing"] = status["jobs_total"] - status["jobs_done"]
res2 = run2.result()
out["resume_bitwise"] = res2.results == res1.results
out["resume_rounds"] = res2.rounds_run
out["resume_ckpt_version"] = int(ckpt_io.load_flat(ck)[0])

# --- 5. v2 -> v3 upgrade across the width change: hand-write the legacy
# 5-leaf layout (UNDECIDED verdict state, partial results), resume at
# W=4, and confirm the next save upgrades the file to v3
ck2 = os.path.join(tmp, "v2.ck")
partial = Checkpoint.load(ck).drop([1, 4])
ckpt_io.save(ck2, [partial.job_idx, partial.stats, partial.ps,
                   np.zeros(1, np.int8), np.int64(2)])
spec_v2 = RunSpec("smallcrush", "splitmix64", 7, scale=SCALE,
                  checkpoint_path=ck2)
res3 = fixed.submit(spec_v2).result()
out["v2_upgrade_bitwise"] = res3.results == res1.results
out["v2_upgraded_leaves"] = len(ckpt_io.load_flat(ck2))

print(json.dumps(out))
