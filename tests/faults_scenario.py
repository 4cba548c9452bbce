"""Multi-worker fault-domain scenario, run as a SUBPROCESS by
tests/test_faults.py: quarantine and ``lose_worker`` need a pool wider
than one device, and the forced host-device count must be set before
jax imports, which the parent test process (already holding an
initialized jax) cannot do for itself.

Covers, on a 4-wide forced-device pool:
  * ``lose_worker`` at round 0 shrinking the pool to 3 mid-battery,
    with stitched p-values bitwise identical to the clean W=4 run;
  * a persistently flaky slot (evict slot 1 every round) walked down by
    the quarantine machinery 4 -> 3 -> 2 -> 1 until the rule can no
    longer match, completing with bitwise-identical p-values — the
    headline "any plan leaving >= 1 healthy worker degrades, never
    corrupts" invariant;
  * the degraded daemon: a ``SubmissionQueue`` whose session was
    quarantined down to one slot keeps serving (ticket DONE, parity)
    and reports ``status == "degraded"`` in ``stats()``.

Prints one JSON dict on the last stdout line; the pytest side asserts.
Usage: python tests/faults_scenario.py <tmpdir>
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import json                                            # noqa: E402
import sys                                             # noqa: E402

from repro.core.api import PoolSession, RunSpec        # noqa: E402
from repro.core.faults import FaultPlan, FaultRule     # noqa: E402
from repro.core.policies import RetryPolicy            # noqa: E402
from repro.serve.queue import DONE, SubmissionQueue    # noqa: E402

SCALE = 0.0625
tmp = sys.argv[1]
out = {}

session = PoolSession()
assert session.n_workers == 4, session.n_workers


def spec_for(plan=None, retry=None, policy="lpt"):
    return RunSpec("smallcrush", "splitmix64", 7, scale=SCALE,
                   retry=retry or RetryPolicy(), policy=policy,
                   inject=plan)


clean = session.submit(spec_for()).result()
# roundrobin keeps every slot busy on consecutive rounds, so a
# persistently flaky slot actually accumulates the quarantine streak;
# parity must hold across policies anyway, but the baseline matches the
# policy under test
clean_rr = session.submit(spec_for(policy="roundrobin")).result()
assert clean_rr.results == clean.results

# -- lose_worker: width drops 4 -> 3 after round 0 ------------------------
lose = FaultPlan(rules=(FaultRule("lose_worker", round=0),))
h = session.submit(spec_for(lose))
res = h.result()
out["lose_worker_bitwise"] = res.results == clean.results
out["lose_worker_final_w"] = session.n_workers
out["lose_worker_events"] = [e.kind for e in h.fault_events]

# -- quarantine: slot 1 evicts every round; pool walks down to W=1 --------
session.resize(4)
flaky = FaultPlan(rules=(FaultRule("evict", slot=1),))
h = session.submit(spec_for(
    flaky, RetryPolicy(max_retries=10, quarantine_after=2),
    policy="roundrobin"))
res = h.result()
out["quarantine_bitwise"] = res.results == clean.results
out["quarantine_verdict"] = res.verdict.decision == clean.verdict.decision
out["quarantines"] = h.quarantines
out["final_workers"] = session.n_workers
out["quarantine_retries"] = res.retries

# -- degraded daemon: quarantined-to-one-slot queue keeps serving ---------
qsession = PoolSession()
qsession.resize(4)
queue = SubmissionQueue(qsession, state_dir=os.path.join(tmp, "serve"),
                        inject=flaky)
t = queue.submit(spec_for(
    retry=RetryPolicy(max_retries=10, quarantine_after=2),
    policy="roundrobin"))
queue.drain()
stats = queue.stats()
out["serve_state"] = t.state == DONE
out["serve_bitwise"] = t.result().results == clean.results
out["serve_status"] = stats["status"]
out["serve_workers"] = stats["workers"]

print(json.dumps(out))
