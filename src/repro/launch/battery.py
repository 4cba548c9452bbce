"""One-command battery CLI — the paper's `master` script on the session API.

  PYTHONPATH=src python -m repro.launch.battery \
      --battery bigcrush --gen splitmix64 --workers 8 --scale 0.05

``--gen`` takes a comma-separated list: several generators are assessed in
ONE dispatch per round (the pool maps the job over a gen_ids axis).
``--workers N`` spreads the pool over N devices: N chips on an
accelerator host, N CPU devices elsewhere. Compiled programs persist in
JAX's compilation cache (``JAX_COMPILATION_CACHE_DIR``, else
``<checkout>/.jax_cache``). Checkpoints progress per round; re-running
the same command resumes (only missing tests execute). ``--json PATH``
writes a machine-readable report next to the text one.

``--source file:PATH[:fmt]`` screens a CAPTURED bitstream (a file of
raw uint32 words, ``fmt`` ``npy`` or ``u32``) through the same battery
machinery: the file becomes a ``CapturedSource`` position riding
alongside any ``--gen`` positions, its verdict bitwise what the
in-repo generator of the same bits would earn. ``--register
PKG.MOD:FN`` imports and calls a registration hook before the run, so
external generators (``repro.rng.sources.register_generator``) are
valid ``--gen`` names — the plugin seam of DESIGN.md §11.

``--adaptive`` switches to the early-stopping execution mode: the
adaptive schedule policy front-loads cheap discriminating tests, the
sequential verdict engine (alpha from ``--alpha``) decides
PASS/FAIL/UNDECIDED after every round, and pending rounds for a
definitively-failed generator are cancelled instead of dispatched.

``--verdict-engine {bonferroni,evalue}`` picks the verdict engine
(DESIGN.md §13): ``evalue`` scores every test's p-value as an e-value
and multiplies them into an anytime-valid wealth process — FAIL the
moment wealth crosses 1/alpha — and the ``--json`` payload gains an
``"evidence"`` section with each generator's wealth trajectory.

``--backend {auto,reference,accelerated}`` picks the test-kernel
implementation (stats/backends.py): ``accelerated`` routes the counting
hot loops through the Pallas kernels, ``auto`` does so only on real TPU
hardware. The choice (and its resolution) is recorded in ``--json``.

``--resize-at ROUND:WIDTH[,ROUND:WIDTH...]`` demonstrates elastic
re-meshing (the paper's opportunistic pool — machines join and vacate
mid-battery): after the given round the pool is resized to WIDTH and
the remaining rounds replan onto it, e.g. ``--resize-at 2:4,5:8`` for a
pool that shrinks to 4 workers after round 2 and grows back to 8 after
round 5. Stitched p-values are bitwise identical to a fixed-width run.

``--serve`` routes the run through the screening service
(``repro.serve``, DESIGN.md §10): each ``--gen`` entry is submitted as
its OWN ticket — separate clients — and the submission queue coalesces
them into one shared dispatch per round, memoizing every cell in the
content-addressed result cache under ``--serve-state``.
``--serve-resubmit`` submits the first generator's spec a second time
after completion to demonstrate the cache path (zero added
dispatches); the ``--json`` payload gains a ``"serve"`` section with
the ticket table, batch/dispatch counters and cache traffic.

``--campaign`` switches to generator-FLEET screening (DESIGN.md §8):
the ``--gen`` list x ``--streams`` sub-stream offsets are screened in
``--waves`` battery scales (cheapest first), failed cells knocked out
of later waves, the inter-stream seam check run as phase 0::

  PYTHONPATH=src python -m repro.launch.battery --campaign \
      --battery smallcrush --gen splitmix64,pcg32,randu --streams 4 \
      --waves 0.125,0.5 --ledger campaign.ck --json report.json

The output is the per-cell PASS/FAIL matrix; ``--ledger`` makes the
campaign resumable (knocked-out cells stay knocked out across restarts).
"""
import argparse
import json
import os
import sys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--battery", default="smallcrush",
                    choices=["smallcrush", "crush", "bigcrush"])
    ap.add_argument("--gen", default=None,
                    help="generator name, or comma-separated list for "
                         "multi-generator fan-out in one dispatch "
                         "(default: splitmix64 when no --source is given)")
    ap.add_argument("--source", action="append", default=None,
                    metavar="file:PATH[:FMT]",
                    help="screen a captured bitstream: file:PATH[:fmt], "
                         "fmt 'npy' (uint32 array, 2-D = one stream per "
                         "row) or 'u32' (raw little-endian words); "
                         "repeatable — each file rides alongside the "
                         "--gen positions in the same dispatch")
    ap.add_argument("--register", action="append", default=None,
                    metavar="PKG.MOD:FN",
                    help="import PKG.MOD and call FN() before the run; "
                         "the hook registers external generators via "
                         "repro.rng.sources.register_generator, making "
                         "them valid --gen names")
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--scale", type=float, default=0.25)
    ap.add_argument("--workers", type=int, default=0,
                    help="0 = all local devices")
    ap.add_argument("--policy", "--mode", dest="policy", default="lpt",
                    choices=["lpt", "roundrobin", "over_decompose",
                             "adaptive"])
    ap.add_argument("--adaptive", action="store_true",
                    help="early-stopping mode: adaptive schedule order + "
                         "stop_on_verdict (cancel a generator's pending "
                         "rounds once its verdict is definitive)")
    ap.add_argument("--alpha", type=float, default=0.01,
                    help="family-wise error rate the sequential verdict "
                         "engine spends across the battery")
    ap.add_argument("--verdict-engine", dest="verdict_engine",
                    default="bonferroni",
                    choices=["bonferroni", "evalue"],
                    help="verdict engine (core/stitch.py registry): "
                         "bonferroni = the classic sequential test, "
                         "evalue = anytime-valid e-process wealth "
                         "(core/evidence.py); evalue adds an 'evidence' "
                         "section with wealth trajectories to --json")
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "reference", "accelerated"],
                    help="test-kernel backend (stats/backends.py): "
                         "reference = pure-jnp, accelerated = Pallas "
                         "kernels, auto = accelerated on real TPU only")
    ap.add_argument("--retries", type=int, default=2)
    ap.add_argument("--inject", default=None, metavar="PLAN.json",
                    help="chaos-test the run under a deterministic fault "
                         "plan (core/faults.py JSON: seeded rules of kind "
                         "evict/corrupt/straggle/lose_worker); the --json "
                         "payload gains a 'faults' ledger. Recovered runs "
                         "are bitwise identical to the clean run "
                         "(DESIGN.md §12)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--resize-at", dest="resize_at", default=None,
                    help="comma-separated ROUND:WIDTH pairs — resize the "
                         "pool to WIDTH workers once ROUND rounds have "
                         "run (elastic re-meshing demo)")
    ap.add_argument("--json", dest="json_path", default=None,
                    help="write a machine-readable report to this path")
    ap.add_argument("--campaign", action="store_true",
                    help="generator-fleet screening: the --gen list x "
                         "--streams sub-streams screened in --waves "
                         "scales with knockout (core/campaign.py)")
    ap.add_argument("--streams", type=int, default=1,
                    help="sub-stream offsets per generator in a campaign "
                         "grid (requires counter-based generators)")
    ap.add_argument("--waves", default=None,
                    help="comma-separated wave scales for --campaign "
                         "(default: one wave at --scale)")
    ap.add_argument("--ledger", default=None,
                    help="campaign ledger path (resumable screening)")
    ap.add_argument("--no-stream-check", dest="stream_check",
                    action="store_false",
                    help="skip the pairstream seam phase of a campaign")
    ap.add_argument("--serve", action="store_true",
                    help="submit through the screening service: one "
                         "ticket per --gen entry, coalesced by the "
                         "admission batcher, memoized in the result "
                         "cache (repro.serve)")
    ap.add_argument("--serve-state", dest="serve_state", default=None,
                    help="serve state dir (result cache + batch "
                         "checkpoints; restart-resumable)")
    ap.add_argument("--serve-resubmit", dest="serve_resubmit",
                    action="store_true",
                    help="resubmit the first generator's spec after "
                         "completion (cache-hit demo: zero dispatches)")
    ap.add_argument("--serve-max-wait", dest="serve_max_wait",
                    type=float, default=0.0,
                    help="admission fairness bound (seconds) for --serve")
    args = ap.parse_args()
    if not args.serve:
        for flag, default, name in ((args.serve_state, None,
                                     "--serve-state"),
                                    (args.serve_resubmit, False,
                                     "--serve-resubmit"),
                                    (args.serve_max_wait, 0.0,
                                     "--serve-max-wait")):
            if flag != default:
                ap.error(f"{name} only applies with --serve")
    elif args.campaign or args.resize_at or args.ckpt:
        ap.error("--serve cannot combine with --campaign/--resize-at/"
                 "--ckpt (serve batches own their checkpoints under "
                 "--serve-state)")
    if not args.campaign:
        for flag, default, name in ((args.waves, None, "--waves"),
                                    (args.streams, 1, "--streams"),
                                    (args.ledger, None, "--ledger"),
                                    (args.stream_check, True,
                                     "--no-stream-check")):
            if flag != default:
                ap.error(f"{name} only applies with --campaign")
    if args.inject and (args.serve or args.campaign):
        ap.error("--inject only applies to the classic run path (serve/"
                 "campaign chaos testing is driven through the library: "
                 "SubmissionQueue(inject=...))")
    if args.adaptive:
        if args.policy not in ("lpt", "adaptive"):
            ap.error(f"--adaptive selects the adaptive schedule policy; "
                     f"it cannot be combined with --policy {args.policy}")
        args.policy = "adaptive"
    resize_at = {}
    if args.resize_at:
        try:
            for tok in args.resize_at.split(","):
                rnd, width = tok.strip().split(":")
                resize_at[int(rnd)] = int(width)
        except ValueError:
            ap.error(f"--resize-at wants ROUND:WIDTH[,ROUND:WIDTH...], "
                     f"got {args.resize_at!r}")
        if any(w < 1 for w in resize_at.values()):
            ap.error("--resize-at widths must be >= 1")

    # on a host without accelerators the CPU pool must cover the widest
    # point of the run
    from repro.common.runtime import cpu_devices, use_compile_cache
    cpu_devices(max([args.workers] + list(resize_at.values())))
    use_compile_cache()

    from repro.core import stitch                     # noqa: E402
    from repro.core.api import (                      # noqa: E402
        BatteryResult, CampaignSpec, PoolSession, RunSpec)
    from repro.core.battery import routing_report     # noqa: E402
    from repro.core.faults import FaultPlan           # noqa: E402
    from repro.core.policies import (                 # noqa: E402
        RetryBudgetExhausted, RetryPolicy)
    from repro.launch.mesh import make_pool_mesh      # noqa: E402

    from repro.stats import backends as kernel_backends  # noqa: E402

    # external-generator hooks run BEFORE any spec resolves names, so a
    # --gen entry a hook registers is indistinguishable from a built-in
    if args.register:
        import importlib                              # noqa: E402
        for hook in args.register:
            mod_name, sep, fn_name = hook.partition(":")
            if not sep or not mod_name or not fn_name:
                ap.error(f"--register wants PKG.MOD:FN, got {hook!r}")
            try:
                fn = getattr(importlib.import_module(mod_name), fn_name)
            except (ImportError, AttributeError) as exc:
                ap.error(f"--register {hook!r}: {exc}")
            fn()

    gens = (tuple(g.strip() for g in args.gen.split(",") if g.strip())
            if args.gen else ())
    source_specs = tuple(args.source or ())
    if not gens and not source_specs:
        gens = ("splitmix64",)
    positions = gens + source_specs
    session = PoolSession(mesh=make_pool_mesh(args.workers or None))

    if args.campaign:
        if args.adaptive or args.resize_at or args.ckpt:
            ap.error("--campaign cannot combine with --adaptive/"
                     "--resize-at/--ckpt (its own ledger handles resume)")
        from repro.core.campaign import Campaign      # noqa: E402
        waves = (tuple(float(w) for w in args.waves.split(","))
                 if args.waves else (args.scale,))
        cspec = CampaignSpec(
            args.battery, sources=positions, n_streams=args.streams,
            seed=args.seed, waves=waves, alpha=args.alpha,
            policy=args.policy,
            retry=RetryPolicy(max_retries=args.retries),
            backend=args.backend,
            stream_check=args.stream_check, ledger_path=args.ledger,
            progress=True, verdict_engine=args.verdict_engine)
        campaign = Campaign(session, cspec)
        print(f"campaign: {len(cspec.generators)} source(s) x {args.streams} "
              f"stream(s) | battery={args.battery} waves={waves} "
              f"span={campaign.span} policy={args.policy} "
              f"backend={args.backend}")
        res = campaign.run()
        print(res.report)
        print(f"\nwall={res.wall_s:.1f}s rounds={res.rounds_run} "
              f"traces={session.total_traces}")
        n_open = len(res.cells) - len(res.survivors) - len(res.knockouts)
        if args.json_path:
            payload = {
                "battery": args.battery, "workers": session.n_workers,
                "policy": args.policy, "backend": args.backend,
                "backend_resolved": kernel_backends.resolve(args.backend),
                "alpha": args.alpha, "seed": args.seed,
                "wall_s": round(res.wall_s, 3),
                "rounds_run": res.rounds_run,
                "campaign": {
                    "n_streams": args.streams, "waves": list(waves),
                    **({"sources": [
                        {"spec": raw, "uid": src.uid()}
                        for raw, src in zip(
                            source_specs,
                            cspec.sources[len(gens):])]}
                       if args.source else {}),
                    "span": campaign.span,
                    "phases": res.phase_names,
                    "stream_check": args.stream_check,
                    "survivors": len(res.survivors),
                    "knockouts": len(res.knockouts),
                    "undecided": n_open,
                    "cells": [
                        {"gen": g, "stream": s,
                         "decision": res.decision(g, s),
                         "phase": (int(res.decided_phase[i])
                                   if res.decided_phase[i] >= 0 else None)}
                        for i, (g, s) in enumerate(res.cells)],
                },
            }
            if args.verdict_engine != "bonferroni":
                # conditional section: golden-key consumers of the
                # classic campaign payload see exactly the historical keys
                payload["evidence"] = {
                    "engine": args.verdict_engine,
                    "threshold": 1.0 / args.alpha,
                    "continuations": res.continuations,
                    "cells": [
                        {"gen": g, "stream": s,
                         "wealth": float(res.wealth[i]),
                         "log_wealth": float(res.log_wealth[i])}
                        for i, (g, s) in enumerate(res.cells)]}
            os.makedirs(os.path.dirname(args.json_path) or ".",
                        exist_ok=True)
            with open(args.json_path, "w") as f:
                json.dump(payload, f, indent=2)
            print(f"json report -> {args.json_path}")
        # a completed campaign exits 0 (knockouts are the product, not an
        # error); undecided cells mean the screening did not finish
        sys.exit(0 if n_open == 0 else 1)
    launch_workers = session.n_workers          # width before any resize
    fault_plan = None
    if args.inject:
        try:
            fault_plan = FaultPlan.load(args.inject)
        except (OSError, ValueError, TypeError, KeyError) as exc:
            ap.error(f"--inject {args.inject!r}: {exc}")
    spec = RunSpec(args.battery, sources=positions, seeds=(args.seed,),
                   scale=args.scale, policy=args.policy,
                   retry=RetryPolicy(max_retries=args.retries),
                   checkpoint_path=args.ckpt, progress=True,
                   alpha=args.alpha, stop_on_verdict=args.adaptive,
                   verdict_engine=args.verdict_engine,
                   backend=args.backend, inject=fault_plan)
    names = spec.generators
    backend_resolved = kernel_backends.resolve(args.backend)
    print(f"pool: {session.n_workers} workers | battery={args.battery} "
          f"gen={','.join(names)} scale={args.scale} policy={args.policy} "
          f"backend={args.backend}"
          + (f"->{backend_resolved}" if args.backend == "auto" else "")
          + (f" adaptive(alpha={args.alpha})" if args.adaptive else ""))
    if backend_resolved == "accelerated":
        for line in routing_report(session.entries(spec)):
            print(f"  kernels {line}")

    resizes = []
    serve_info = None
    if args.serve:
        from repro.serve import SubmissionQueue       # noqa: E402
        queue = SubmissionQueue(session=session,
                                state_dir=args.serve_state,
                                max_wait=args.serve_max_wait)
        # one ticket per source position: independent clients whose
        # compatible specs the admission batcher coalesces into shared
        # dispatches
        gen_specs = [RunSpec(args.battery, sources=(p,),
                             seeds=(args.seed,), scale=args.scale,
                             policy=args.policy,
                             retry=RetryPolicy(max_retries=args.retries),
                             alpha=args.alpha,
                             stop_on_verdict=args.adaptive,
                             verdict_engine=args.verdict_engine,
                             backend=args.backend) for p in positions]
        tickets = [queue.submit(s) for s in gen_specs]
        queue.drain()
        runs = {g: t.result() for g, t in zip(names, tickets)}
        resubmit = None
        if args.serve_resubmit:
            before = queue.dispatch_rounds
            rticket = queue.submit(gen_specs[0])
            done_at_submit = rticket.done
            queue.drain()
            rticket.result()
            resubmit = {"ticket": rticket.id,
                        "cache_hits": rticket.cache_hits,
                        "done_at_submit": done_at_submit,
                        "dispatches_added": queue.dispatch_rounds - before}
            print(f"  resubmit {names[0]}: cache_hits="
                  f"{rticket.cache_hits} dispatches_added="
                  f"{resubmit['dispatches_added']}")
        stats = queue.stats()
        serve_info = {
            "state": args.serve_state, "max_wait": args.serve_max_wait,
            "tickets": [{"ticket": t.id, "gen": g, "state": t.state,
                         "batch": t.batch_id, "cache_hits": t.cache_hits}
                        for g, t in zip(names, tickets)],
            "batches": stats["batches"],
            "dispatch_rounds": stats["dispatch_rounds"],
            "cache": stats["cache"], "traces": stats["traces"],
            "resubmit": resubmit}
        print(f"serve: {len(tickets)} ticket(s) -> "
              f"{stats['batches']} batch(es), "
              f"{stats['dispatch_rounds']} dispatch round(s), "
              f"{stats['cache']['hits']} cache hit(s)")
        wall_s = max(r.wall_s for r in runs.values())
        rounds_run = max(r.rounds_run for r in runs.values())
        retries_total = max(r.retries for r in runs.values())
    else:
        handle = session.submit(spec)
        for rnd in sorted(resize_at):           # elastic re-meshing demo
            while handle.pending_rounds and handle.rounds_run < rnd:
                handle.poll()
            if handle.pending_rounds:
                session.resize(resize_at[rnd])
                resizes.append({"round": handle.rounds_run,
                                "workers": resize_at[rnd]})
                print(f"  resize: pool -> {resize_at[rnd]} workers after "
                      f"round {handle.rounds_run}")
        try:
            res = handle.result()
        except RetryBudgetExhausted as exc:
            print(f"error: {exc}", file=sys.stderr)
            sys.exit(2)
        multi = isinstance(res, BatteryResult)
        runs = res.runs if multi else {names[0]: res}
        wall_s, rounds_run = res.wall_s, res.rounds_run
        retries_total = res.retries
    for run in runs.values():
        print(run.report)
    for gen, run in runs.items():
        print(f"verdict[{gen}]: {run.verdict}")
    print(f"\nwall={wall_s:.1f}s rounds={rounds_run}"
          f"/{next(iter(runs.values())).plan_rounds}"
          f" retries={retries_total}")

    if args.json_path:
        entries = session.entries(spec)
        payload = {
            "battery": args.battery, "scale": args.scale,
            "workers": launch_workers, "policy": args.policy,
            "backend": args.backend,
            "backend_resolved": backend_resolved,
            "adaptive": args.adaptive, "alpha": args.alpha,
            "resizes": resizes,
            "seed": args.seed, "wall_s": round(wall_s, 3),
            "rounds_run": rounds_run, "retries": retries_total,
            "plan_rounds": next(iter(runs.values())).plan_rounds,
            "runs": {},
        }
        if serve_info is not None:
            payload["serve"] = serve_info
        if args.verdict_engine != "bonferroni":
            # only present under a non-default engine: the wealth
            # trajectories the anytime-valid verdicts were read off
            payload["evidence"] = {
                "engine": args.verdict_engine,
                "threshold": 1.0 / args.alpha,
                "runs": {gen: {"wealth": run.verdict.wealth,
                               "log_wealth": run.verdict.log_wealth,
                               "trajectory": list(run.verdict.trajectory)}
                         for gen, run in runs.items()}}
        if args.source:
            # only present when --source was used: golden-key consumers
            # of the classic payload see exactly the historical keys
            payload["sources"] = [
                {"spec": raw, "uid": src.uid()}
                for raw, src in zip(source_specs,
                                    spec.sources[len(gens):])]
        if args.inject:
            # only present under --inject (which forbids --serve, so
            # `handle` is guaranteed bound): the fault/quarantine ledger
            payload["faults"] = {
                "plan": fault_plan.to_dict(),
                "events": [e.to_dict() for e in handle.fault_events],
                "quarantines": list(handle.quarantines)}
        for gen, run in runs.items():
            tests = []
            for e in entries:
                stat, p = run.results.get(e.index, (None, None))
                suspect = (p is not None
                           and (p < stitch.SUSPECT_P
                                or p > 1 - stitch.SUSPECT_P))
                tests.append({"index": e.index, "name": e.name,
                              "stat": stat, "p": p, "suspect": suspect})
            v = run.verdict
            payload["runs"][gen] = {
                "suspects": run.n_suspect,
                "verdict": v.decision,
                "tests_checked": v.n_checked,
                "failed_tests": list(v.failed_tests),
                "rounds_run": run.rounds_run,
                "tests": tests}
        os.makedirs(os.path.dirname(args.json_path) or ".", exist_ok=True)
        with open(args.json_path, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"json report -> {args.json_path}")

    # classic contract: exit 1 iff any suspect p-value. An adaptive run may
    # have cancelled before producing suspects-in-report for every failed
    # generator, so there the sequential verdict also gates the exit code
    # (its alpha/2n boundary is looser than SUSPECT_P — applying it to
    # non-adaptive runs would contradict the printed report).
    suspects = sum(run.n_suspect for run in runs.values())
    failed = ((args.adaptive or args.verdict_engine != "bonferroni")
              and any(run.verdict.decision == "FAIL"
                      for run in runs.values()))
    sys.exit(0 if suspects == 0 and not failed else 1)


if __name__ == "__main__":
    main()
