"""Training launcher — a thin CLI over ``repro.api.TrainSession``.

Modes (the survey's taxonomy, selectable from the CLI; every flag below maps
onto a ``SyncStrategy`` = round scheduler × per-round reducer, DESIGN.md §7):

  * --sync vanilla                 BSP data-parallel, dense psum (baseline)
  * --sync comm                    every-step sync through --compressor/
                                   --algo/--bucket-mb/--no-error-feedback
  * --sync auto                    communication planner: profile one step,
                                   search (rounds schedule x per-bucket
                                   compressor x algo x fusion) against the
                                   --link α-β model, run the winning
                                   composite (DESIGN.md §6/§7)
  * --local-sgd TAU                periodic averaging (+ --post-local N);
                                   with --sync comm the averaging round
                                   itself is compressed (anchor-delta)
  * --lag THRESH                   lazily aggregated gradients (host
                                   dispatch; skipped rounds cost only the
                                   8-byte trigger probe)
  * --push-pull N_PUSH N_FETCH     Dean-style asymmetric push/pull cadences

Runs on whatever devices exist (CPU: 1-device mesh; the same code drives the
production mesh).  Example (the e2e driver, deliverable b):

    PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m --reduced \
        --steps 200 --batch 8 --seq 128 --sync comm --compressor topk --algo ring
"""
from __future__ import annotations

import argparse
import dataclasses
import os

from repro.api import SessionConfig, TrainSession
from repro.configs import ALL_ARCHS
from repro.core import (ParallelismSpec, SyncConfig, SyncStrategy,
                        get_scheduler, make_strategy)
from repro.core.schedule import LINK_PRESETS
from repro.launch.paths import use_compile_cache
from repro.launch.report import render_strategy_plan, save_strategy_plan


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ALL_ARCHS, default="xlstm-125m")
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized variant of the same family")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--optimizer", default="adam",
                    choices=["sgd", "adam", "lars", "lamb"])
    ap.add_argument("--data-parallel", type=int, default=0)
    ap.add_argument("--sync", default="vanilla",
                    choices=["vanilla", "comm", "auto"])
    ap.add_argument("--compressor", default="none")
    ap.add_argument("--algo", default="psum")
    ap.add_argument("--bucket-mb", type=float, default=32.0)
    ap.add_argument("--no-error-feedback", action="store_true")
    ap.add_argument("--topology", default="",
                    help="tiered network model (DESIGN.md §10): a spec "
                         "'node:4@datacenter,device:8@fast_ici' (outermost "
                         "tier first, @link names a --link preset) or a "
                         "TOPOLOGY_PRESETS name.  The planner prices every "
                         "collective phase on the tier it traverses and "
                         "searches pipe/tp/ep-axis placements; its world "
                         "is the tier-size product.  "
                         "When it matches this host's device count the "
                         "mesh is rebuilt one-axis-per-tier so collectives "
                         "dispatch axis→tier")
    ap.add_argument("--link", default="fast_ici", choices=sorted(LINK_PRESETS),
                    help="α-β regime the planner optimizes for (--sync "
                         "auto).  Legacy FLAT network shim: builds "
                         "Topology.flat; superseded by --topology")
    ap.add_argument("--alpha", type=float, default=None,
                    help="override link latency α in seconds (--sync auto; "
                         "flat shim, ignored under --topology)")
    ap.add_argument("--beta-gbps", type=float, default=None,
                    help="override link bandwidth in GB/s (--sync auto; "
                         "flat shim, ignored under --topology)")
    ap.add_argument("--plan-backward-ms", type=float, default=0.0,
                    help="plan for this per-step backward time instead of "
                         "measuring (model a TPU's backward from a laptop; "
                         "--sync auto)")
    ap.add_argument("--compression-costs", default="", metavar="PATH",
                    help="measured per-compressor encode/decode cost table "
                         "(JSON recorded by benchmarks/bench_collectives.py "
                         "--write-compression-costs); replaces the analytic "
                         "compression-compute term in --sync auto's model "
                         "(DESIGN.md §11)")
    ap.add_argument("--parallelism", default="", metavar="SPEC",
                    help="the whole parallelism axis in one spec "
                         "(DESIGN.md §14): "
                         "'dp=4,tp=2@device,pp=2@node,micro=8,shard' — "
                         "dp/tp/pp/ep group sizes with optional @tier "
                         "placements (tier names from --topology), plus "
                         "the micro=M and shard tokens.  Subsumes the "
                         "deprecated --shard-state/--pipeline-stages/"
                         "--micro-batches trio; under --sync auto the "
                         "planner prices every arm but only spec-matching "
                         "arms may win (impossible specs fail loudly)")
    ap.add_argument("--shard-state", action="store_true",
                    help="DEPRECATED shim for --parallelism '...,shard'. "
                         "Sharded data parallelism (ZeRO-style): gradients "
                         "reduce-scatter per bucket, optimizer moments + "
                         "f32 master params partitioned 1/p over the data "
                         "axes, params all-gathered on the forward edge")
    ap.add_argument("--memory-budget-gb", type=float, default=None,
                    help="per-worker optimizer-state budget for --sync auto"
                         ": arms that do not fit are dropped, which is how "
                         "the shard axis wins (it never wins on wall clock)")
    ap.add_argument("--pipeline-stages", type=int, default=1, metavar="S",
                    help="DEPRECATED shim for --parallelism 'pp=S'. "
                         "Pipeline parallelism (DESIGN.md §9): cut the "
                         "model into S stages on a pipe x data mesh and "
                         "run 1F1B micro-batching; the gradient sync "
                         "(--compressor/--algo, or the planner's pick "
                         "under --sync auto) runs on the DP dimension "
                         "only, per layer row")
    ap.add_argument("--micro-batches", type=int, default=0, metavar="M",
                    help="DEPRECATED shim for --parallelism 'micro=M'. "
                         "Micro-batches per step (default: 8 in pipeline "
                         "mode, 1 otherwise; bubble fraction "
                         "(S-1)/(S-1+M); the global batch must split into "
                         "DP shards x M).  M>1 with --pipeline-stages 1 "
                         "runs micro-batched gradient accumulation "
                         "through the same executor")
    ap.add_argument("--local-sgd", type=int, default=0, metavar="TAU")
    ap.add_argument("--post-local", type=int, default=0)
    ap.add_argument("--lag", type=float, default=0.0, metavar="THRESH")
    ap.add_argument("--push-pull", type=int, nargs=2, default=None,
                    metavar=("N_PUSH", "N_FETCH"),
                    help="push gradients every N_PUSH steps, fetch (average) "
                         "parameters every N_FETCH steps")
    ap.add_argument("--calibrate", action="store_true",
                    help="time real collectives on this host's mesh before "
                         "planning and fit per-tier α/β (with confidence "
                         "bounds) — --sync auto then prices every arm on "
                         "the FITTED fabric instead of the presets, and "
                         "the plan record gains calibration + drift blocks")
    ap.add_argument("--replan-drift-pct", type=float, default=0.0,
                    metavar="PCT",
                    help="re-run the planner mid-training when the "
                         "measured step time drifts more than PCT%% from "
                         "the modeled wall step (checked every "
                         "--replan-every steps; 0 = off, the default)")
    ap.add_argument("--replan-every", type=int, default=25,
                    help="steps between drift checks for "
                         "--replan-drift-pct (default 25)")
    ap.add_argument("--elastic", action="store_true",
                    help="supervised fault-tolerant step loop (DESIGN.md "
                         "§15): survive worker preemption by resharding "
                         "through the portable checkpoint — no process "
                         "restart — and demote the sync cadence under "
                         "stragglers.  Requires --topology (its world is "
                         "the fleet the fault trace runs against); "
                         "composes with vanilla/comm/auto and pinned "
                         "rounds schedulers, not with pipeline stages")
    ap.add_argument("--fault-trace", default="", metavar="SPEC_OR_PATH",
                    help="deterministic fault schedule for --elastic: a "
                         "compact spec 'kill:3@5,slow:1x4@3,restore:3@9' "
                         "(kind:worker[xfactor]@step) or a path to a JSON "
                         "trace file (FaultSchedule.to_json).  Empty = "
                         "no faults (the supervised loop still runs)")
    ap.add_argument("--checkpoint", default="")
    ap.add_argument("--log-every", type=int, default=10)
    return ap.parse_args(argv)


def scheduler_from_args(args):
    """The rounds axis a user pinned explicitly (None -> every step, or the
    planner's choice under --sync auto)."""
    picked = [f for f, on in (("--lag", args.lag > 0),
                              ("--local-sgd", args.local_sgd > 1),
                              ("--push-pull", args.push_pull is not None))
              if on]
    if len(picked) > 1:
        raise SystemExit(f"pick one rounds schedule, got {picked}")
    if args.lag > 0:
        return get_scheduler("lag", threshold=args.lag)
    if args.local_sgd > 1:
        return get_scheduler("local_sgd", period=args.local_sgd,
                             post_local_after=args.post_local)
    if args.push_pull is not None:
        return get_scheduler("push_pull", n_push=args.push_pull[0],
                             n_fetch=args.push_pull[1])
    return None


def resolve_cli_parallelism(args):
    """Fold the CLI's parallelism surface — the unified ``--parallelism``
    spec and the deprecated ``--shard-state``/``--pipeline-stages``/
    ``--micro-batches`` shims — into ``(par_spec, shard, pipe, micro)``.
    Mixing the spec with a shim is a loud SystemExit; a shim alone warns
    and builds the equivalent spec via :meth:`ParallelismSpec.legacy`."""
    legacy_used = [f for f, on in
                   (("--shard-state", args.shard_state),
                    ("--pipeline-stages", args.pipeline_stages != 1),
                    ("--micro-batches", args.micro_batches != 0)) if on]
    if args.parallelism:
        if legacy_used:
            raise SystemExit(
                f"--parallelism subsumes {', '.join(legacy_used)}; fold "
                f"them into the spec (e.g. 'dp=4,pp=2,micro=8,shard')")
        try:
            par_spec = ParallelismSpec.from_spec(args.parallelism)
        except ValueError as e:
            raise SystemExit(f"--parallelism: {e}")
        if par_spec.pp > 1 and not par_spec.micro_batches:
            # the executor's pipeline default (bubble (S-1)/(S-1+M))
            par_spec = dataclasses.replace(par_spec, micro_batches=8)
        return (par_spec, par_spec.shard_state, par_spec.pp,
                par_spec.micro_batches or 1)
    if legacy_used:
        print(f"warning: {', '.join(legacy_used)} deprecated; use "
              f"--parallelism (e.g. 'dp=4,pp=2,micro=8,shard')",
              flush=True)
    shard = args.shard_state
    pipe = args.pipeline_stages
    if pipe < 1:
        raise SystemExit(f"--pipeline-stages must be >= 1, got {pipe}")
    micro = args.micro_batches or (8 if pipe > 1 else 1)
    if pipe > 1 and shard:
        raise SystemExit("--pipeline-stages and --shard-state are "
                         "competing answers to the optimizer-memory "
                         "axis; pick one (DESIGN.md §9)")
    par_spec = ParallelismSpec.legacy(shard_state=shard,
                                      pipeline_stages=pipe,
                                      micro_batches=micro)
    return par_spec, shard, pipe, micro


def run_elastic(args, scfg):
    """``--elastic``: drive the session through the supervised
    fault-tolerant loop instead of a bare ``run()``.  Fresh sessions (and
    fresh scheduler instances — backpressure mutates scheduler config)
    come from a factory so resharding rebuilds from scratch every time."""
    import tempfile

    from repro.elastic import ElasticConfig, ElasticRuntime, FaultSchedule
    from repro.launch.report import render_elastic_events

    if not args.topology:
        raise SystemExit("--elastic needs --topology: the tier-size "
                         "product is the fleet the fault trace runs "
                         "against")
    _, shard, pipe, micro = resolve_cli_parallelism(args)
    if pipe > 1 or micro > 1:
        raise SystemExit("--elastic resharding composes with replicated "
                         "and sharded DP; pipeline/micro-batched builds "
                         "cannot restore mid-run (DESIGN.md §15)")

    def factory():
        s = TrainSession(SessionConfig(**dataclasses.asdict(scfg)))
        scheduler = scheduler_from_args(args)
        if args.sync == "comm":
            sync_cfg = SyncConfig(
                compressor=args.compressor, algo=args.algo,
                error_feedback=not args.no_error_feedback,
                bucket_bytes=int(args.bucket_mb * 2**20))
            s.strategy = make_strategy(
                scheduler if scheduler is not None else "every_step",
                axes=s.axes, sync=sync_cfg)
        elif scheduler is not None:
            s.strategy = SyncStrategy(scheduler=scheduler)
        return s

    from repro.core.schedule import Topology
    topo = Topology.from_spec(args.topology)
    trace = args.fault_trace
    if trace and os.path.exists(trace):
        schedule = FaultSchedule.from_json(trace)
        if schedule.world != topo.world:
            raise SystemExit(
                f"fault trace {trace} is against world={schedule.world} "
                f"but --topology {topo.spec()!r} has world={topo.world}")
    else:
        schedule = FaultSchedule.from_spec(trace, world=topo.world)
    cfg = ElasticConfig(
        topology=topo, checkpoint_dir=tempfile.mkdtemp(prefix="elastic_"),
        plan=(args.sync == "auto"), link=args.link,
        t_backward_s=(args.plan_backward_ms / 1e3
                      if args.plan_backward_ms > 0 else 0.05))
    rt = ElasticRuntime(factory, schedule, cfg)
    losses = rt.run(args.steps)
    print(render_elastic_events(rt.events), flush=True)
    if args.checkpoint:
        rt.session.save_checkpoint(args.checkpoint)
        print("checkpoint written:", args.checkpoint)
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f}) | "
          f"steps {rt.session.step}, comm rounds {rt.comm_rounds} "
          f"(grad {rt.grad_rounds}, param {rt.param_rounds}), "
          f"{len(rt.events)} elastic events")
    return rt.session, losses


def main(argv=None):
    """The training CLI; returns the run's losses."""
    return run(argv)[1]


def run(argv=None):
    """The whole CLI path: parse ``argv``, build the session and strategy,
    train, print the report.  Returns ``(session, losses)`` — under
    ``--elastic`` the runtime's current session."""
    args = parse_args(argv)
    use_compile_cache()
    scfg = SessionConfig(
        arch=args.arch, reduced=args.reduced, steps=args.steps,
        batch=args.batch, seq=args.seq, lr=args.lr, warmup=args.warmup,
        optimizer=args.optimizer, data_parallel=args.data_parallel)
    if args.elastic:
        return run_elastic(args, scfg)
    if args.fault_trace:
        raise SystemExit("--fault-trace only applies under --elastic")
    scheduler = scheduler_from_args(args)
    par_spec, shard, pipe, micro = resolve_cli_parallelism(args)
    if shard and scheduler is not None:
        raise SystemExit("shard_state partitions optimizer state, which "
                         "requires every-step gradient sync; drop "
                         "--local-sgd/--lag/--push-pull")
    pipe_mode = pipe > 1 or micro > 1
    if pipe_mode and scheduler is not None:
        raise SystemExit("pipeline stages / micro-batches require "
                         "every-step gradient sync; drop "
                         "--local-sgd/--lag/--push-pull")
    session = TrainSession(scfg)
    if args.topology:
        superseded = [f for f, on in (("--link", args.link != "fast_ici"),
                                      ("--alpha", args.alpha is not None),
                                      ("--beta-gbps",
                                       args.beta_gbps is not None))
                      if on]
        if superseded:
            print(f"warning: --topology models the network per tier; "
                  f"ignoring flat link flags {', '.join(superseded)}",
                  flush=True)
        topo = session.apply_topology(args.topology)
        if session.tiered_mesh:
            print(f"topology: {topo.spec()} (tiered mesh, axes "
                  f"{'x'.join(t.name for t in topo.tiers)})", flush=True)
        else:
            print(f"topology: {topo.spec()} (planning model; executing on "
                  f"the flat {session.world}-worker host mesh)", flush=True)

    if args.sync == "auto":
        ignored = []
        if args.compressor != "none":
            ignored.append("--compressor")
        if args.algo != "psum":
            ignored.append("--algo")
        if args.bucket_mb != 32.0:
            ignored.append("--bucket-mb")
        if args.no_error_feedback:
            ignored.append("--no-error-feedback")
        if ignored:
            print(f"warning: --sync auto chooses per-bucket strategies; "
                  f"ignoring {', '.join(ignored)}", flush=True)
        cal = None
        if args.calibrate:
            cal = session.calibrate()
            print(cal.describe(), flush=True)
        if args.parallelism and scheduler is not None:
            raise SystemExit("--parallelism pins arms of --sync auto's "
                             "free search; a pinned rounds scheduler "
                             "bypasses that search — drop one")
        plan_kw = dict(
            link=args.link, alpha=args.alpha, beta_gbps=args.beta_gbps,
            t_backward_s=(args.plan_backward_ms / 1e3
                          if args.plan_backward_ms > 0 else None),
            memory_budget_gb=args.memory_budget_gb,
            compression_costs=args.compression_costs or None,
            calibration=cal)
        if args.parallelism:
            sp = session.plan_auto(parallelism=par_spec, **plan_kw)
        else:
            sp = session.plan_auto(
                scheduler=scheduler,
                shard_state=(True if shard else None),
                pipeline_stages=(pipe if pipe > 1 else None),
                micro_batches=(micro if pipe > 1 else None),
                **plan_kw)
        if pipe <= 1 and micro > 1:
            # S=1 accumulation rides the winning arm when it composes
            session.apply_micro_batching(micro)
        print(render_strategy_plan(
            sp, arms=session.planned["arms"],
            baselines=session.planned["baselines"],
            t_backward_s=session.planned["t_backward_s"]), flush=True)
        plan_path = save_strategy_plan(sp, args.arch)
        print(f"plan record: {plan_path}", flush=True)
        best_fixed = min(p.modeled_step_s
                         for p in session.planned["baselines"].values())
        unconstrained = (scheduler is None and not shard
                         and args.memory_budget_gb is None and pipe <= 1
                         and par_spec.is_trivial)
        if unconstrained and sp.modeled_step_s > best_fixed + 1e-12:
            # a memory budget / pinned shard axis may legitimately force an
            # arm that is modeled slower than the replicated baselines —
            # the auto<=fixed guarantee holds only for the free search
            raise RuntimeError(
                f"planner regression: auto strategy modeled "
                f"{sp.modeled_step_s:.6f}s > best fixed baseline "
                f"{best_fixed:.6f}s")
    elif args.sync == "comm":
        sync_cfg = SyncConfig(
            compressor=args.compressor, algo=args.algo,
            error_feedback=not args.no_error_feedback,
            bucket_bytes=int(args.bucket_mb * 2**20))
        session.strategy = make_strategy(
            scheduler if scheduler is not None else "every_step",
            axes=session.axes, sync=sync_cfg, parallelism=par_spec)
    elif pipe_mode or shard or not par_spec.is_trivial:
        # vanilla + a parallelism spec: dense psum wires on the DP edge,
        # pipeline/micro-batching/partitioned state per the spec
        session.strategy = make_strategy(
            "every_step", axes=session.axes, parallelism=par_spec)
    elif scheduler is not None:
        # vanilla + an explicit rounds schedule: dense reducers
        session.strategy = SyncStrategy(scheduler=scheduler)
    # else: strategy None -> vanilla BSP (pjit, XLA collectives)

    if args.calibrate and args.sync != "auto":
        print("warning: --calibrate fits the link model --sync auto plans "
              "with; without --sync auto the fit is printed but unused",
              flush=True)
        print(session.calibrate().describe(), flush=True)
    if args.replan_drift_pct > 0:
        if args.sync != "auto" or scheduler is not None or pipe_mode \
                or shard:
            raise SystemExit("--replan-drift-pct re-runs the free planner "
                             "search; it requires --sync auto without a "
                             "pinned scheduler/pipeline/shard axis")
        session.enable_replan(args.replan_drift_pct,
                              check_every=args.replan_every)
    if session.strategy is not None:
        print(f"strategy: {session.strategy.describe()}", flush=True)
    losses = session.run(args.steps, log_every=args.log_every)
    drift = session.drift_report()
    if drift is not None and (args.calibrate or args.replan_drift_pct > 0):
        from repro.launch.report import render_drift_table
        print(render_drift_table(drift), flush=True)
        if args.sync == "auto":
            # re-write the record with the post-run calibration + drift
            # blocks (the pre-run write keeps the base schema)
            plan_path = save_strategy_plan(
                session.planned["strategy_plan"], args.arch,
                calibration=session.calibration, drift=drift)
            print(f"plan record (with drift): {plan_path}", flush=True)
    if getattr(session, "layout", None) is not None:
        from repro.launch.report import render_sharded_memory
        print(render_sharded_memory(session.layout, args.optimizer,
                                    moments=session.opt_moments),
              flush=True)
    if session.routed_tokens:
        from repro.launch.report import render_moe_drops
        print(render_moe_drops(session.dropped_tokens, session.routed_tokens,
                               session.model_cfg.capacity_factor),
              flush=True)
    if getattr(session, "staged", None) is not None:
        from repro.launch.report import render_pipeline_stages
        print(render_pipeline_stages(
            session.staged, session._params,
            session.strategy.micro_batches, moments=session.opt_moments),
            flush=True)

    if args.checkpoint:
        session.save_checkpoint(args.checkpoint)
        print("checkpoint written:", args.checkpoint)
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f}) "
          f"steps/s {args.steps / session.wall_s:.2f} | {session.summary()}")
    return session, losses


if __name__ == "__main__":
    main()
