"""TrainSession — the programmatic synchronization surface (DESIGN.md §7).

The survey's levers used to be hand-wired in ``launch/train.py``'s main():
rounds (§3.1 local SGD / LAG), bits (§3.2-3.3 compression / fusion / the
planner) and overlap each had a one-off code path, ``--lag`` was silently
dead, and there was no entry point for benchmarks, serving or tests.  A
session owns the pieces once:

    from repro.api import SessionConfig, TrainSession
    from repro.core import SyncConfig, make_strategy

    sess = TrainSession(SessionConfig(arch="xlstm-125m", reduced=True),
                        strategy=make_strategy("local_sgd", period=8,
                                               sync=SyncConfig(
                                                   compressor="int8",
                                                   algo="ring")))
    losses = sess.run(steps=50, log_every=10)
    print(sess.comm_rounds, "communication rounds over", sess.step, "steps")

or let the planner choose the whole composite (rounds × bits × overlap):

    sess = TrainSession(SessionConfig(arch="xlstm-125m", reduced=True))
    sp = sess.plan_auto(link="commodity", plan_world=256)
    print(sp.describe()); sess.run(steps=50)

The session compiles one program per strategy *phase* — the synced step, the
purely-local step, the parameter-round, LAG's probe/sync/reuse — and the
strategy's :class:`~repro.core.strategy.RoundScheduler` dispatches between
them host-side (exactly how LAG deploys on a real pod: data-dependent wire
traffic cannot live inside one SPMD program).  Communication rounds are
counted HONESTLY: a round is a collective that actually ran (gradient syncs
+ parameter rounds; LAG's 8-byte trigger probes are tallied separately as
``control_rounds``), which is the survey's Table 2 quantity.
"""
from __future__ import annotations

import dataclasses
import statistics
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import load_arrays as load_ckpt_arrays
from repro.checkpoint import save as save_ckpt
from repro.checkpoint.checkpoint import _flatten_with_paths
from repro.configs import get_config, reduced
from repro.core import (GradientSynchronizer, ParallelismSpec, PlanExecutor,
                        ShardLayout, SyncConfig, SyncStrategy, get_scheduler)
from repro.core.grad_sync import sharded_plan_from_config
from repro.core.pipeline import StagedModel
from repro.core.collectives import axes_for_topology
from repro.core.schedule import (LINK_PRESETS, CalibratedTopology,
                                 ExpertAxis, LinkParams, PipelineAxis,
                                 RoundSchedule, StrategyPlan, TensorAxis,
                                 Topology, calibrate_topology,
                                 drift_fraction, fixed_config_plan,
                                 modeled_wall_step_s, pipeline_arm,
                                 pipeline_placements, plan, plan_comm_error_s,
                                 plan_rounds, profiles_from_grads,
                                 resolve_calibration, resolve_cost_table,
                                 serial_round_plan)
from repro.core.schedule.planner import FIXED_BASELINES, local_sgd_arm
from repro.core.strategy import LocalSGDScheduler
from repro.data import DataConfig, SyntheticPipeline
from repro.launch.mesh import (data_axes, make_host_mesh, make_pipe_mesh,
                               make_topology_mesh)
from repro.launch.steps import (_make_synced_train_step, _world_of,
                                broadcast_worker_state, make_lag_programs,
                                make_local_train_step, make_param_round_step,
                                make_pipeline_train_step,
                                make_sharded_train_step, make_train_step,
                                merge_opt_rows, worker_view)
from repro.models import Model
from repro.models.sharding_ctx import set_mesh_ctx
from repro.optim import make_optimizer, make_sharded_optimizer, warmup_cosine


@dataclasses.dataclass
class SessionConfig:
    """What to train (model/optimizer/data); HOW to synchronize is the
    strategy, passed separately."""
    arch: str = "xlstm-125m"
    reduced: bool = False
    steps: int = 100            # LR-schedule horizon and default run length
    batch: int = 8
    seq: int = 128
    lr: float = 3e-3
    warmup: int = 20
    optimizer: str = "adam"
    data_parallel: int = 0      # 0 -> all devices
    seed: int = 0


def strategy_from_plan(sp: StrategyPlan,
                       axes: Sequence[str] = ("data",)) -> SyncStrategy:
    """Instantiate the executable strategy a planner composite describes."""
    if sp.schedule.kind == "local_sgd":
        return SyncStrategy(
            scheduler=get_scheduler("local_sgd", period=sp.schedule.period),
            param_reducer=PlanExecutor(sp.comm, tuple(axes)))
    if sp.pipeline_stages > 1:
        # the arm's comm plan describes the DP edge of the modeled heavy
        # stage; execution re-derives a per-row plan on the live stage
        # pytree from the arm's dominant (compressor, algo) choice — the
        # reference executor's granularity contract (DESIGN.md §9)
        dom = max(sp.comm.buckets, key=lambda b: b.bucket_bytes)
        return SyncStrategy(
            scheduler=get_scheduler("every_step"),
            grad_reducer=GradientSynchronizer(
                SyncConfig(compressor=dom.compressor,
                           compressor_args=dom.compressor_args,
                           algo=dom.algo, bucket_bytes=0), tuple(axes)),
            parallelism=sp.parallelism)
    # tp/ep winners execute their DP edge here (the model axes need a
    # tp×data / ep×data mesh; on this host they are planning + record
    # axes, validated bit-exactly by the multi-device checks) — the
    # strategy still CARRIES the spec so records and describe() are honest
    return SyncStrategy(scheduler=get_scheduler("every_step"),
                        grad_reducer=PlanExecutor(sp.comm, tuple(axes)),
                        parallelism=sp.parallelism)


# Compile events, tallied process-wide by one listener; a session takes the
# deltas around its own program calls (``TrainSession.compile_s``).  A
# backend-compile event is a compile, or a load when the persistent cache
# served it (a ``cache_hits`` event fires inside it).
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_COMPILE_TALLY = {"seconds": 0.0, "backend": 0, "hits": 0}
_compile_listener = []


def _on_compile_duration(event: str, duration: float, **kw) -> None:
    if event == _BACKEND_COMPILE:
        _COMPILE_TALLY["seconds"] += duration
        _COMPILE_TALLY["backend"] += 1


def _on_compile_event(event: str, **kw) -> None:
    if event == _CACHE_HIT:
        _COMPILE_TALLY["hits"] += 1


def _listen_to_compiles() -> None:
    if not _compile_listener:
        jax.monitoring.register_event_duration_secs_listener(
            _on_compile_duration)
        jax.monitoring.register_event_listener(_on_compile_event)
        _compile_listener.append(True)


def _collapse_mean(tree):
    """Collapse per-worker state (leading world axis, the diverging-
    scheduler carry) to its consensus view: the mean for inexact leaves —
    exactly the parameter-averaging round a local scheduler would run
    next — and worker 0 for integer/bool leaves (step counters etc.,
    identical across workers by construction)."""
    def one(x):
        if jnp.issubdtype(x.dtype, jnp.inexact):
            return jnp.mean(x, axis=0).astype(x.dtype)
        return x[0]
    return jax.tree.map(one, tree)


class TrainSession:
    """One training run driven by a :class:`SyncStrategy`.

    ``strategy=None`` is the vanilla BSP baseline (pjit, XLA-inserted
    collectives).  Everything else goes through the scheduler-dispatched
    phase programs.  Rounds accounting: ``grad_rounds`` (gradient syncs),
    ``param_rounds`` (parameter averaging), ``control_rounds`` (LAG scalar
    probes); ``comm_rounds = grad_rounds + param_rounds``.

    Counters: ``dropped_tokens`` / ``routed_tokens``, the MoE token
    choices dropped to capacity overflow and routed, come back from the
    device with each step's loss; ``compile_s`` / ``compiles`` /
    ``cache_hits`` tally the compiles (and persistent-cache loads) of the
    session's step programs, and ``programs`` keeps each compiled program
    by phase name (``base``, ``sync``, ``local``, ``probe``, ``reuse``,
    ``param_round``) for inspection.  Each step runs inside ``jax.profiler``
    spans on the device trace's clock: ``repro.step`` (a step annotation)
    around ``repro.input`` (batch, placement, step index and rng),
    ``repro.probe`` (LAG), ``repro.dispatch`` (the step program's call),
    ``repro.param_round``, ``repro.loss_wait`` and ``repro.counters``,
    each with its ``step``; the first step's ``_build`` runs in
    ``repro.build``.  With no profiler running they cost next to nothing.
    """

    def __init__(self, cfg: Optional[SessionConfig] = None,
                 strategy: Optional[SyncStrategy] = None):
        self.cfg = cfg or SessionConfig()
        self.strategy = strategy
        c = self.cfg
        model_cfg = get_config(c.arch)
        if c.reduced:
            model_cfg = reduced(model_cfg)
        self.model_cfg = model_cfg
        self.model = Model(model_cfg)
        n_dev = len(jax.devices())
        dp = c.data_parallel or n_dev
        self.mesh = make_host_mesh(data=dp, model=n_dev // dp)
        set_mesh_ctx(self.mesh, ("data",))
        self.axes = data_axes(self.mesh)
        self.world = _world_of(self.mesh, self.axes)
        lr = warmup_cosine(c.lr, c.warmup, c.steps)
        self._lr = lr          # schedule, reused by the sharded optimizer
        self.optimizer = make_optimizer(c.optimizer, lr=lr)
        self.data = SyntheticPipeline(DataConfig(
            vocab_size=model_cfg.vocab_size, seq_len=c.seq,
            global_batch=c.batch,
            embedding_dim=model_cfg.d_model if model_cfg.embedding_inputs
            else 0))
        self.rng = jax.random.PRNGKey(c.seed)
        self._params = self.model.init(self.rng)
        self._opt_state = self.optimizer.init(self._params)
        # measured f32 moment buffers per parameter (sgd with momentum=0.0
        # carries none; the planner's per-name default would over-count) —
        # feeds the memory model and the per-worker report
        n_elems = sum(l.size for l in jax.tree.leaves(self._params))
        self.opt_moments = (sum(l.size for l in
                                jax.tree.leaves(self._opt_state))
                            / max(n_elems, 1))

        self.step = 0
        self.losses: List[float] = []
        self.grad_rounds = 0
        self.param_rounds = 0
        self.control_rounds = 0
        # MoE capacity overflow must not vanish silently (DESIGN.md §14):
        # the step programs return the counts with the loss
        self.dropped_tokens = 0.0
        self.routed_tokens = 0.0
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.programs: Dict[str, Any] = {}
        _listen_to_compiles()
        self.planned: Optional[Dict[str, Any]] = None
        self.layout: Optional[ShardLayout] = None   # set by sharded builds
        self.staged: Optional[StagedModel] = None   # set by pipeline builds
        self.topology: Optional[Topology] = None    # set by apply_topology
        self.tiered_mesh = False     # True when the mesh IS one-axis-per-tier
        self.calibration: Optional[CalibratedTopology] = None
        self.step_times: List[float] = []      # per-step wall time (run())
        self.replans = 0
        self.replan_events: List[Dict[str, Any]] = []
        self._t_backward_spread_s = 0.0        # profile_backward repeat spread
        self._replan_drift_pct = 0.0           # 0 = replanning off
        self._replan_every = 25
        self._max_replans = 1
        self._window: List[float] = []         # step times since last check
        self._plan_kwargs: Optional[Dict[str, Any]] = None
        self._restore_opt: Optional[Dict[str, Any]] = None  # load_checkpoint
        self._built = False

    # -- state views ---------------------------------------------------------

    @property
    def comm_rounds(self) -> int:
        """Collective rounds that actually ran (survey Table 2)."""
        return self.grad_rounds + self.param_rounds

    @property
    def _diverging(self) -> bool:
        return (self.strategy is not None
                and self.strategy.scheduler.diverges_params)

    @property
    def params(self):
        if self.staged is not None:
            return self.staged.merge(self._params["shared"],
                                     self._params["rows"])
        return worker_view(self._params) if (self._built and self._diverging) \
            else self._params

    @property
    def opt_state(self):
        return worker_view(self._opt_state) \
            if (self._built and self._diverging) else self._opt_state

    @property
    def sync_state(self):
        """Grad-reducer state (EF residuals etc.), worker-0 view."""
        if getattr(self, "_sync_state", None) is None:
            return None
        return worker_view(self._sync_state)

    # -- auto planning (rounds × bits × overlap) -----------------------------

    def resolve_link(self, link="fast_ici", alpha=None,
                     beta_gbps=None) -> LinkParams:
        lp = LINK_PRESETS[link] if isinstance(link, str) else link
        a = lp.alpha_s if alpha is None else alpha
        b = lp.beta_s_per_byte if beta_gbps is None \
            else 1.0 / (beta_gbps * 1e9)
        return LinkParams(alpha_s=a, beta_s_per_byte=b)

    def apply_topology(self, topology) -> Topology:
        """Install a tiered network model (``--topology``, DESIGN.md §10).

        ``topology`` is a :class:`Topology`, a spec string
        (``"node:4@datacenter,device:8@fast_ici"``), or a
        ``TOPOLOGY_PRESETS`` name.  The planner then prices every arm on
        it (its world REPLACES ``plan_world``).  When the topology's
        world matches this host's devices (pure DP — no model axis), the
        session mesh is rebuilt with one axis per tier so collectives
        actually dispatch axis→tier — hierarchical's inner ring runs on
        the fast-tier axis (``collectives.axes_for_topology``); otherwise
        the topology stays a planning model (a pod modeled from a
        laptop) and execution keeps the flat host mesh."""
        if self._built:
            raise RuntimeError("apply_topology must run before the first "
                               "step")
        topo = Topology.from_spec(topology) if isinstance(topology, str) \
            else topology
        self.topology = topo
        n_dev = len(jax.devices())
        self.tiered_mesh = (topo.n_tiers > 1 and topo.world == n_dev
                            and self.cfg.data_parallel in (0, n_dev))
        if self.tiered_mesh:
            self.mesh = make_topology_mesh(topo)
            set_mesh_ctx(self.mesh, tuple(t.name for t in topo.tiers))
            self.axes = axes_for_topology(topo)
            self.world = topo.world
        return topo

    def profile_backward(self, repeats: int = 3) -> float:
        """Wall time of the PER-DEVICE backward (compile excluded): the
        planned shard_map step computes global_batch / world per device, so
        time that slice — timing the full global batch would inflate
        t_backward by the data-parallel factor and make the planner
        over-hide communication.  bwd ≈ 2/3 of a grad step.  Min-of-N
        (the calibration timing policy, DESIGN.md §13); the repeat spread
        is kept as ``_t_backward_spread_s``, the measurement-error term
        of the drift report's fit bound."""
        grad_fn = jax.jit(lambda p, b: jax.grad(self.model.loss)(p, b))
        batch = jax.tree.map(jnp.asarray, self.data.batch(0))
        n_global = jax.tree.leaves(batch)[0].shape[0]
        per_dev = max(1, n_global // self.world)
        batch = jax.tree.map(lambda x: x[:per_dev], batch)
        jax.block_until_ready(grad_fn(self._params, batch))   # compile
        times = []
        for _ in range(max(repeats, 1)):
            t0 = time.time()
            jax.block_until_ready(grad_fn(self._params, batch))
            times.append(time.time() - t0)
        self._t_backward_spread_s = (max(times) - min(times)) * (2.0 / 3.0)
        return min(times) * (2.0 / 3.0)

    def calibrate(self, sizes=None, repeats=None,
                  timer=None) -> CalibratedTopology:
        """Measure THIS host's collective fabric and fit per-tier α/β
        with confidence bounds (``--calibrate``, DESIGN.md §13).  On a
        tiered mesh (``apply_topology`` matched the device count) each
        tier's axis is timed separately; otherwise the flat fabric over
        all local devices is fitted — and if a planning-only topology was
        requested, the calibration measures the host, not the model, so
        say so.  The result is stored as ``self.calibration`` and feeds
        :meth:`plan_auto` via ``calibration=``."""
        from repro.core.schedule.calibration import (CAL_LINK_REPEATS,
                                                     CAL_LINK_SIZES)
        if self.topology is not None and not self.tiered_mesh:
            print(f"note: --calibrate times the HOST fabric "
                  f"({len(jax.devices())} device(s)), not the planning "
                  f"topology {self.topology.spec()}", flush=True)
        topo = self.topology if self.tiered_mesh else None
        kw: Dict[str, Any] = {
            "sizes": sizes if sizes is not None else CAL_LINK_SIZES,
            "repeats": repeats if repeats is not None else CAL_LINK_REPEATS,
        }
        if timer is not None:
            kw["timer"] = timer
            if topo is None and self.topology is not None:
                topo = self.topology    # injected timer: no mesh needed
        elif topo is not None:
            kw["mesh"] = self.mesh      # the tiered session mesh
        self.calibration = calibrate_topology(topo, **kw)
        return self.calibration

    def _pipeline_executable(self, S: int, M: int) -> bool:
        """Can pipeline(S, M) actually run on THIS host's devices/batch?
        (The modeled plan may target a pod via ``plan_world``.)"""
        n_dev = len(jax.devices())
        if S < 2 or n_dev % S:
            return False
        dp = self.cfg.data_parallel or n_dev // S
        if dp * S != n_dev or self.cfg.batch % dp:
            return False
        if (self.cfg.batch // dp) % M:
            return False
        try:
            StagedModel(self.model, S)
        except ValueError:
            return False
        return True

    def _model_axes(self, pipe_axis: PipelineAxis
                    ) -> Tuple[TensorAxis, Optional[ExpertAxis]]:
        """The tp/ep pricing axes for THIS model (DESIGN.md §14): tp pays
        4 activation allreduces per layer (Megatron wire); ep exists only
        for MoE stacks, dispatching top-k activation rows per token with
        ``expert_fraction`` measured from the analytic param count."""
        mc = self.model_cfg
        tensor_axis = TensorAxis(
            global_tokens=pipe_axis.global_tokens,
            bytes_per_token=pipe_axis.bytes_per_token,
            n_layers=mc.num_layers)
        expert_axis = None
        if mc.num_experts:
            n_moe = sum(1 for i in range(mc.num_layers)
                        if mc.layer_spec(i).ffn == "moe")
            if n_moe:
                ffm = mc.moe_d_ff or mc.d_ff
                expert_params = n_moe * 3 * mc.num_experts * mc.d_model * ffm
                frac = min(0.99, expert_params / max(mc.num_params(), 1))
                expert_axis = ExpertAxis(
                    global_tokens=pipe_axis.global_tokens,
                    bytes_per_token=float(mc.top_k * mc.d_model * 4),
                    n_moe_layers=n_moe, expert_fraction=frac)
        return tensor_axis, expert_axis

    def plan_auto(self, link="fast_ici", *, alpha=None, beta_gbps=None,
                  plan_world: int = 0, tau_grid=None, candidates=None,
                  scheduler=None, t_backward_s: Optional[float] = None,
                  shard_state: Optional[bool] = None,
                  memory_budget_gb: Optional[float] = None,
                  pipeline_stages: Optional[int] = None,
                  micro_batches: Optional[int] = None,
                  parallelism=None,
                  topology=None,
                  compression_costs=None,
                  calibration=None,
                  straggler_s: float = 0.0) -> StrategyPlan:
        """``--sync auto``: profile one step, search (rounds schedule ×
        per-bucket strategy × shard axis × parallelism axis), install the
        winning composite as this session's strategy.  ``scheduler`` pins
        the rounds axis (an explicit ``--local-sgd``/``--lag``/
        ``--push-pull`` choice) and only the per-bucket plan is searched.
        ``shard_state`` pins the shard axis (None = searched: sharded wins
        only when ``memory_budget_gb`` rules replicated optimizer state out
        — the gather tail never wins on wall clock alone).
        ``pipeline_stages``/``micro_batches`` pin the parallelism axis to
        pipeline(S, M); left None the free search prices pipeline arms too
        (DESIGN.md §9).  ``topology`` (or a prior :meth:`apply_topology`)
        replaces the flat link model with a tiered network — every arm is
        then priced per tier, the pipeline arms search axis placements,
        and the topology's world supersedes the deprecated ``plan_world``
        (a disagreement warns and prefers the topology).
        ``compression_costs`` — a
        :class:`~repro.core.schedule.cost.CompressionCostTable` or a path
        to one recorded by ``benchmarks/bench_collectives.py
        --write-compression-costs`` — replaces the analytic
        compression-compute term with MEASURED per-compressor fits in
        every arm (and in the fixed baselines, so the comparison stays
        apples-to-apples).  ``calibration`` — a
        :class:`~repro.core.schedule.CalibratedTopology` (from
        :meth:`calibrate` / ``--calibrate``) or a path to a saved one —
        replaces the preset link model with the FITTED fabric: a tiered
        calibration becomes the pricing topology outright; a flat one
        supplies the measured link (so an explicit ``plan_world`` still
        prices a hypothetical pod, on real α/β).  Stashes the full
        ``parallelism`` — a :class:`~repro.core.ParallelismSpec` or spec
        string (``"dp=4,tp=2@device"``) pinning the whole parallelism
        axis at once: the free search prices every arm but only arms
        matching the spec may win (impossible specs fail loudly inside
        ``plan_rounds``).  It subsumes the single-axis pins, so combining
        it with ``shard_state``/``pipeline_stages``/``micro_batches`` or
        a pinned ``scheduler`` is an error.  ``straggler_s`` (measured
        worst-vs-median step-time skew, the elastic runtime's signal)
        prices ``cost.straggler_penalty_s`` into every arm so a
        persistent straggler demotes the winning cadence (DESIGN.md §15).
        Stashes the full decision record in ``self.planned`` for
        reporting."""
        if self._built:
            raise RuntimeError("plan_auto must run before the first step")
        if parallelism is not None:
            if (shard_state is not None or pipeline_stages is not None
                    or micro_batches is not None):
                raise ValueError(
                    "parallelism= subsumes shard_state/pipeline_stages/"
                    "micro_batches — fold them into the spec "
                    "(e.g. 'dp=4,pp=2,micro=8,shard')")
            if scheduler is not None:
                raise ValueError(
                    "parallelism= pins arms of the planner's FREE search; "
                    "a pinned rounds scheduler bypasses that search — "
                    "drop one")
            parallelism = ParallelismSpec.coerce(parallelism)
        if topology is not None:
            self.apply_topology(topology)
        cal = resolve_calibration(calibration)
        cal_link = None
        if cal is not None:
            self.calibration = cal
            shape = [(t.name, t.size) for t in cal.topology.tiers]
            if self.topology is not None and \
                    [(t.name, t.size) for t in self.topology.tiers] != shape:
                print(f"warning: calibration measured "
                      f"{cal.topology.spec()} but the planning topology is "
                      f"{self.topology.spec()}; fitted links apply only to "
                      f"the fabric they were measured on — planning keeps "
                      f"the preset links", flush=True)
            elif cal.topology.is_flat and self.topology is None \
                    and plan_world and plan_world != cal.world:
                # hypothetical world, measured link: the fitted flat α/β
                # price the requested plan_world
                cal_link = cal.topology.innermost.link
            else:
                self.apply_topology(cal.topology)
        if scheduler is not None and shard_state:
            raise ValueError("shard_state composes only with the planner's "
                             "every-step arm, not a pinned rounds scheduler")
        if scheduler is not None and memory_budget_gb is not None:
            raise ValueError(
                "memory_budget_gb constrains the planner's FREE search "
                "over arms; a pinned rounds scheduler fixes the memory "
                "footprint, so the budget cannot be enforced — drop one")
        if pipeline_stages is not None and pipeline_stages > 1:
            if scheduler is not None or shard_state:
                raise ValueError("pipeline_stages composes with every-step "
                                 "replicated DP only (DESIGN.md §9)")
        if self.topology is not None:
            lp = self.topology
            world = lp.world
            if plan_world and plan_world != world:
                print(f"warning: plan_world={plan_world} disagrees with "
                      f"the topology ({lp.spec()} = world {world}); "
                      f"planning for the topology — plan_world is "
                      f"deprecated, the tier-size product wins", flush=True)
        else:
            lp = cal_link if cal_link is not None \
                else self.resolve_link(link, alpha, beta_gbps)
            world = plan_world or self.world
        if t_backward_s is None:
            t_backward_s = self.profile_backward()
        profiles = profiles_from_grads(self._params, t_backward_s)
        cost_table = resolve_cost_table(compression_costs)
        kw: Dict[str, Any] = {}
        if candidates is not None:
            kw["candidates"] = candidates
        if cost_table is not None:
            kw["cost_table"] = cost_table
        t_bwd = sum(p.t_backward_s for p in profiles)
        pipe_axis = PipelineAxis(
            global_tokens=float(self.cfg.batch * self.cfg.seq),
            bytes_per_token=float(self.model_cfg.d_model * 4))
        tensor_axis, expert_axis = self._model_axes(pipe_axis)
        mem_budget = (memory_budget_gb * 2**30
                      if memory_budget_gb is not None else None)

        def _stash(sg) -> Dict[str, Any]:
            # what _replan / replan_now re-runs with a fresh profile.
            # Pinned-scheduler sessions stash the FREE search (their pin
            # is a user preference, not an execution constraint), so a
            # straggler-priced re-plan can demote a pinned-LAG cadence
            # to local SGD mid-run (DESIGN.md §15).
            return {"lp": lp, "world": world,
                    "opt_name": self.cfg.optimizer, "shard_grid": sg,
                    "opt_moments": self.opt_moments,
                    "memory_budget_bytes": mem_budget,
                    "pipe_axis": pipe_axis, "tensor_axis": tensor_axis,
                    "expert_axis": expert_axis, "parallelism": parallelism,
                    "kw": dict(kw), "tau_grid": tau_grid,
                    "straggler_s": straggler_s}

        arms: Dict[str, StrategyPlan]
        if pipeline_stages is not None and pipeline_stages > 1:
            # pinned pipeline(S, M): price that arm, plan only its DP edge
            S = pipeline_stages
            M = micro_batches or 8
            # price at the requested world when it factors into pipe(S) x
            # data(>=2); otherwise at the smallest such world (a 1-device
            # demo still gets an honest modeled record)
            plan_w = world if (world % S == 0 and world // S >= 2) else 2 * S
            act = (pipe_axis.global_tokens / (plan_w // S) / M
                   * pipe_axis.bytes_per_token)
            net_p = lp
            if isinstance(lp, Topology) and (
                    plan_w != lp.world
                    or not pipeline_placements(lp, plan_w, S)):
                # the pinned S fits no tier (or the fallback world left
                # the topology behind): price flat on the outermost link
                print(f"note: pinned pipeline(S={S}) fits no tier of "
                      f"{lp.spec()}; pricing it flat on the outermost "
                      f"link", flush=True)
                net_p = lp.outermost.link
            best = pipeline_arm(
                profiles, net_p, plan_w, S, M, act,
                opt_name=self.cfg.optimizer,
                opt_moments=self.opt_moments, **kw)
            arms = {best.key: best}
            self.strategy = strategy_from_plan(best, self.axes)
        elif scheduler is None:
            shard_grid = ((False, True) if shard_state is None
                          else (bool(shard_state),))
            # replan hook re-runs exactly this search with a fresh profile
            self._plan_kwargs = _stash(shard_grid)
            best, arms = plan_rounds(
                profiles, lp, world,
                opt_name=self.cfg.optimizer, shard_grid=shard_grid,
                opt_moments=self.opt_moments,
                memory_budget_bytes=mem_budget,
                pipeline=pipe_axis, tensor=tensor_axis, expert=expert_axis,
                parallelism=parallelism, straggler_s=straggler_s,
                **dict(kw, **({"tau_grid": tau_grid}
                              if tau_grid is not None else {})))
            exec_best = best
            if best.pipeline_stages > 1 and not self._pipeline_executable(
                    best.pipeline_stages, best.micro_batches):
                # the modeled winner targets a pod this host cannot stage;
                # run the best arm that CAN execute here, keep the record
                fits = [a for a in arms.values()
                        if a.pipeline_stages <= 1
                        or self._pipeline_executable(a.pipeline_stages,
                                                     a.micro_batches)]
                exec_best = min(fits, key=lambda a: a.modeled_step_s)
                print(f"note: modeled winner {best.key} needs a "
                      f"pipe({best.pipeline_stages}) mesh this host cannot "
                      f"build; executing {exec_best.key} instead", flush=True)
            self.strategy = strategy_from_plan(exec_best, self.axes)
        elif isinstance(scheduler, LocalSGDScheduler):
            self._plan_kwargs = _stash((False,))
            rp = serial_round_plan(profiles, lp, world, **kw)
            best = local_sgd_arm(rp, t_bwd, scheduler.cfg.period)
            arms = {best.schedule.key: best}
            self.strategy = SyncStrategy(
                scheduler=scheduler,
                param_reducer=PlanExecutor(rp, tuple(self.axes)))
        else:
            # LAG / push-pull / every-step instance: the grad-sync rounds
            # get the overlap-planned per-bucket plan; the round COUNT is
            # the scheduler's (data-dependent for LAG), so the every-step
            # modeled time is an upper bound.  The schedule records the
            # scheduler actually executed, not every_step.
            self._plan_kwargs = _stash((False,))
            cp = plan(profiles, lp, world, **kw)
            best = StrategyPlan(
                schedule=RoundSchedule(kind=scheduler.name), comm=cp,
                modeled_step_s=cp.modeled_step_s,
                round_cost_s=cp.modeled_step_s, t_backward_s=t_bwd)
            arms = {best.schedule.key: best}
            self.strategy = SyncStrategy(
                scheduler=scheduler,
                grad_reducer=PlanExecutor(cp, tuple(self.axes)))

        baselines = {
            name: fixed_config_plan(profiles, lp, world, comp, algo,
                                    compressor_args=cargs,
                                    cost_table=cost_table)
            for name, (comp, algo, cargs) in FIXED_BASELINES.items()}
        self.planned = {"strategy_plan": best, "arms": arms,
                        "baselines": baselines,
                        "t_backward_s": t_backward_s,
                        "cost_table": cost_table}
        return best

    def apply_micro_batching(self, micro_batches: int) -> bool:
        """Attach S=1 micro-batched accumulation (the degenerate pipe) to
        the installed strategy — the ``--sync auto --micro-batches M``
        composition.  Composes with every-step replicated arms only; for
        other winners (local SGD, sharded, an already-pipelined arm) the
        request is declined with a printed reason rather than silently
        dropped.  Returns True when micro-batching will run."""
        if self._built:
            raise RuntimeError("apply_micro_batching must run before the "
                               "first step")
        M = int(micro_batches)
        st = self.strategy
        if M <= 1 or st is None:
            return M <= 1 and st is None
        if st.pipeline_stages > 1 or st.micro_batches > 1:
            return True                      # already micro-batched
        sched = st.scheduler
        if (sched.computes != frozenset({"sync"}) or sched.has_param_rounds
                or sched.needs_grad_probe or st.shard_state):
            print(f"note: micro-batching composes with every-step "
                  f"replicated sync only; chosen arm "
                  f"({st.describe()}) runs without it", flush=True)
            return False
        reducer = st.grad_reducer
        if isinstance(reducer, PlanExecutor):
            # re-derive a per-row config reducer (plans are tied to the
            # full-model pytree) from the plan's dominant bucket
            dom = max(reducer.plan.buckets, key=lambda b: b.bucket_bytes)
            reducer = GradientSynchronizer(
                SyncConfig(compressor=dom.compressor,
                           compressor_args=dom.compressor_args,
                           algo=dom.algo, bucket_bytes=0),
                tuple(self.axes))
        self.strategy = SyncStrategy(
            scheduler=sched, grad_reducer=reducer,
            parallelism=ParallelismSpec(micro_batches=M))
        return True

    # -- program construction ------------------------------------------------

    def _build(self) -> None:
        if self._built:
            return
        with jax.profiler.TraceAnnotation("repro.build"):
            self._build_programs()

    def _build_programs(self) -> None:
        self.programs = {}
        self._sync_state = None
        self._anchor = None
        self._red_state = None
        if self.strategy is None:
            # params/optimizer state replicated over the session mesh, the
            # batch split over its data axes: XLA inserts the gradient
            # all-reduce (without shardings everything ran on device 0)
            rep = NamedSharding(self.mesh, P())
            self._params = self._place(self._params, False)
            self._opt_state = self._place(self._opt_state, False)
            self._base = jax.jit(
                make_train_step(self.model, self.optimizer),
                in_shardings=(rep, rep, self._batch_sharding(), rep),
                out_shardings=(rep, rep, rep), donate_argnums=(0, 1))
            self._built = True
            return

        if self.strategy.pipeline_stages > 1 or \
                self.strategy.micro_batches > 1:
            # S=1 with micro-batches is the degenerate pipe: same 1F1B
            # executor, no boundary sends — plain gradient accumulation
            self._build_pipeline(self.strategy)
            self._built = True
            return

        if self.strategy.shard_state:
            self._build_sharded(self.strategy)
            self._built = True
            return

        st = self.strategy
        sched = st.scheduler
        self._sched_state = sched.init_state(self._params)
        engine = st.grad_reducer
        if engine is None and "sync" in sched.computes:
            engine = GradientSynchronizer(SyncConfig(), tuple(self.axes))

        if sched.needs_grad_probe:
            probe, sync_apply, reuse_apply = make_lag_programs(
                self.model, self.optimizer, engine, self.mesh, self.axes)
            # probe must NOT donate: params/batch are reused by the apply
            # program the scheduler dispatches afterwards
            self._probe = jax.jit(probe)
            self._sync = jax.jit(sync_apply, donate_argnums=(0, 1, 2, 3))
            self._reuse = jax.jit(reuse_apply, donate_argnums=(0, 1))
            self._sync_state = broadcast_worker_state(
                engine.init_state(self._params), self.world)
        elif "sync" in sched.computes:
            step_fn, _, init_sync_state = _make_synced_train_step(
                self.model, self.optimizer, engine, self.mesh, self.axes,
                per_worker_params=sched.diverges_params)
            self._sync = jax.jit(step_fn, donate_argnums=(0, 1, 2))
            self._sync_state = init_sync_state(self._params)
        if "local" in sched.computes:
            self._local = jax.jit(
                make_local_train_step(self.model, self.optimizer, self.mesh,
                                      self.axes),
                donate_argnums=(0, 1))
        if sched.has_param_rounds:
            self._param_round = jax.jit(
                make_param_round_step(st.param_reducer, self.mesh, self.axes,
                                      algo=st.param_algo),
                donate_argnums=(0, 1, 2))
            if st.param_reducer is not None:
                self._anchor = jax.tree.map(
                    lambda p: p.astype(jnp.float32), self._params)
                self._red_state = broadcast_worker_state(
                    st.param_reducer.init_state(self._params), self.world)
        if sched.diverges_params:
            self._params = broadcast_worker_state(self._params, self.world)
            self._opt_state = broadcast_worker_state(self._opt_state,
                                                     self.world)
        self._params = self._place(self._params, sched.diverges_params)
        self._opt_state = self._place(self._opt_state, sched.diverges_params)
        self._sync_state = self._place(self._sync_state, True)
        self._built = True

    def _place(self, tree, per_worker: bool):
        """Commit state to the session mesh in the layout the step programs
        return it in: whole on every device, or split over the data axes
        along a leading per-worker axis.  Left uncommitted, the first step
        compiles once for it and the second compiles again for the step's
        own outputs."""
        spec = P(tuple(self.axes)) if per_worker else P()
        return jax.device_put(tree, NamedSharding(self.mesh, spec))

    def _build_pipeline(self, st: SyncStrategy) -> None:
        """Pipeline-parallel programs (DESIGN.md §9): rebuild the mesh as
        ``pipe(S) × data``, split params into shared + per-stage layer rows,
        and compile the 1F1B step.  ``self._params`` becomes
        ``{"shared": ..., "rows": (S, R/S, ...)}`` (the ``params`` property
        merges it back); the DP gradient edge runs per LAYER ROW so
        compression granularity is stage-count invariant."""
        sched = st.scheduler
        if (sched.computes != frozenset({"sync"}) or sched.has_param_rounds
                or sched.needs_grad_probe or sched.diverges_params):
            raise ValueError(
                f"pipeline_stages requires an every-step gradient-sync "
                f"scheduler, got {sched.name!r}: local phases and gradient "
                f"reuse assume each worker holds the WHOLE model")
        S, M = st.pipeline_stages, st.micro_batches
        n_dev = len(jax.devices())
        if n_dev % S != 0:
            raise ValueError(f"{n_dev} devices do not factor into "
                             f"pipe({S}) x data")
        dp = self.cfg.data_parallel or n_dev // S
        if dp * S != n_dev:
            raise ValueError(f"data_parallel={dp} x pipeline_stages={S} "
                             f"!= {n_dev} devices")
        if self.cfg.batch % dp or (self.cfg.batch // dp) % M:
            raise ValueError(
                f"global batch {self.cfg.batch} must split into "
                f"{dp} DP shards x {M} micro-batches")
        self.mesh = make_pipe_mesh(S, dp)
        set_mesh_ctx(self.mesh, ("data",))
        self.axes = data_axes(self.mesh)
        self.world = dp
        self._sched_state = sched.init_state(self._params)
        self.staged = StagedModel(self.model, S)
        shared, rows = self.staged.split(self._params)
        self._params = {"shared": shared, "rows": rows}

        engine = st.grad_reducer
        if engine is None:
            engine = GradientSynchronizer(SyncConfig(), tuple(self.axes))
        elif isinstance(engine, GradientSynchronizer):
            # per-leaf buckets: the DP edge syncs per layer row, keeping
            # compression granularity identical for every stage count
            engine = GradientSynchronizer(
                dataclasses.replace(engine.cfg, bucket_bytes=0),
                tuple(self.axes))
        else:
            raise ValueError(
                "pipeline mode takes a SyncConfig-backed reducer (a "
                "CommPlan is tied to the full-model pytree; the stage "
                "pytree is per-row)")
        step_fn, init_opt_state, init_sync_state = make_pipeline_train_step(
            self.staged, self.optimizer, engine, self.mesh, M, self.axes)
        self._sync = jax.jit(step_fn, donate_argnums=(0, 1, 2))
        self._opt_state = init_opt_state(self._params)
        self._sync_state = init_sync_state(self._params)
        self._anchor = None
        self._red_state = None

    def _build_sharded(self, st: SyncStrategy) -> None:
        """Sharded-DP programs (DESIGN.md §8): the every-step sync program
        is replaced by ``make_sharded_train_step`` and ``self._opt_state``
        becomes the partitioned {master, moments} shard rows."""
        sched = st.scheduler
        if (sched.computes != frozenset({"sync"}) or sched.has_param_rounds
                or sched.needs_grad_probe or sched.diverges_params):
            raise ValueError(
                f"shard_state requires an every-step gradient-sync "
                f"scheduler, got {sched.name!r}: local phases (local_sgd/"
                f"push_pull) and gradient reuse (lag) need full per-worker "
                f"optimizer state by construction")
        self._sched_state = sched.init_state(self._params)
        engine = st.grad_reducer
        if engine is None:
            engine = PlanExecutor(
                sharded_plan_from_config(SyncConfig(), self._params),
                tuple(self.axes))
        elif isinstance(engine, GradientSynchronizer):
            engine = PlanExecutor(
                sharded_plan_from_config(engine.cfg, self._params),
                tuple(self.axes))
        axis_sizes = tuple(self.mesh.shape[a] for a in self.axes)
        self.layout = ShardLayout.from_plan(engine.plan, self._params,
                                            axis_sizes)
        shopt = make_sharded_optimizer(self.cfg.optimizer, self.layout,
                                       self.axes, lr=self._lr)
        step_fn, init_opt_rows, init_sync_state = make_sharded_train_step(
            self.model, engine, self.layout, shopt, self.mesh, self.axes)
        self._sync = jax.jit(step_fn, donate_argnums=(0, 1, 2))
        if self._restore_opt is not None:
            # elastic-resharding restore (DESIGN.md §15): re-partition the
            # checkpoint's LEAF-SHAPED optimizer state onto THIS layout —
            # the f32 master (synthesized from the restored params when
            # the checkpoint came from a replicated run) and each moment
            # tree become canonical shard rows via ``shard_rows``, which
            # is what makes an 8-world checkpoint land bit-equal on a
            # 6-rank fabric
            full = dict(self._restore_opt)
            master = full.pop("master", None)
            if master is None:
                master = jax.tree.map(lambda p: p.astype(jnp.float32),
                                      self._params)
            masters = self.layout.shard_rows(master)
            fresh = shopt.init(masters)
            if sorted(fresh) != sorted(full):
                raise ValueError(
                    f"checkpoint optimizer buffers {sorted(full)} do not "
                    f"match {self.cfg.optimizer!r}'s {sorted(fresh)}")
            self._opt_state = {
                "master": masters,
                "opt": {k: self.layout.shard_rows(full[k]) for k in fresh}}
            self._restore_opt = None
        else:
            self._opt_state = init_opt_rows(self._params)  # replaces replicated
        self._sync_state = init_sync_state(self._params)
        self._params = self._place(self._params, False)
        self._opt_state = self._place(self._opt_state, True)
        self._sync_state = self._place(self._sync_state, True)
        self._anchor = None
        self._red_state = None

    def full_opt_state(self):
        """Leaf-shaped view of the optimizer state: the replicated state
        as-is, or — in sharded mode — moments and the f32 master params
        reconstructed from the canonical shard rows (checkpoint
        portability / conformance testing).  In pipeline mode the per-stage
        (S, R/S, ...) moment rows are merged back to the stack's (R, ...)
        leaves, so the checkpoint does not pin the stage count."""
        if self._built and self.staged is not None:
            return merge_opt_rows(self._opt_state, self.staged.layout.rows)
        if not (self._built and self.strategy is not None
                and self.strategy.shard_state):
            return self.opt_state
        rows = self._opt_state
        full = {k: self.layout.tree_from_rows(v, self._params)
                for k, v in rows["opt"].items()}
        full["master"] = self.layout.tree_from_rows(rows["master"],
                                                    self._params)
        return full

    # -- stepping ------------------------------------------------------------

    def _batch_sharding(self) -> NamedSharding:
        """Global batch split over the data axes on its leading dim (whole
        on every device when it does not divide)."""
        split = self.cfg.batch % self.world == 0
        return NamedSharding(self.mesh,
                             P(tuple(self.axes)) if split else P())

    def _dispatch(self, name: str, fn, *args):
        """Call step program ``name`` and tally the compile events its call
        fires.  The first call compiles it ahead of time (the jitted call
        then finds it in JAX's caches) and keeps it in ``programs``."""
        t = dict(_COMPILE_TALLY)
        if name not in self.programs and hasattr(fn, "lower"):
            self.programs[name] = fn.lower(*args).compile()
        out = fn(*args)
        hits = _COMPILE_TALLY["hits"] - t["hits"]
        self.compile_s += _COMPILE_TALLY["seconds"] - t["seconds"]
        self.compiles += _COMPILE_TALLY["backend"] - t["backend"] - hits
        self.cache_hits += hits
        return out

    def step_once(self) -> float:
        """Run one training step under the strategy; returns the loss."""
        step = self.step
        with jax.profiler.StepTraceAnnotation("repro.step", step_num=step):
            self._build()
            with jax.profiler.TraceAnnotation("repro.input", step=step):
                batch = jax.device_put(self.data.batch(step),
                                       self._batch_sharding())
                step_i = jnp.asarray(step, jnp.int32)
                rng_s = jax.random.fold_in(self.rng, step)
            if self.strategy is None:
                with jax.profiler.TraceAnnotation("repro.dispatch",
                                                  step=step):
                    self._params, self._opt_state, out = self._dispatch(
                        "base", self._base, self._params, self._opt_state,
                        batch, step_i)
                self.grad_rounds += 1   # BSP syncs gradients every step
            else:
                out = self._strategy_step(step, batch, step_i, rng_s)
            with jax.profiler.TraceAnnotation("repro.loss_wait", step=step):
                for v in out.values():      # the counters come with it
                    v.copy_to_host_async()
                loss = float(out["loss"])
            if self.model_cfg.num_experts:
                with jax.profiler.TraceAnnotation("repro.counters",
                                                  step=step):
                    dropped, routed = jax.device_get(
                        (out["moe_dropped"], out["moe_routed"]))
                self.dropped_tokens += float(dropped)
                self.routed_tokens += float(routed)
        self.losses.append(loss)
        self.step += 1
        return loss

    def _strategy_step(self, step: int, batch, step_i, rng_s):
        """One step of the scheduler-dispatched phase programs; returns the
        loss output of the program that computed it."""
        sched = self.strategy.scheduler
        probe = None
        if sched.needs_grad_probe:
            with jax.profiler.TraceAnnotation("repro.probe", step=step):
                out_p, grads_w, delta, scale = self._dispatch(
                    "probe", self._probe, self._params, batch,
                    self._sched_state["g_last"])
                probe = {"delta": float(delta), "scale": float(scale)}
            self.control_rounds += 1
        action, self._sched_state = sched.round(step, self._sched_state,
                                                probe)
        synced = None
        with jax.profiler.TraceAnnotation("repro.dispatch", step=step):
            if action.compute == "sync":
                if sched.needs_grad_probe:
                    self._params, self._opt_state, self._sync_state, \
                        synced = self._dispatch(
                            "sync", self._sync, self._params,
                            self._opt_state, self._sync_state, grads_w,
                            step_i, rng_s)
                    out = out_p
                else:
                    self._params, self._opt_state, self._sync_state, out = \
                        self._dispatch("sync", self._sync, self._params,
                                       self._opt_state, self._sync_state,
                                       batch, step_i, rng_s)
                self.grad_rounds += 1
            elif action.compute == "reuse":
                self._params, self._opt_state = self._dispatch(
                    "reuse", self._reuse, self._params, self._opt_state,
                    self._sched_state["g_last"], step_i)
                out = out_p
            elif action.compute == "local":
                self._params, self._opt_state, out = self._dispatch(
                    "local", self._local, self._params, self._opt_state,
                    batch, step_i)
            else:
                raise ValueError(f"unknown action {action.compute!r}")
        if action.param_round:
            with jax.profiler.TraceAnnotation("repro.param_round",
                                              step=step):
                self._params, self._anchor, self._red_state = \
                    self._dispatch("param_round", self._param_round,
                                   self._params, self._anchor,
                                   self._red_state, rng_s)
            self.param_rounds += 1
        self._sched_state = sched.commit(self._sched_state, action, synced)
        return out

    @property
    def drop_fraction(self) -> float:
        """Fraction of routed token-choices dropped to capacity overflow
        so far (0.0 for dense models or before any step)."""
        return self.dropped_tokens / self.routed_tokens \
            if self.routed_tokens else 0.0

    def run(self, steps: Optional[int] = None, log_every: int = 0,
            log=print) -> List[float]:
        """Train ``steps`` steps (default: ``cfg.steps``); returns the
        losses of THIS run.  The step log reports honest round counts."""
        steps = steps or self.cfg.steps
        t0 = time.time()
        start = self.step
        out: List[float] = []
        for i in range(steps):
            pre_built = self._built      # a build step pays compile time
            ts = time.time()
            loss = self.step_once()
            dt = time.time() - ts
            self.step_times.append(dt)
            if pre_built:
                self._window.append(dt)
            self._maybe_replan()
            out.append(loss)
            if log_every and i % log_every == 0:
                dt = (time.time() - t0) / max(i, 1)
                drops = (f", dropped {self.drop_fraction * 100:.1f}%"
                         if self.routed_tokens else "")
                log(f"step {self.step - 1:5d} loss {loss:.4f} "
                    f"({dt * 1e3:.0f} ms/step, comm rounds "
                    f"{self.comm_rounds}{drops})", flush=True)
        self.wall_s = time.time() - t0
        self.steps_run = self.step - start
        return out

    # -- modeled vs measured -------------------------------------------------

    def measured_step_s(self) -> float:
        """Median wall time of the steps :meth:`run` executed, dropping
        the first (it pays compilation).  NaN before any steps ran."""
        times = self.step_times[1:] or self.step_times
        return statistics.median(times) if times else float("nan")

    def enable_replan(self, drift_pct: float, check_every: int = 25,
                      max_replans: int = 1) -> None:
        """Arm the drift-gated re-planning hook (``--replan-drift-pct``):
        every ``check_every`` post-compile steps, compare the window's
        median step time against the plan's modeled wall step; when the
        drift exceeds ``drift_pct`` percent, re-profile the backward pass
        and re-run the planner search.  Off by default (0 disarms)."""
        self._replan_drift_pct = float(drift_pct)
        self._replan_every = max(int(check_every), 2)
        self._max_replans = int(max_replans)

    def _modeled_wall_s(self) -> float:
        sp = self.planned.get("strategy_plan") if self.planned else None
        if sp is None:
            return float("nan")
        return modeled_wall_step_s(sp.modeled_step_s, sp.t_backward_s)

    def _maybe_replan(self) -> None:
        if (self._replan_drift_pct <= 0 or self.planned is None
                or len(self._window) < self._replan_every
                or self.replans >= self._max_replans):
            if len(self._window) >= self._replan_every:
                self._window.clear()
            return
        measured = statistics.median(self._window)
        self._window.clear()
        modeled = self._modeled_wall_s()
        if not modeled or modeled != modeled:
            return
        drift = drift_fraction(modeled, measured)
        if abs(drift) * 100.0 <= self._replan_drift_pct:
            return
        self._replan(drift, measured)

    def replan_now(self, straggler_s: float = 0.0,
                   t_backward_s: Optional[float] = None) -> Dict[str, Any]:
        """Force one re-plan outside the drift gate — the elastic
        runtime's straggler escalation (DESIGN.md §15): re-run the stashed
        planner search pricing every arm with
        ``cost.straggler_penalty_s(straggler_s, rounds/step)``, so a
        persistent straggler demotes the winning cadence (every-step pays
        the full skew per step; a local-SGD τ arm pays skew/τ) instead of
        stalling the bus.  ``t_backward_s`` skips the wall-clock backward
        re-profile (deterministic replans).  Returns the recorded event;
        requires a prior :meth:`plan_auto` (the stashed search)."""
        if self.planned is None:
            raise RuntimeError("replan_now needs a prior plan_auto")
        self._replan(0.0, self.measured_step_s(),
                     straggler_s=straggler_s, t_backward_s=t_backward_s)
        return self.replan_events[-1]

    def _replan(self, drift: float, measured_s: float,
                straggler_s: float = 0.0,
                t_backward_s: Optional[float] = None) -> None:
        """Re-run the stashed planner search with a FRESH backward profile
        (the measured fabric disagreed with the modeled one, or a
        straggler skew was reported).  The new winner is installed when
        neither the outgoing nor the incoming arm pins an execution shape
        that would strand state: no pipeline/micro-batch mesh and no shard
        rows on either side, and an incoming arm the session can rebuild
        from the live leaf-shaped params — plain every-step or local SGD.
        Rounds-schedule swaps (every_step↔local_sgd, LAG→either) ARE
        installed: an outgoing diverging scheduler's per-worker state is
        collapsed to its mean view first (counted as one parameter round —
        it IS the averaging round the scheduler owed), scheduler/EF state
        re-initializes on the rebuild.  Pipeline and sharded shapes still
        only record the recommendation."""
        event: Dict[str, Any] = {
            "step": self.step, "drift_frac": drift,
            "measured_step_s": measured_s,
            "old_key": self.planned["strategy_plan"].key,
            "applied": False, "note": ""}
        if straggler_s > 0.0:
            event["straggler_s"] = straggler_s
        pk = self._plan_kwargs
        if pk is None:
            event["note"] = ("no free-search plan to rerun (pinned "
                             "pipeline)")
            event["new_key"] = event["old_key"]
            self.replans += 1
            self.replan_events.append(event)
            return
        t_bwd = t_backward_s if t_backward_s is not None \
            else self.profile_backward()
        params = worker_view(self._params) if (self._built
                                               and self._diverging) \
            else self._params
        if self.staged is not None:
            params = self.params
        profiles = profiles_from_grads(params, t_bwd)
        extra = dict(pk["kw"])
        if pk["tau_grid"] is not None:
            extra["tau_grid"] = pk["tau_grid"]
        ss = straggler_s if straggler_s > 0.0 \
            else pk.get("straggler_s", 0.0)
        best, arms = plan_rounds(
            profiles, pk["lp"], pk["world"], opt_name=pk["opt_name"],
            shard_grid=pk["shard_grid"], opt_moments=pk["opt_moments"],
            memory_budget_bytes=pk["memory_budget_bytes"],
            pipeline=pk["pipe_axis"], tensor=pk["tensor_axis"],
            expert=pk["expert_axis"], parallelism=pk["parallelism"],
            straggler_s=ss, **extra)
        event["new_key"] = best.key
        old = self.strategy
        old_ok = (old is not None
                  and old.pipeline_stages <= 1 and old.micro_batches <= 1
                  and not old.shard_state)
        new_ok = (best.schedule.kind in ("every_step", "local_sgd")
                  and not best.shard_state
                  and best.pipeline_stages <= 1
                  and best.micro_batches <= 1)
        if old_ok and new_ok:
            if best.key != event["old_key"] \
                    or type(old.scheduler).name != best.schedule.kind:
                if self._built and old.scheduler.diverges_params:
                    # the collapse IS the parameter-averaging round the
                    # outgoing local scheduler owed — count it honestly
                    self._params = _collapse_mean(self._params)
                    self._opt_state = _collapse_mean(self._opt_state)
                    self.param_rounds += 1
                self.strategy = strategy_from_plan(best, self.axes)
                self._built = False    # rebuild lazily; EF residual resets
                event["applied"] = True
            else:
                event["note"] = "re-plan kept the incumbent arm"
        else:
            event["note"] = ("winner needs a different execution shape "
                             "(shard/pipeline); not swapped mid-run")
        self.planned = dict(self.planned, strategy_plan=best, arms=arms,
                            t_backward_s=t_bwd)
        self.replans += 1
        self.replan_events.append(event)
        print(f"replan @step {self.step}: drift {drift * 100:+.1f}%"
              + (f", straggler {ss * 1e3:.1f} ms" if ss > 0 else "")
              + f" -> {best.key}"
              + (" (installed)" if event["applied"]
                 else f" ({event['note']})"), flush=True)

    def drift_report(self) -> Optional[Dict[str, Any]]:
        """The modeled-vs-measured closing of the loop: per-arm predicted
        step time against this run's measured median, with the fit's
        error budget (comm α/β confidence + backward-profile spread +
        measurement spread).  None until both a plan and steps exist."""
        if self.planned is None or not self.step_times:
            return None
        sp = self.planned["strategy_plan"]
        measured = self.measured_step_s()
        modeled_wall = self._modeled_wall_s()
        times = self.step_times[1:] or self.step_times
        spread = (max(times) - min(times)) / 2.0 if len(times) > 1 else 0.0
        comm_err = plan_comm_error_s(sp.comm, self.calibration)
        fit_err = comm_err + self._t_backward_spread_s + spread
        arms = {}
        for key, arm in self.planned.get("arms", {}).items():
            wall = modeled_wall_step_s(arm.modeled_step_s, arm.t_backward_s)
            arms[key] = {
                "modeled_step_s": arm.modeled_step_s,
                "modeled_wall_step_s": wall,
                "drift_pct": drift_fraction(wall, measured) * 100.0}
        return {
            "plan_key": sp.key,
            "modeled_step_s": sp.modeled_step_s,
            "modeled_wall_step_s": modeled_wall,
            "measured_step_s": measured,
            "steps_measured": len(times),
            "drift_frac": drift_fraction(modeled_wall, measured),
            "drift_pct": drift_fraction(modeled_wall, measured) * 100.0,
            "comm_fit_err_s": comm_err,
            "t_backward_err_s": self._t_backward_spread_s,
            "measured_spread_s": spread,
            "fit_error_s": fit_err,
            "within_fit_error": abs(measured - modeled_wall) <= fit_err,
            "replans": self.replans,
            "replan_events": list(self.replan_events),
            "arms": arms,
        }

    def save_checkpoint(self, path: str) -> None:
        """In sharded mode the optimizer state is saved LEAF-SHAPED (via
        :meth:`full_opt_state` — master params + moments reconstructed
        from the canonical shard rows), so a checkpoint restores onto any
        mesh shape or bucket plan; raw (world, m) rows would pin the
        checkpoint to this run's layout.  ``ShardLayout.shard_rows``
        re-partitions on restore."""
        save_ckpt(path, {"params": self.params, "opt": self.full_opt_state()},
                  step=self.step)

    def load_checkpoint(self, path: str) -> int:
        """Restore a checkpoint written by :meth:`save_checkpoint` into
        this session, BEFORE the first step compiles the programs.  The
        payload checksum is verified first (a truncated file raises
        ``ValueError``, DESIGN.md §15).  Because checkpoints are
        leaf-shaped, restore is execution-mode agnostic: params load
        directly; optimizer state fills the replicated template when this
        session runs replicated (a sharded checkpoint's f32 master is
        simply dropped — the params carry the same values), and the full
        leaf-shaped dict is stashed for :meth:`_build_sharded` to
        re-partition onto THIS session's ``ShardLayout`` — the elastic
        resharding path: a checkpoint saved on world 8 restores onto a
        6-rank fabric without restart.  Sets and returns the restored
        step; the synthetic data pipeline is a pure function of the step
        index, so resumption replays the exact batch sequence."""
        if self._built:
            raise RuntimeError("load_checkpoint must run before the first "
                               "step")
        if self.strategy is not None and (
                self.strategy.pipeline_stages > 1
                or self.strategy.micro_batches > 1):
            raise NotImplementedError(
                "load_checkpoint composes with replicated and sharded DP "
                "builds; restoring into a pipeline/micro-batched build is "
                "not supported")
        data, manifest = load_ckpt_arrays(path)

        def tree_at(prefix, like):
            flat = _flatten_with_paths(like)
            missing = [k for k in flat if f"{prefix}/{k}" not in data]
            if missing:
                raise ValueError(
                    f"checkpoint {path!r} lacks {prefix!r} leaves "
                    f"{missing[:3]}{'…' if len(missing) > 3 else ''} — "
                    f"was it saved from a different model config?")
            leaves = [jnp.asarray(data[f"{prefix}/{k}"]) for k in flat]
            return jax.tree.unflatten(jax.tree.structure(like), leaves)

        self._params = tree_at("params", self._params)
        # every top-level optimizer entry is params-shaped by the
        # checkpoint contract (full_opt_state): moments, momentum, and —
        # for sharded-run checkpoints — the f32 "master" copy
        tops = sorted({k.split("/", 2)[1]
                       for k in data if k.startswith("opt/")})
        full = {t: tree_at(f"opt/{t}", self._params) for t in tops}
        self._restore_opt = dict(full)
        moments = {k: v for k, v in full.items() if k != "master"}
        if isinstance(self._opt_state, dict):
            missing = sorted(set(self._opt_state) - set(moments))
            if missing:
                raise ValueError(
                    f"checkpoint {path!r} lacks optimizer buffers "
                    f"{missing} required by {self.cfg.optimizer!r}")
            self._opt_state = {k: moments[k] for k in self._opt_state}
        else:                      # non-dict optimizer state: structural
            self._opt_state = tree_at("opt", self._opt_state)
        self.step = int(manifest.get("step") or 0)
        return self.step

    def summary(self) -> str:
        parts = [f"steps {self.step}", f"comm rounds {self.comm_rounds} "
                 f"(grad {self.grad_rounds}, param {self.param_rounds}"
                 + (f", control probes {self.control_rounds}"
                    if self.control_rounds else "") + ")"]
        if self.routed_tokens:
            parts.append(
                f"moe dropped {self.dropped_tokens:.0f}/"
                f"{self.routed_tokens:.0f} token-choices "
                f"({self.drop_fraction * 100:.1f}%)")
        if self.strategy is not None:
            parts.append(self.strategy.describe())
        else:
            parts.append("vanilla BSP")
        return "; ".join(parts)
