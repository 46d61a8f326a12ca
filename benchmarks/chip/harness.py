"""Run one cell of ``BENCHMARK.json`` once and print its result line.

A cell names a configuration (``configs/<config>.json``: the sizes as
stated, the program's ``arch`` and ``overrides``) and a traffic mix
(``jobs/<traffic>.json``: rows, length, optimizer, sync, and the name of
its generator, ``traffic/<generator>.py``); its limits for ``correct``
are ``limits/<cell>.json`` and each per-layer metric is
``metrics/<metric>.py``.  Nothing here names a cell, a generator or a
metric: a new one is new files plus entries in ``BENCHMARK.json``.

One run: check the chip, build ``TrainSession`` as the training CLI does
for the job's flags, with the benchmark's weights and batches; drive its
first steps (the reference follows them) and warm up until a step runs
without compiling (set-up ends there); then either time the window
(``--trace 0``, end-to-end metrics) or trace a few steps (``--trace 1``,
per-layer metrics); free the program's state and run the reference.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import json
import math
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
SRC = os.path.join(ROOT, "src")
OUT = os.path.join(ROOT, "bench_out")
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration")


class Failure(Exception):
    """The run cannot give a result: exit non-zero, print nothing."""


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def _json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


# -- the cell ------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    job: Dict
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]

    @property
    def family(self) -> str:
        return self.config["reference"]

    @property
    def tokens_per_step(self) -> int:
        return self.job["rows_per_chip"] * self.chips * self.job["seq_len"]

    def traffic(self, seed: int):
        """The job's batches for ``seed``: ``Traffic(job, vocab, seed)``
        of the module ``traffic/<generator>.py``."""
        module = importlib.import_module(f"traffic.{self.job['generator']}")
        return module.Traffic(self.job, self.config[self.config["vocab_key"]],
                              seed)


def load_cell(name: str, root: str = ROOT) -> Cell:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
        raise Failure(f"no BENCHMARK.json at {root}")
    bench = _json(path)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Failure(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    job = _json(os.path.join(HERE, "jobs", f"{w['traffic']}.json"))
    if not os.path.exists(os.path.join(HERE, "traffic",
                                       f"{job['generator']}.py")):
        raise Failure(f"job {w['traffic']}: no generator "
                      f"traffic/{job['generator']}.py")
    if job["chips"] != w["chips"]:
        raise Failure(f"job {w['traffic']} is for {job['chips']} chips, "
                      f"the cell asks for {w['chips']}")
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name] if m["moves"] in moved
                                  else [])]
    return Cell(name, w["chips"], _json(os.path.join(root, conf["file"])),
                job, _json(os.path.join(HERE, "limits", f"{name}.json")),
                e2e, per_layer)


def peaks_for(kind: str) -> Dict[str, float]:
    table = _json(os.path.join(HERE, "peaks.json"))["devices"]
    if kind not in table:
        raise Failure(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def place_caches() -> None:
    """Before JAX is imported: its persistent compilation cache at the
    fixed ``<checkout>/.jax_cache`` (the program's ``use_compile_cache``
    takes the directory from ``JAX_COMPILATION_CACHE_DIR``), whatever the
    environment says, and the TPU runtime's logs inside the checkout."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(OUT, "tpu_logs"))


def device_check(chips: int) -> Dict[str, Any]:
    """The chips the cell asks for, and nothing else: no CPU fallback."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Failure(f"no TPU: JAX found platform {devs[0].platform!r}")
    if len(devs) != chips:
        raise Failure(f"the cell asks for {chips} chip(s), JAX sees "
                      f"{len(devs)}")
    peaks_for(devs[0].device_kind)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class CompileCounter:
    """Counts JAX's lowering and backend-compile events (a compile or a
    load from the persistent cache): a warm step has none."""

    def __init__(self):
        import jax.monitoring
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event.startswith(COMPILE_EVENTS):
            self.n += 1


# -- the system under test ---------------------------------------------------------

class AnnotatedTraffic:
    """The job's batches, each made inside a ``bench.batch`` span."""

    def __init__(self, traffic):
        self.traffic = traffic

    def batch(self, step: int):
        import jax
        with jax.profiler.TraceAnnotation("bench.batch"):
            return self.traffic.batch(step)


def model_config(cell: Cell):
    from repro.configs import get_config
    c = cell.config
    return dataclasses.replace(get_config(c["arch"]), **c["overrides"])


def build_session(cell: Cell, seed: int, make_params):
    """``TrainSession`` as ``repro.launch.train.run`` builds it for the
    job's flags.  The resolved configuration is handed to the session's
    one lookup by architecture name, and its model's ``init`` returns the
    benchmark's weights: both only in this process.  A job whose ``sync``
    is null runs the session's own step (the CLI's ``--sync vanilla``);
    otherwise the session gets ``make_strategy(scheduler, sync=
    SyncConfig(**config))`` from the job's ``sync`` keys, as the CLI
    builds it for ``--sync comm``."""
    import repro.api as api
    from repro.core import SyncConfig, make_strategy
    job = cell.job
    mcfg = model_config(cell)

    class SeededModel(api.Model):
        def init(self, rng, dtype=None):
            return make_params(seed)

    saved = api.get_config, api.Model
    api.get_config = lambda name: mcfg
    api.Model = SeededModel
    try:
        session = api.TrainSession(api.SessionConfig(
            arch=cell.config["arch"], steps=job["lr_horizon"],
            batch=job["rows_per_chip"] * cell.chips, seq=job["seq_len"],
            lr=job["lr"], warmup=job["warmup"], optimizer=job["optimizer"],
            data_parallel=cell.chips, seed=seed % 2 ** 31))
    finally:
        api.get_config, api.Model = saved
    sync = job["sync"]
    if sync is not None:
        session.strategy = make_strategy(
            sync["scheduler"], axes=session.axes,
            sync=SyncConfig(**sync["config"]))
    session.data = AnnotatedTraffic(cell.traffic(seed))
    return session


class Params:
    """The benchmark's weights for the program's parameter tree."""

    def __init__(self, cell: Cell):
        import jax
        import weights
        from repro.models import Model
        self.abstract = jax.eval_shape(Model(model_config(cell)).init,
                                       jax.random.PRNGKey(0))
        self.names = weights.leaf_names(self.abstract)
        self._make = weights.maker(self.abstract)
        self._jit = jax.jit(self._make)
        self.key = weights.seed_key

    def __call__(self, seed: int):
        return self._jit(self.key(seed))

    def delta_norms_fn(self):
        """jitted (params, key) -> per-leaf norm of params - initial."""
        import jax
        import jax.numpy as jnp

        def f(params, key):
            start = self._make(key)
            return [jnp.sqrt(jnp.sum(jnp.square(
                a.astype(jnp.float32) - b.astype(jnp.float32))))
                for a, b in zip(jax.tree.leaves(params),
                                jax.tree.leaves(start))]
        return jax.jit(f)


def leaf_norms_fn(scale: float = 1.0):
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(
        l.astype(jnp.float32)))) * scale for l in jax.tree.leaves(t)])


def first_steps(session, cell: Cell, params: Params, seed: int) -> Dict:
    """The steps the reference follows, through the window's own call
    and feed: each step's loss, the first gradient as the optimizer got
    it (from Adam's first moment after one step) and each leaf's change
    after the last of them."""
    b1 = cell.job["adam_b1"]
    g_norms = leaf_norms_fn(1.0 / (1.0 - b1))
    losses, g1 = [], None
    t0 = time.time()
    for i in range(cell.job["checked_steps"]):
        losses.append(session.step_once())
        log(f"checked step {i + 1} at +{time.time() - t0:.1f}s")
        if i == 0:
            g1 = [float(x) for x in g_norms(session._opt_state["m"])]
    d = params.delta_norms_fn()(session._params, params.key(seed))
    log(f"norms read at +{time.time() - t0:.1f}s")
    out = {"losses": losses, "g1": g1, "d3": [float(x) for x in d]}
    if cell.chips > 1:
        out["replica_gap"] = float(replica_gap_fn(session.mesh, session.axes)(
            session._params))
    return out


def replica_gap_fn(mesh, axes):
    """jitted params -> the largest ``|x_d - x_0|`` of any parameter
    between device 0's replica and device ``d``'s, over every device of
    ``mesh``: replicated parameters that drift apart read above 0."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    def body(tree):
        first = jax.lax.axis_index(axes) == 0
        worst = jnp.zeros((), jnp.float32)
        for x in jax.tree.leaves(tree):
            x0 = jax.lax.psum(jnp.where(first, x, jnp.zeros_like(x)), axes)
            worst = jnp.maximum(worst, jnp.max(jnp.abs(x - x0)).astype(
                jnp.float32))
        return jax.lax.pmax(worst, axes)
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P(),
                                 out_specs=P(), check_vma=False))


def warm_up(session, counter: CompileCounter, limit: int = 4) -> int:
    """Steps until one runs without compiling; returns how many ran."""
    for i in range(1, limit + 1):
        before = counter.n
        session.step_once()
        if counter.n == before:
            return i
    raise Failure(f"every one of {limit} warm-up steps compiled")


def free_program(session) -> None:
    import jax
    session._params = session._opt_state = session._sync_state = None
    gc.collect()
    jax.clear_caches()


def memory_peak() -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


# -- the reference -------------------------------------------------------------------

def reference_steps(cell: Cell, params: Params, seed: int, batches,
                    rows: slice = slice(None), **kw) -> Dict:
    """The reference's first steps from the same weights and batches,
    its rows split over the cell's chips where they divide.  The program
    routes each data shard's rows alone (one chip's ``rows_per_chip``
    rows), so the reference is told how many such shards the rows hold."""
    import functools
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from reference import train as rtrain
    module = importlib.import_module(f"reference.{cell.family}")
    n = len(range(*rows.indices(len(batches[0]["tokens"]))))
    nll = functools.partial(module.nll_sum,
                            shards=max(1, n // cell.job["rows_per_chip"]))
    sharding, place = None, None
    if cell.chips > 1 and n % cell.chips == 0:
        mesh = Mesh(np.array(jax.devices()), ("data",))
        sharding = NamedSharding(mesh, P("data"))
        place = NamedSharding(mesh, P())

    def params0():
        p = params(seed)
        return p if place is None else jax.device_put(p, place)
    return rtrain.run_steps(nll, cell.config, params0, batches,
                            cell.job, data_sharding=sharding, rows=rows,
                            **kw)


# -- one run -------------------------------------------------------------------------

def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        device: Optional[Dict] = None, peaks: Optional[Dict] = None,
        tamper=None) -> Dict:
    """One run of ``cell``.  ``device``/``peaks`` stand in for the chip
    check and the peak table, and ``tamper(session)`` breaks the program
    underneath, only in the benchmark's own tests on the CPU."""
    import jax
    import check
    from flops import train_flops_per_token
    from repro.launch.paths import use_compile_cache

    if device is None:
        device = device_check(cell.chips)
    cache = use_compile_cache()
    # every program, however quick to compile, is loaded from the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"cell {cell.name} seed {seed} chips {cell.chips} cache {cache!r}; "
        f"devices at {time.time() - t_start:.1f}s")
    counter = CompileCounter()
    params = Params(cell)
    session = build_session(cell, seed, params)
    if tamper is not None:
        tamper(session)
    log(f"session built at {time.time() - t_start:.1f}s")
    prog = first_steps(session, cell, params, seed)
    log(f"checked steps done at {time.time() - t_start:.1f}s")
    warm = warm_up(session, counter)
    setup_s = time.time() - t_start
    log(f"set-up {setup_s:.3f}s ({cell.job['checked_steps']} checked steps, "
        f"{warm} warm-up)")
    peaks = peaks or peaks_for(jax.devices()[0].device_kind)
    flops_tok = train_flops_per_token(cell.config, cell.family,
                                      cell.job["seq_len"])
    step_losses: List[float] = []
    metrics: Dict[str, Dict[str, Any]] = {}
    out: Dict[str, Any] = {}
    before = counter.n
    if not trace:
        step_s = []
        t0 = t1 = time.perf_counter()
        while t1 - t0 < seconds:
            step_losses.append(session.step_once())
            t, t1 = t1, time.perf_counter()
            step_s.append(t1 - t)
        tps = len(step_losses) * cell.tokens_per_step / (t1 - t0)
        out["step_ms"] = step_quantiles(step_s)
        values = {"tokens_per_s": tps,
                  "mfu": 100.0 * tps * flops_tok / (
                      cell.chips * peaks["bf16_flops_per_s"]),
                  "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
        log(f"window {t1 - t0:.3f}s, {len(step_losses)} steps, "
            f"{tps:.3f} tokens/s")
    else:
        step_losses, metrics, out = traced_window(
            session, cell, peaks, flops_tok, seconds)
    window_compiles = counter.n - before
    out["session"] = {k: getattr(session, k, None)
                      for k in ("compiles", "cache_hits", "compile_s")}
    device = dict(device, memory_peak_bytes=memory_peak(), **out.pop(
        "device", {}))
    free_program(session)
    del session

    t_ref = time.time()
    feed = cell.traffic(seed)
    batches = [feed.batch(i) for i in range(cell.job["checked_steps"])]
    ref = reference_steps(cell, params, seed, batches)
    found = check.readings(prog, ref)
    values = {k: v for k, (v, _) in found.items()}
    values["window_compiles"] = float(window_compiles)
    if "replica_gap" in prog:
        values["replica_gap"] = prog["replica_gap"]
    limits = dict(cell.limits["limits"], window_compiles=0.0)
    ok, rows = check.judge(values, limits)
    failed = sum(1 for x in step_losses if not math.isfinite(x))
    ok = ok and failed == 0
    for k, (v, at) in found.items():
        where = params.names[at] if k != "loss_gap" else f"step {at + 1}"
        log(f"reading {k} {v!r} at {where}")
    log(f"reference {time.time() - t_ref:.1f}s; program losses "
        f"{prog['losses']} reference {ref['losses']}")
    for name, v, lim in rows:
        log(f"check {name} {v!r} limit {lim!r}")
    result = {"correct": bool(ok), "attempted": len(step_losses),
              "failed": failed, "metrics": metrics, "device": device}
    result.update(out)
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in rows}
    return result


def step_quantiles(step_s: List[float]) -> Dict[str, float]:
    """The window's step times in ms: least, quartiles, most, and count
    (a whole slow run moves every quantile, a few long steps the top)."""
    import statistics
    ms = sorted(1e3 * t for t in step_s)
    q = statistics.quantiles(ms, n=4) if len(ms) > 1 else ms * 3
    return {"min": ms[0], "q1": q[0], "median": q[1], "q3": q[2],
            "max": ms[-1], "n": len(ms)}


def traced_window(session, cell: Cell, peaks, flops_tok, seconds):
    """Trace ``trace_steps`` steps (at most ``seconds``) and read the
    per-layer metrics from the trace and the session's counters."""
    import jax
    import tracefile as tr
    tdir = os.path.join(OUT, "trace", cell.name)
    shutil.rmtree(tdir, ignore_errors=True)
    os.makedirs(tdir, exist_ok=True)
    session.dropped_tokens = session.routed_tokens = 0.0
    losses = []
    t0 = time.perf_counter()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0          # only the bench.* spans
    with jax.profiler.trace(tdir, profiler_options=options):
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(cell.job["trace_steps"]):
                with jax.profiler.TraceAnnotation("bench.step"):
                    losses.append(session.step_once())
                if time.perf_counter() - t0 > seconds:
                    break
    t_read = time.time()
    tr_data = tr.load(tdir)
    shutil.rmtree(tdir, ignore_errors=True)
    lo, hi = tr.window(tr_data)
    busy = [tr.total(tr.busy(tr_data, p)) / 1e9 for p in tr_data.devices]
    ctx = Context(trace=tr_data, cell=cell, session=session, peaks=peaks,
                  flops_per_token=flops_tok, steps=len(losses))
    metrics = {}
    for m in cell.per_layer:
        reader = importlib.import_module(f"metrics.{m['name']}")
        v = reader.read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    plane = tr.busiest(tr_data)
    out = {"device": {"busy_s": sum(busy) / len(busy),
                      "window_s": (hi - lo) / 1e9},
           "breakdown": {"device_ops": tr.top_ops(tr_data, plane),
                         "idle_gaps": tr.idle_gaps(tr_data, plane)}}
    log(f"traced {len(losses)} steps; trace read in "
        f"{time.time() - t_read:.1f}s; busy {out['device']['busy_s']!r}s "
        f"of {out['device']['window_s']!r}s")
    return losses, metrics, out


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader may read."""
    trace: Any
    cell: Cell
    session: Any
    peaks: Dict[str, float]
    flops_per_token: float
    steps: int


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse
    t_start = time.time() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        print("bench: --seed must be non-negative", file=sys.stderr)
        return 2
    for p in (HERE, SRC):
        if p not in sys.path:
            sys.path.insert(0, p)
    place_caches()
    try:
        if not os.path.isdir(os.path.join(SRC, "repro")):
            raise Failure(f"no program under {SRC}: run from a checkout")
        cell = load_cell(args.workload)
        result = run(cell, args.seed, args.seconds, bool(args.trace),
                     t_start)
    except Failure as e:
        print(f"bench FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps(result), flush=True)
    return 0
