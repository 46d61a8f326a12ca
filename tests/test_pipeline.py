"""Pipeline parallelism (core/pipeline.py + the planner's parallelism axis
+ launch/steps.make_pipeline_train_step) — ISSUE 4.

Covers: the canonical 1F1B op order, the bubble-fraction ↔ simulated-
timeline identity, stage-cut balance properties (hypothesis), micro-batch
gradient accumulation bit-exactness vs the scan-accumulated reference,
the planner's pipeline arms (pricing, budget wins, invariants), staged-
model split/merge round-trips, and the bench-regression gate
(scripts/bench_ci.py) including the injected-perturbation negative test.
The 8-device pipeline-vs-DP bit-exactness lives in multi_device_checks.py.
"""
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
from hyp_compat import given, settings, st  # noqa: E402
from tiny_lm import TinyStackLM, tiny_batch  # noqa: E402

from repro.core.pipeline import (PIPE_FWD_FRACTION, StagedModel,  # noqa: E402
                                 aligned_order, aligned_ticks, balanced_cuts,
                                 bubble_fraction, schedule_1f1b,
                                 simulate_1f1b, stage_costs)
from repro.core.schedule import (LINK_PRESETS, LayerProfile,  # noqa: E402
                                 PipelineAxis, pipeline_arm, plan_rounds,
                                 profiles_from_sizes)

LINK = LINK_PRESETS["commodity"]


# ---------------------------------------------------------------------------
# 1F1B schedule
# ---------------------------------------------------------------------------

def test_schedule_1f1b_canonical_2x4():
    sched = schedule_1f1b(2, 4)
    assert sched[0] == [("F", 0), ("F", 1), ("B", 0), ("F", 2), ("B", 1),
                        ("F", 3), ("B", 2), ("B", 3)]
    assert sched[1] == [("F", 0), ("B", 0), ("F", 1), ("B", 1), ("F", 2),
                        ("B", 2), ("F", 3), ("B", 3)]


def test_schedule_1f1b_canonical_4x8():
    sched = schedule_1f1b(4, 8)
    # stage s warms up with S-1-s forwards, then strictly alternates
    for s, ops in enumerate(sched):
        warm = 4 - 1 - s
        assert ops[:warm] == [("F", m) for m in range(warm)]
        steady = ops[warm:]
        # alternation: F(warm), B(0), F(warm+1), B(1), ... then B-drain
        fs = [m for op, m in ops if op == "F"]
        bs = [m for op, m in ops if op == "B"]
        assert fs == list(range(8)) and bs == list(range(8))
        # memory bound: at most S - s micro-batches in flight
        flight = peak = 0
        for op, _ in ops:
            flight += 1 if op == "F" else -1
            peak = max(peak, flight)
        assert peak == 4 - s
    assert sched[3] == [("F", 0), ("B", 0), ("F", 1), ("B", 1), ("F", 2),
                        ("B", 2), ("F", 3), ("B", 3), ("F", 4), ("B", 4),
                        ("F", 5), ("B", 5), ("F", 6), ("B", 6), ("F", 7),
                        ("B", 7)]


@pytest.mark.parametrize("S,M", [(2, 4), (4, 8), (3, 5), (8, 32), (1, 6)])
def test_bubble_formula_matches_simulated_timeline(S, M):
    t_f, t_b = 1.0, 2.0
    makespan = simulate_1f1b(S, M, t_f, t_b)
    ideal = M * (t_f + t_b)
    assert makespan == pytest.approx((M + S - 1) * (t_f + t_b))
    assert (makespan - ideal) / makespan == pytest.approx(
        bubble_fraction(S, M))


def test_simulate_1f1b_send_cost_only_on_boundary_hops():
    # S=1: no boundary, sends are free regardless
    assert simulate_1f1b(1, 4, 1.0, 1.0, t_send=5.0) == \
        simulate_1f1b(1, 4, 1.0, 1.0)
    assert simulate_1f1b(2, 4, 1.0, 1.0, t_send=0.5) > \
        simulate_1f1b(2, 4, 1.0, 1.0)


@pytest.mark.parametrize("S,M", [(2, 4), (4, 8)])
def test_aligned_order_consistent_with_canonical(S, M):
    """The SPMD slot grid preserves the canonical per-stage F/B structure:
    same F order, same B order, F(m) strictly before B(m), and the O(S)
    in-flight bound 2(S-1-s)+1."""
    assert aligned_ticks(S, M) == M + 2 * (S - 1)
    aligned = aligned_order(S, M)
    canon = schedule_1f1b(S, M)
    for s in range(S):
        assert [x for x in aligned[s] if x[0] == "F"] == \
            [x for x in canon[s] if x[0] == "F"]
        assert [x for x in aligned[s] if x[0] == "B"] == \
            [x for x in canon[s] if x[0] == "B"]
        pos = {op: i for i, op in enumerate(aligned[s])}
        for m in range(M):
            assert pos[("F", m)] < pos[("B", m)]
        flight = peak = 0
        for op, _ in aligned[s]:
            flight += 1 if op == "F" else -1
            peak = max(peak, flight)
        assert peak <= 2 * (S - 1 - s) + 1
    # last stage is identical to canonical 1F1B
    assert aligned[S - 1] == canon[S - 1]


# ---------------------------------------------------------------------------
# Stage cuts
# ---------------------------------------------------------------------------

def _brute_min_max(costs, S):
    import itertools
    n = len(costs)
    best = float("inf")
    for bounds in itertools.combinations(range(1, n), S - 1):
        cuts = (0,) + bounds + (n,)
        best = min(best, max(sum(costs[cuts[i]:cuts[i + 1]])
                             for i in range(S)))
    return best


@given(st.lists(st.floats(min_value=0.0, max_value=100.0), min_size=1,
                max_size=9),
       st.integers(min_value=1, max_value=4))
@settings(max_examples=60, deadline=None)
def test_balanced_cuts_properties(costs, S):
    if len(costs) < S:
        with pytest.raises(ValueError):
            balanced_cuts(costs, S)
        return
    cuts = balanced_cuts(costs, S)
    assert cuts[0] == 0 and cuts[-1] == len(costs)
    assert len(cuts) == S + 1
    assert all(a < b for a, b in zip(cuts, cuts[1:]))   # non-empty stages
    got = max(stage_costs(costs, cuts))
    assert got == pytest.approx(_brute_min_max(tuple(costs), S))


def test_balanced_cuts_monotone_in_stages():
    costs = [5.0, 1.0, 3.0, 2.0, 4.0, 1.0, 2.0, 6.0]
    prev = float("inf")
    for S in (1, 2, 3, 4):
        cur = max(stage_costs(costs, balanced_cuts(costs, S)))
        assert cur <= prev + 1e-12
        prev = cur


# ---------------------------------------------------------------------------
# Micro-batch gradient accumulation (world=1)
# ---------------------------------------------------------------------------

def _pipeline_step_once(model, params, batch, M, opt_name="sgd", lr=0.1):
    from repro.core import GradientSynchronizer, SyncConfig
    from repro.launch.mesh import make_pipe_mesh
    from repro.launch.steps import make_pipeline_train_step
    from repro.optim import make_optimizer

    mesh = make_pipe_mesh(1, 1)
    opt = make_optimizer(opt_name, lr=lr)
    engine = GradientSynchronizer(SyncConfig(bucket_bytes=0), ("data",))
    step_fn, init_opt, init_ss = make_pipeline_train_step(model, opt, engine,
                                                          mesh, M)
    shared, rows = model.split(params)
    p = {"shared": shared, "rows": rows}
    o, ss = init_opt(p), init_ss(p)
    p2, _, _, loss = jax.jit(step_fn)(p, o, ss, batch,
                                      jnp.zeros((), jnp.int32),
                                      jax.random.PRNGKey(1))
    return model.merge(p2["shared"], p2["rows"]), float(loss["loss"])


def test_microbatch_accumulation_bit_exact_vs_scan_reference():
    """The S=1 pipeline step's gradient = ascending-order micro-batch
    accumulation — bit-exact against the hand-rolled scan reference run
    through the SAME optimizer step."""
    from repro.optim import apply_updates, make_optimizer

    M = 4
    model = TinyStackLM(blocks=4, n_stages=1)
    params = model.init(jax.random.PRNGKey(0))
    batch = tiny_batch(0, batch=8, seq=16)
    got, loss = _pipeline_step_once(model, params, batch, M)

    toks = batch["tokens"]
    mb = toks.shape[0] // M

    @jax.jit
    def ref(params):
        g = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        ls = jnp.zeros(())
        for m in range(M):
            l, gm = jax.value_and_grad(model.loss)(
                params, {"tokens": toks[m * mb:(m + 1) * mb]})
            g = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), g, gm)
            ls = ls + l
        g = jax.tree.map(lambda a: a / M, g)
        opt = make_optimizer("sgd", lr=0.1)
        upd, _ = opt.update(g, opt.init(params), params,
                            jnp.zeros((), jnp.int32))
        return apply_updates(params, upd), ls / M

    want, ref_loss = ref(params)
    assert loss == pytest.approx(float(ref_loss), rel=1e-6)
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_leaves_with_path(got),
            jax.tree_util.tree_leaves_with_path(want)):
        a, b = np.asarray(a), np.asarray(b)
        # world=1: XLA may contract the update-add differently per graph
        # (DESIGN.md §8/§9) — ulp-tight here; 8-device checks assert exact
        np.testing.assert_allclose(a, b, rtol=3e-6, atol=1e-7,
                                   err_msg=jax.tree_util.keystr(pa))


def test_microbatch_accumulation_close_to_full_batch():
    """Mean-of-micro-batch-means ≈ full-batch grad (equal only in exact
    arithmetic; the tokens-per-micro-batch counts are equal here)."""
    model = TinyStackLM(blocks=2, n_stages=1)
    params = model.init(jax.random.PRNGKey(0))
    batch = tiny_batch(0, batch=8, seq=16)
    got, _ = _pipeline_step_once(model, params, batch, 4, lr=0.1)
    full, _ = _pipeline_step_once(model, params, batch, 1, lr=0.1)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(full)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# Staged models
# ---------------------------------------------------------------------------

def test_staged_model_split_merge_roundtrip():
    from repro.configs import get_config, reduced
    from repro.models import Model

    model = Model(reduced(get_config("gemma-2b")))
    staged = StagedModel(model, 2)
    params = model.init(jax.random.PRNGKey(0))
    shared, rows = staged.split(params)
    merged = staged.merge(shared, rows)
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(params),
            jax.tree_util.tree_leaves_with_path(merged)):
        assert a.shape == b.shape, jax.tree_util.keystr(pa)
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_staged_model_rejects_heterogeneous_and_encdec():
    from repro.configs import get_config, reduced
    from repro.models import Model

    # xlstm reduced: one 4-layer heterogeneous period, repeats=1
    with pytest.raises(ValueError, match="divisible|single-row"):
        StagedModel(Model(reduced(get_config("xlstm-125m"))), 2)
    with pytest.raises(ValueError, match="decoder-only"):
        StagedModel(Model(reduced(get_config("seamless-m4t-large-v2"))), 2)
    with pytest.raises(ValueError, match="divisible"):
        StagedModel(Model(reduced(get_config("gemma-2b"))), 3)


def test_tiny_stack_loss_is_staged_composition():
    """TinyStackLM.loss == loss_tail(shared, stage(rows, embed(...)))."""
    model = TinyStackLM(blocks=4, n_stages=2)
    params = model.init(jax.random.PRNGKey(3))
    batch = tiny_batch(1)
    shared, rows = model.split(params)
    h = model.embed_mb(shared, batch["tokens"])
    flat = jax.tree.map(lambda x: x.reshape((4,) + x.shape[2:]), rows)
    h2, _ = TinyStackLM(blocks=4, n_stages=1).stage_apply(flat, h)
    want = model.loss_tail(shared, h2, batch["tokens"])
    got = model.loss(params, batch)
    assert float(got) == float(want)


# ---------------------------------------------------------------------------
# The planner's parallelism axis
# ---------------------------------------------------------------------------

def _profiles(n=24, mb=8.0, t=1e-3):
    return profiles_from_sizes([mb * 2**20] * n, t)


def test_pipeline_arm_pricing_fields():
    arm = pipeline_arm(_profiles(), LINK, 64, 4, 8, act_bytes_mb=1e6)
    assert arm.pipeline_stages == 4 and arm.micro_batches == 8
    assert arm.bubble == pytest.approx(bubble_fraction(4, 8))
    assert arm.key == "pipeline(S=4,M=8)"
    # bubble + p2p are charged on top of the DP edge plan
    assert arm.modeled_step_s >= arm.comm.modeled_step_s + arm.pipe_p2p_s
    assert arm.comm.world == 16          # world/S replicas on the DP edge


def test_pipeline_arm_rejects_bad_factorization():
    with pytest.raises(ValueError):
        pipeline_arm(_profiles(), LINK, 6, 4, 8, 1e6)    # 6 % 4 != 0
    with pytest.raises(ValueError):
        pipeline_arm(_profiles(), LINK, 8, 8, 8, 1e6)    # dp would be 1
    with pytest.raises(ValueError):
        pipeline_arm(_profiles(n=2), LINK, 64, 4, 8, 1e6)  # 2 leaves, S=4


def test_pipeline_bubble_shrinks_with_micro_batches():
    prev = float("inf")
    for M in (4, 8, 16, 32):
        arm = pipeline_arm(_profiles(), LINK, 64, 4, M, act_bytes_mb=1e4)
        assert arm.bubble < prev
        prev = arm.bubble


def test_plan_rounds_prices_pipeline_arms_only_with_axis():
    profiles = _profiles()
    _, arms = plan_rounds(profiles, LINK, 64)
    assert not any(a.pipeline_stages > 1 for a in arms.values())
    pa = PipelineAxis(global_tokens=4096.0 * 64, bytes_per_token=4096.0)
    best, arms = plan_rounds(profiles, LINK, 64, pipeline=pa)
    pipes = [a for a in arms.values() if a.pipeline_stages > 1]
    assert pipes
    # winner is never modeled slower than any arm (invariant extends)
    assert all(best.modeled_step_s <= a.modeled_step_s + 1e-12
               for a in arms.values())


def test_plan_rounds_pipeline_respects_world_divisibility():
    pa = PipelineAxis(global_tokens=4096.0 * 6, bytes_per_token=4096.0)
    _, arms = plan_rounds(_profiles(), LINK, 6, pipeline=pa)
    # 6 only factors into pipe(2) x data(3); S=4, S=8 must be absent
    keys = {a.pipeline_stages for a in arms.values()
            if a.pipeline_stages > 1}
    assert keys == {2}


def test_pipeline_wins_under_memory_budget_when_comm_dominates():
    """Big comm-dominated model on a slow link + a budget below replicated
    moments: local-SGD and replicated every-step drop, and the pipeline
    arm must beat the sharded arm (whose serial gather tail is priced on
    the same slow link) — the tentpole's planner acceptance point."""
    profiles = _profiles(n=32, mb=64.0, t=1e-4)   # 2 GiB model, fast bwd
    pa = PipelineAxis(global_tokens=4096.0 * 64, bytes_per_token=4096.0)
    pb = sum(p.grad_bytes for p in profiles)
    budget = 2.0 * pb / 2                          # half of adam's moments
    best, arms = plan_rounds(profiles, LINK, 64, pipeline=pa,
                             memory_budget_bytes=budget)
    assert best.pipeline_stages > 1, best.key
    assert best.opt_mem_bytes <= budget
    assert best.modeled_step_s < arms["every_step"].modeled_step_s
    assert best.modeled_step_s < arms["every_step_sharded"].modeled_step_s


def test_strategy_from_plan_pipeline_arm():
    from repro.api import strategy_from_plan
    from repro.core import GradientSynchronizer

    arm = pipeline_arm(_profiles(), LINK, 64, 2, 8, act_bytes_mb=1e5)
    st_ = strategy_from_plan(arm)
    assert st_.pipeline_stages == 2 and st_.micro_batches == 8
    assert isinstance(st_.grad_reducer, GradientSynchronizer)
    assert st_.grad_reducer.cfg.bucket_bytes == 0    # per-row granularity


def test_sync_strategy_rejects_bad_pipeline_compositions():
    from repro.core import SyncStrategy, get_scheduler

    with pytest.raises(ValueError, match="shard_state|pipeline"):
        SyncStrategy(scheduler=get_scheduler("every_step"),
                     pipeline_stages=2, shard_state=True)
    with pytest.raises(ValueError):
        SyncStrategy(scheduler=get_scheduler("every_step"),
                     pipeline_stages=0)

    from repro.api import SessionConfig, TrainSession
    sess = TrainSession(
        SessionConfig(arch="xlstm-125m", reduced=True, batch=4, seq=16),
        strategy=SyncStrategy(scheduler=get_scheduler("local_sgd", period=2),
                              pipeline_stages=2))
    with pytest.raises(ValueError, match="every-step"):
        sess.step_once()


def test_report_renders_pipeline_arm(tmp_path):
    from repro.launch import report

    arm = pipeline_arm(_profiles(), LINK, 64, 4, 8, act_bytes_mb=1e5)
    txt = report.render_strategy_plan(arm, arms={arm.key: arm,
                                                 "every_step": arm})
    assert "pipeline: 4 stages × 8 micro-batches" in txt
    assert "bubble" in txt
    rec = report.comm_plan_record(arm.comm)
    assert rec["world"] == 16
    # the saved strategy record carries the pipeline block
    import repro.launch.paths as paths
    old = paths.COMM_PLANS
    paths.COMM_PLANS = str(tmp_path)
    try:
        p = report.save_strategy_plan(arm, "testarch")
        with open(p) as f:
            saved = json.load(f)
        assert saved["pipeline"]["stages"] == 4
        assert saved["pipeline"]["bubble_fraction"] == pytest.approx(
            bubble_fraction(4, 8))
    finally:
        paths.COMM_PLANS = old


# ---------------------------------------------------------------------------
# scripts/bench_ci.py — the regression gate
# ---------------------------------------------------------------------------

def _load_bench_ci():
    path = os.path.join(os.path.dirname(__file__), "..", "scripts",
                        "bench_ci.py")
    spec = importlib.util.spec_from_file_location("bench_ci", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_ci_gate_trips_on_regression(tmp_path):
    bench_ci = _load_bench_ci()
    base = {"a/b/auto": {"modeled_step_ms": 10.0, "arm": "x"},
            "a/b/fixed": {"modeled_step_ms": 20.0, "arm": "y"}}
    bdir = tmp_path / "baselines"
    bdir.mkdir()
    (bdir / "BENCH_planner.json").write_text(json.dumps(base))

    ok = {"planner": {k: dict(v) for k, v in base.items()}}
    assert bench_ci.gate(ok, str(bdir), 0.10) == []

    # +5% passes, +20% trips, vanished number trips
    mild = {"planner": {k: {"modeled_step_ms": v["modeled_step_ms"] * 1.05,
                            "arm": v["arm"]} for k, v in base.items()}}
    assert bench_ci.gate(mild, str(bdir), 0.10) == []
    bad = {"planner": {k: {"modeled_step_ms": v["modeled_step_ms"] * 1.20,
                           "arm": v["arm"]} for k, v in base.items()}}
    fails = bench_ci.gate(bad, str(bdir), 0.10)
    assert len(fails) == 2 and all("+20.0%" in f for f in fails)
    gone = {"planner": {"a/b/auto": base["a/b/auto"]}}
    assert any("vanished" in f for f in bench_ci.gate(gone, str(bdir), 0.10))
    # missing baseline file is itself a failure
    assert bench_ci.gate({"sharded": {}}, str(bdir), 0.10)


def test_bench_ci_committed_baselines_exist_and_match_schema():
    bdir = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "baselines")
    for suite in ("planner", "sharded", "pipeline", "topology"):
        path = os.path.join(bdir, f"BENCH_{suite}.json")
        assert os.path.exists(path), f"missing committed baseline {path}"
        with open(path) as f:
            recs = json.load(f)
        assert recs, path
        for name, r in recs.items():
            assert "modeled_step_ms" in r and r["modeled_step_ms"] > 0, name
