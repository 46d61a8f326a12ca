"""Multi-device correctness checks, run in a SUBPROCESS with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (tests must not pollute
the main process's device count — smoke tests see 1 device).

Exit code 0 = all checks passed.  Invoked by test_collectives.py.
"""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P  # noqa: E402


def check_collectives():
    from repro.core.collectives import allreduce, ALGOS
    mesh = jax.make_mesh((4, 2), ("data", "pod"), axis_types=(AxisType.Auto,) * 2)
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 37))
    ref = np.asarray(x).reshape(4, 2, 37).sum(axis=(0, 1))
    for algo in ALGOS:
        f = jax.shard_map(lambda v: allreduce(v, algo, ("data", "pod")),
                          mesh=mesh, in_specs=P(("data", "pod"), None),
                          out_specs=P(None, None),
                          axis_names={"data", "pod"}, check_vma=False)
        out = np.asarray(jax.jit(f)(x))[0]
        if algo == "ring_fused":
            # the compressed ring is LOSSY by design (int8 wire with
            # per-hop requantization of partial sums, DESIGN.md §11):
            # bounded relative error, not exact.  Rank agreement is
            # checked with per-rank out_specs in check_ring_fused.
            rel = np.abs(out - ref).max() / (np.abs(ref).max() + 1e-9)
            assert rel < 0.05, ("ring_fused", rel)
        else:
            assert np.allclose(out, ref, atol=1e-4), algo
        # the manual algorithms must NOT lower to a plain all-reduce
        txt = jax.jit(f).lower(x).compile().as_text()
        if algo not in ("psum",):
            assert "collective-permute" in txt, algo
    print("collectives ok")


def check_grad_sync():
    from repro.core import GradientSynchronizer, SyncConfig
    mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
    grads = {"w": jax.random.normal(jax.random.PRNGKey(1), (8, 64, 32)),
             "b": jax.random.normal(jax.random.PRNGKey(2), (8, 33))}
    ref = jax.tree.map(lambda g: np.asarray(g).mean(0), grads)
    configs = [
        SyncConfig(compressor="none", algo="ring"),
        SyncConfig(compressor="int8", algo="hierarchical"),
        SyncConfig(compressor="qsgd", algo="ring"),
        SyncConfig(compressor="topk", algo="ring",
                   compressor_args=(("ratio", 0.5),)),
        SyncConfig(compressor="powersgd", algo="mesh2d",
                   compressor_args=(("rank", 16),)),
        # the fused Pallas wires (DESIGN.md §11), including the lossy
        # compressed-ring transport for the int8 payload
        SyncConfig(compressor="int8_fused", algo="ring"),
        SyncConfig(compressor="int8_fused", algo="ring_fused"),
        SyncConfig(compressor="topk_fused", algo="ring",
                   compressor_args=(("ratio", 0.25),)),
    ]
    for cfg in configs:
        sync = GradientSynchronizer(cfg, ("data",))

        def body(g, rng):
            g = jax.tree.map(lambda x: x[0], g)
            st = sync.init_state(g)
            out, _ = sync(g, st, rng)
            return out

        f = jax.shard_map(body, mesh=mesh,
                          in_specs=({"w": P("data", None, None),
                                     "b": P("data", None)}, P()),
                          out_specs={"w": P(None, None), "b": P(None)},
                          axis_names={"data"}, check_vma=False)
        out = jax.jit(f)(grads, jax.random.PRNGKey(0))
        for k in ref:
            denom = np.abs(ref[k]).max() + 1e-9
            rel = float(jnp.max(jnp.abs(out[k] - ref[k]))) / denom
            limit = 1e-5 if cfg.compressor == "none" else 1.2
            assert rel < limit, (cfg.compressor, rel)
    print("grad_sync ok")


def check_error_feedback_converges_distributed():
    """EF-compressed SGD on a shared quadratic reaches the optimum even with
    1-bit sign compression (the survey's §3.2.1 headline result)."""
    from repro.core import GradientSynchronizer, SyncConfig
    mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
    w_star = jax.random.normal(jax.random.PRNGKey(5), (64,))
    sync = GradientSynchronizer(
        SyncConfig(compressor="sign", algo="ring"), ("data",))

    def run(noise):
        def body(noise):
            w = jnp.zeros((64,))
            st = sync.init_state({"w": w})

            def step(carry, i):
                w, st = carry
                # per-worker noisy gradient of ||w - w*||^2 / 2
                g = (w - w_star) + noise[0, i % 16]
                synced, st = sync({"w": g}, st, jax.random.fold_in(
                    jax.random.PRNGKey(0), i))
                w = w - 0.3 * synced["w"]
                return (w, st), None

            (w, _), _ = jax.lax.scan(step, (w, st), jnp.arange(300))
            return w

        f = jax.shard_map(body, mesh=mesh,
                          in_specs=P("data", None, None),
                          out_specs=P(None), axis_names={"data"},
                          check_vma=False)
        return jax.jit(f)(noise)

    noise = jax.random.normal(jax.random.PRNGKey(6), (8, 16, 64)) * 0.5
    # zero-mean noise across workers
    noise = noise - noise.mean(axis=0, keepdims=True)
    w = run(noise)
    rel = float(jnp.linalg.norm(w - w_star) / jnp.linalg.norm(w_star))
    assert rel < 0.05, rel
    print("EF sign-SGD convergence ok, rel err", rel)


def check_ring_fused():
    """The compressed-ring prototype on 8 REAL ranks (DESIGN.md §11):
    every rank reconstructs the SAME lossy sum (the all-gather phase
    circulates one quantized payload per chunk, owner included — any
    per-rank dequantization asymmetry would diverge replicas), the error
    is within the per-hop requantization bound, and the wire actually
    lowers to ppermute steps, not a hidden all-reduce."""
    from repro.core.collectives import allreduce
    mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
    x = jax.random.normal(jax.random.PRNGKey(30), (8, 5000))
    ref = np.asarray(x).sum(0)

    f = jax.jit(jax.shard_map(
        lambda v: allreduce(v[0], "ring_fused", ("data",))[None],
        mesh=mesh, in_specs=P("data", None), out_specs=P("data", None),
        axis_names={"data"}, check_vma=False))
    per_rank = np.asarray(f(x))                 # (8, 5000), one row per rank
    assert np.all(per_rank == per_rank[0:1]), "ranks disagree"
    rel = np.abs(per_rank[0] - ref).max() / (np.abs(ref).max() + 1e-9)
    assert rel < 0.05, rel
    txt = f.lower(x).compile().as_text()
    assert "collective-permute" in txt and "all-reduce" not in txt
    print(f"ring_fused ok (8 ranks agree bitwise, rel err {rel:.4f})")


def check_fused_bit_trajectory():
    """THE fused-wire acceptance criterion: the one-pass kernels vs the
    SAME plan with ``fused=False`` (decomposed reference chain) on the
    REAL 8-device mesh, 3 sync rounds — EF residual trajectories must be
    bit-identical for both wires (int8 tiles + scales, bisection top-k).
    Payload equality per call is pinned at the compressor level in
    test_compression.py; residual equality across steps proves the
    executor's fused dispatch feeds the kernels identical buffers and
    carries identical state.  Synced sums: bit-equal for the aggregatable
    top-k; the int8 gather wire's fused decode is one reduction over the
    payload axis vs the loop's sequential adds — 2-ulp bound, the
    documented summation-order difference."""
    import dataclasses
    from repro.core import PlanExecutor, SyncConfig
    from repro.core.grad_sync import plan_from_config

    mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
    tmpl = {"w": jnp.zeros((64, 33)), "b": jnp.zeros((17,))}
    grads = {"w": jax.random.normal(jax.random.PRNGKey(31), (8, 3, 64, 33)),
             "b": jax.random.normal(jax.random.PRNGKey(32), (8, 3, 17))}

    for name, args in (("int8_fused", ()), ("topk_fused",
                                            (("ratio", 0.25),))):
        plan_f = plan_from_config(
            SyncConfig(compressor=name, algo="ring", bucket_bytes=2048,
                       compressor_args=args), tmpl)
        assert all(b.fused for b in plan_f.buckets)
        plan_u = dataclasses.replace(plan_f, buckets=tuple(
            dataclasses.replace(b, fused=False) for b in plan_f.buckets))
        outs = {}
        for tag, plan in (("fused", plan_f), ("unfused", plan_u)):
            ex = PlanExecutor(plan, ("data",))

            def body(g):
                g0 = jax.tree.map(lambda x: x[0], g)
                st = ex.init_state(jax.tree.map(lambda x: x[0], g0))
                res, errs = [], []
                for s in range(3):
                    out, st = ex(jax.tree.map(lambda x: x[s], g0), st,
                                 jax.random.PRNGKey(0))
                    res.append(out)
                    errs.append([e for e in st["error"] if e is not None])
                return res, errs

            f = jax.shard_map(body, mesh=mesh,
                              in_specs=({"w": P("data", None, None, None),
                                         "b": P("data", None, None)},),
                              out_specs=(P(None), P(None)),
                              axis_names={"data"}, check_vma=False)
            outs[tag] = jax.jit(f)(grads)
        (res_f, errs_f), (res_u, errs_u) = outs["fused"], outs["unfused"]
        for s in range(3):
            assert len(errs_f[s]) == len(errs_u[s]) > 0
            for j, (a, b) in enumerate(zip(errs_f[s], errs_u[s])):
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b),
                    err_msg=f"{name} step {s} EF[{j}]")
            for k in ("w", "b"):
                a = np.asarray(res_f[s][k], np.float32)
                b = np.asarray(res_u[s][k], np.float32)
                if name == "topk_fused":
                    np.testing.assert_array_equal(
                        a, b, err_msg=f"{name} step {s} {k}")
                else:
                    tol = 2 * np.finfo(np.float32).eps * max(
                        1.0, np.abs(b).max())
                    assert np.abs(a - b).max() <= tol, (name, s, k)
    print("fused-vs-unfused bit trajectory ok (EF residuals bit-equal "
          "over 3 steps, int8 + topk, 8 ranks)")


def check_plan_executor_heterogeneous():
    """A CommPlan mixing dense/psum, packed int8/ring, and per-leaf topk
    must approximate the all-worker mean on a real 8-device mesh."""
    from repro.core import PlanExecutor
    from repro.core.schedule.planner import BucketPlan, CommPlan
    mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
    grads = {"w": jax.random.normal(jax.random.PRNGKey(11), (8, 64, 32)),
             "b": jax.random.normal(jax.random.PRNGKey(12), (8, 33))}
    ref = jax.tree.map(lambda g: np.asarray(g).mean(0), grads)
    # leaf order: b, w
    plan = CommPlan(buckets=(
        BucketPlan(leaves=(0,), compressor="none", algo="psum",
                   bucket_bytes=4 * 33),
        BucketPlan(leaves=(1,), compressor="int8", algo="ring",
                   bucket_bytes=4 * 64 * 32, pack=True),
    ))
    ex = PlanExecutor(plan, ("data",))

    def body(g, rng):
        g = jax.tree.map(lambda x: x[0], g)
        st = ex.init_state(g)
        out, st2 = ex(g, st, rng)
        return out

    f = jax.shard_map(body, mesh=mesh,
                      in_specs=({"w": P("data", None, None),
                                 "b": P("data", None)}, P()),
                      out_specs={"w": P(None, None), "b": P(None)},
                      axis_names={"data"}, check_vma=False)
    out = jax.jit(f)(grads, jax.random.PRNGKey(0))
    # dense psum bucket: exact; int8 bucket: close
    np.testing.assert_allclose(np.asarray(out["b"]), ref["b"], atol=1e-5)
    rel = float(jnp.max(jnp.abs(out["w"] - ref["w"]))) / \
        (np.abs(ref["w"]).max() + 1e-9)
    assert rel < 1.2, rel
    print("heterogeneous plan executor ok")


def check_local_sgd():
    from repro.core import average_params
    mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
    params = {"w": jax.random.normal(jax.random.PRNGKey(7), (8, 16))}
    f = jax.shard_map(lambda p: average_params(p, ("data",)),
                      mesh=mesh, in_specs=({"w": P("data", None)},),
                      out_specs={"w": P(None)}, axis_names={"data"},
                      check_vma=False)
    out = jax.jit(f)(params)
    np.testing.assert_allclose(np.asarray(out["w"])[0],
                               np.asarray(params["w"]).mean(0), atol=1e-5)
    print("local sgd averaging ok")


def check_param_round_strategy():
    """SyncStrategy param round on 8 REAL workers (DESIGN.md §7): per-worker
    diverged params go in with a leading worker axis, one anchor-delta
    round brings every worker to (≈, for the compressed plan) the mean."""
    from repro.core import PlanExecutor, SyncConfig, plan_from_config
    from repro.launch.steps import make_param_round_step

    mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
    anchor = {"w": jax.random.normal(jax.random.PRNGKey(11), (16, 8))}
    noise = jax.random.normal(jax.random.PRNGKey(12), (8, 16, 8)) * 0.01
    params_w = {"w": anchor["w"][None] + noise}   # 8 diverged workers

    for comp, tol in (("none", 1e-6), ("int8", 2e-3)):
        reducer = PlanExecutor(
            plan_from_config(SyncConfig(compressor=comp, bucket_bytes=0),
                             anchor), ("data",))
        round_fn = jax.jit(make_param_round_step(reducer, mesh, ("data",)))
        red_state = jax.tree.map(
            lambda s: jnp.broadcast_to(s, (8,) + s.shape),
            reducer.init_state(anchor))
        out, new_anchor, _ = round_fn(params_w, anchor, red_state,
                                      jax.random.PRNGKey(0))
        got = np.asarray(out["w"])
        want = np.asarray(params_w["w"]).mean(0)
        assert np.all(got == got[0:1]), f"{comp}: workers disagree"
        np.testing.assert_allclose(got[0], want, atol=tol)
        np.testing.assert_allclose(np.asarray(new_anchor["w"]), got[0],
                                   atol=1e-6)
    print("strategy param round ok")


from tiny_lm import TinyLM as _TinyLM, tiny_batch as _tiny_batch  # noqa: E402
from tiny_lm import TinyStackLM as _TinyStackLM  # noqa: E402


def check_pipeline_bit_exact():
    """ISSUE 4's tentpole acceptance criterion: the pipeline(S=2, M=4)
    1F1B train step on the 8-device pipe(2) x data(4) mesh must match the
    single-stage DP step (pipe(1) x data(4), same global batch, same M
    micro-batches) BIT-EXACTLY — params and optimizer state over 3 steps,
    adam + sgd — including under int8/top-k DP-edge compression (the
    per-row sync granularity makes the compressed wire stage-count
    invariant; matching params+moments over 3 steps implies the EF
    residual trajectories agree, since residuals feed every later step).

    What makes this exact (DESIGN.md §9): row-boundary optimization
    barriers keep XLA fusion from crossing potential cut points (so a
    row's forward/backward compiles identically at every stage count), and
    the optimizer updates the per-row-unstacked tree (same leaf shapes at
    every S).  Should the XLA-owned psum wire ever reorder its reduction
    between the two programs, the documented fallback is the §8 ulp
    tolerance — flip ``exact`` for that row.
    """
    from repro.core import GradientSynchronizer, SyncConfig
    from repro.launch.mesh import make_pipe_mesh
    from repro.launch.steps import make_pipeline_train_step
    from repro.optim import make_optimizer

    M = 4

    def run(S, opt_name, comp, algo):
        model = _TinyStackLM(blocks=2, n_stages=S)
        params = model.init(jax.random.PRNGKey(0))
        mesh = make_pipe_mesh(S, 4)
        opt = make_optimizer(opt_name, lr=0.05)
        engine = GradientSynchronizer(
            SyncConfig(compressor=comp, algo=algo, bucket_bytes=0),
            ("data",))
        step_fn, init_opt, init_ss = make_pipeline_train_step(
            model, opt, engine, mesh, M)
        shared, rows = model.split(params)
        p = {"shared": shared, "rows": rows}
        o, ss = init_opt(p), init_ss(p)
        jit = jax.jit(step_fn)
        rng = jax.random.PRNGKey(1)
        for s in range(3):
            p, o, ss, loss = jit(p, o, ss, _tiny_batch(s, batch=16, seq=12),
                                 jnp.asarray(s, jnp.int32),
                                 jax.random.fold_in(rng, s))
        from repro.launch.steps import merge_opt_rows
        merged = model.merge(p["shared"], p["rows"])
        return (merged, merge_opt_rows(o, model.layout.rows),
                float(loss["loss"]))

    for opt_name, comp, algo, exact in (
            ("adam", "none", "psum", True),
            ("adam", "none", "ring", True),
            ("adam", "int8", "ring", True),
            ("adam", "topk", "ring", True),
            ("sgd", "none", "ring", True),
            ("sgd", "none", "psum", True)):
        p1, o1, l1 = run(1, opt_name, comp, algo)
        p2, o2, l2 = run(2, opt_name, comp, algo)
        for (path, a), (_, b) in list(zip(
                jax.tree_util.tree_leaves_with_path(p1),
                jax.tree_util.tree_leaves_with_path(p2))) + list(zip(
                jax.tree_util.tree_leaves_with_path(o1),
                jax.tree_util.tree_leaves_with_path(o2))):
            a, b = np.asarray(a), np.asarray(b)
            what = (opt_name, comp, algo, jax.tree_util.keystr(path))
            if exact:
                assert np.array_equal(a, b), \
                    (what, np.abs(a - b).max())
            else:
                np.testing.assert_allclose(a, b, rtol=3e-5, atol=1e-7,
                                           err_msg=str(what))
        assert abs(l1 - l2) < 1e-5, (opt_name, comp, algo, l1, l2)
    print("pipeline S=2 bit-exact vs single-stage DP ok (adam/sgd x "
          "psum/ring/int8/topk, params + opt state, 3 steps)")


def check_pipeline_matches_classic_dp_step():
    """Anchor for the S=1 reference itself: the degenerate pipeline step
    (S=1, M=1, dense psum) against the classic replicated DP step
    (_make_synced_train_step) — same loss and ulp-tight params (the two
    programs differ only in vjp composition and XLA contraction)."""
    from repro.core import PlanExecutor, SyncConfig, plan_from_config
    from repro.core import GradientSynchronizer
    from repro.launch.mesh import make_pipe_mesh
    from repro.launch.steps import (_make_synced_train_step,
                                    make_pipeline_train_step)
    from repro.optim import make_optimizer

    model = _TinyStackLM(blocks=2, n_stages=1)
    params = model.init(jax.random.PRNGKey(0))
    opt = make_optimizer("adam", lr=0.05)
    batch = _tiny_batch(0, batch=16, seq=12)
    step_i = jnp.zeros((), jnp.int32)
    rng = jax.random.PRNGKey(1)

    mesh_c = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))
    exec_c = PlanExecutor(plan_from_config(SyncConfig(), params), ("data",))
    cstep, _, init_cs = _make_synced_train_step(model, opt, exec_c, mesh_c,
                                                ("data",))
    pc, oc, sc = params, opt.init(params), init_cs(params)
    pc, oc, _, lc = jax.jit(cstep)(pc, oc, sc, batch, step_i, rng)

    mesh_p = make_pipe_mesh(1, 4)
    engine = GradientSynchronizer(SyncConfig(bucket_bytes=0), ("data",))
    pstep, init_po, init_ps = make_pipeline_train_step(model, opt, engine,
                                                       mesh_p, 1)
    shared, rows = model.split(params)
    pp = {"shared": shared, "rows": rows}
    op, sp = init_po(pp), init_ps(pp)
    pp, op, _, lp = jax.jit(pstep)(pp, op, sp, batch, step_i, rng)
    merged = model.merge(pp["shared"], pp["rows"])

    lc, lp = float(lc["loss"]), float(lp["loss"])
    assert abs(lc - lp) < 1e-6, (lc, lp)
    for k in ("emb", "out", "b"):
        np.testing.assert_allclose(np.asarray(merged[k]),
                                   np.asarray(pc[k]),
                                   rtol=3e-5, atol=1e-7, err_msg=k)
    for k in ("w1", "b1", "w2"):
        np.testing.assert_allclose(np.asarray(merged["blocks"][k]),
                                   np.asarray(pc["blocks"][k]),
                                   rtol=3e-5, atol=1e-7, err_msg=k)
    print("pipeline S=1/M=1 matches the classic DP step ok (ulp-tight)")


def check_sharded_dp_bit_exact():
    """The tentpole acceptance criterion: sharded-DP (reduce-scatter grads,
    1/p-partitioned master params + Adam moments, params all-gather) must
    be BIT-EXACT vs replicated DP for dense fp32 over 3 steps on a real
    8-device mesh — for both the explicit ring wires and psum — and the
    per-device optimizer-state arrays must actually be 1/8 the replicated
    footprint.  Compressed (int8) wires must match bit-for-bit too (same
    payload gather, sliced), including the EF residual trajectory."""
    from repro.core import PlanExecutor, ShardLayout, SyncConfig
    from repro.core.grad_sync import sharded_plan_from_config
    from repro.launch.steps import (_make_synced_train_step,
                                    make_sharded_train_step)
    from repro.optim import make_optimizer, make_sharded_optimizer

    mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
    model = _TinyLM()
    params0 = model.init(jax.random.PRNGKey(0))
    rng = jax.random.PRNGKey(1)

    for opt_name, algo, comp, exact in (
            ("adam", "ring", "none", True),
            ("adam", "psum", "none", True),
            ("adam", "ring", "int8", True),
            ("sgd", "ring", "none", True),
            ("lamb", "ring", "none", False)):   # layerwise norms: psum order
        cfg = SyncConfig(compressor=comp, algo=algo,
                         bucket_bytes=2048 if comp != "none" else 32 * 2**20)
        shared_plan = sharded_plan_from_config(cfg, params0)
        opt = make_optimizer(opt_name, lr=0.05)

        # replicated reference runs the SAME plan (same bucket boundaries:
        # ring chunk sums depend on them — DESIGN.md §8)
        step_fn, _, init_ss = _make_synced_train_step(
            model, opt, PlanExecutor(shared_plan, ("data",)), mesh,
            ("data",))
        p_r, os_r, ss_r = params0, opt.init(params0), init_ss(params0)
        jit_r = jax.jit(step_fn)
        for s in range(3):
            p_r, os_r, ss_r, _ = jit_r(p_r, os_r, ss_r, _tiny_batch(s),
                                       jnp.asarray(s, jnp.int32),
                                       jax.random.fold_in(rng, s))

        ex = PlanExecutor(shared_plan, ("data",))
        layout = ShardLayout.from_plan(shared_plan, params0, (8,))
        shopt = make_sharded_optimizer(opt_name, layout, ("data",), lr=0.05)
        sfn, init_rows, init_ss2 = make_sharded_train_step(
            model, ex, layout, shopt, mesh, ("data",))
        p_s, rows, ss_s = params0, init_rows(params0), init_ss2(params0)
        jit_s = jax.jit(sfn)
        for s in range(3):
            p_s, rows, ss_s, _ = jit_s(p_s, rows, ss_s, _tiny_batch(s),
                                       jnp.asarray(s, jnp.int32),
                                       jax.random.fold_in(rng, s))

        def cmp(a, b, what):
            a, b = np.asarray(a), np.asarray(b)
            if exact:
                assert np.array_equal(a, b), \
                    (opt_name, algo, comp, what, np.abs(a - b).max())
            else:
                np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-7)

        for k in p_r:
            cmp(p_r[k], p_s[k], f"params/{k}")
        if opt_name in ("adam", "lamb"):
            for mom in ("m", "v"):
                full = layout.tree_from_rows(rows["opt"][mom], params0)
                for k in p_r:
                    cmp(os_r[mom][k], full[k], f"{mom}/{k}")
        master = layout.tree_from_rows(rows["master"], params0)
        for k in p_r:
            cmp(master[k], p_s[k], f"master/{k}")
        if comp != "none":
            for a, b in zip(ss_r["error"], ss_s["error"]):
                if a is not None:
                    cmp(a, b, "EF residual")

        # the memory identity: per-device partitioned state is 1/8 (+pad)
        n_total = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params0))
        per_dev = sum(b.m for b in layout.buckets)
        assert per_dev <= -(-n_total // 8) + len(layout.buckets) * 8, \
            (per_dev, n_total)
        for r in rows["master"]:
            assert r.shape[0] == 8    # leading worker axis, sharded
    print("sharded-DP bit-exact vs replicated ok (ring/psum, int8, "
          "adam/sgd exact; lamb close)")


def check_sharded_checkpoint_reshard():
    """Partitioned optimizer state round-trips through a checkpoint onto a
    DIFFERENT mesh shape bit-equal: save 8-way shard rows, restore, re-chunk
    to a 4-way (and 2x2) layout — the reconstructed full state is identical
    because every layout chunks the same canonical flat buffer."""
    from repro.checkpoint import restore, save
    from repro.core import ShardLayout, SyncConfig
    from repro.core.grad_sync import sharded_plan_from_config
    import tempfile

    model = _TinyLM()
    params = model.init(jax.random.PRNGKey(3))
    plan = sharded_plan_from_config(SyncConfig(bucket_bytes=4096), params)
    lay8 = ShardLayout.from_plan(plan, params, (8,))
    rows8 = lay8.shard_rows(params)

    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "ckpt")
        save(path, {"master": rows8}, step=7)
        like = {"master": [np.zeros(r.shape, np.float32) for r in rows8]}
        restored = restore(path, like)

    lay4, rows4 = lay8.reshard(restored["master"], (4,))
    lay22, rows22 = lay8.reshard(restored["master"], (2, 2))
    want = jax.tree.leaves(params)
    for lay, rows in ((lay4, rows4), (lay22, rows22), (lay8, rows8)):
        got = lay.tree_from_rows(rows, params)
        for a, b in zip(jax.tree.leaves(got), want):
            assert np.array_equal(np.asarray(a),
                                  np.asarray(b).astype(np.float32)), \
                lay.axis_sizes
    print("sharded checkpoint reshard ok (8 -> 4, 8 -> 2x2, bit-equal)")


def check_reduce_scatter_all_gather_roundtrip():
    """The sharded wire primitives on a 2-axis (4x2) mesh: nested-canonical
    reduce_scatter chunks must agree with the host-side chunking twin, and
    all_gather_shards must invert them exactly."""
    from repro.core import chunk_rows
    from repro.core.collectives import all_gather_shards, reduce_scatter

    mesh = jax.make_mesh((4, 2), ("data", "pod"),
                         axis_types=(AxisType.Auto,) * 2)
    n = 37
    x = jax.random.normal(jax.random.PRNGKey(9), (8, n))
    ref = np.asarray(x).sum(0)
    for algo in ("psum", "ring", "hierarchical"):
        def body(v):
            v = v[0]
            sh = reduce_scatter(v, algo, ("data", "pod"))
            return sh[None], all_gather_shards(sh, n, algo, ("data", "pod"))
        f = jax.shard_map(body, mesh=mesh,
                          in_specs=P(("data", "pod"), None),
                          out_specs=(P(("data", "pod"), None), P(None)),
                          axis_names={"data", "pod"}, check_vma=False)
        shards, full = jax.jit(f)(x)
        want = chunk_rows(ref, (4, 2))
        np.testing.assert_allclose(np.asarray(shards).reshape(want.shape),
                                   want, atol=1e-4, err_msg=algo)
        np.testing.assert_allclose(np.asarray(full), ref, atol=1e-4,
                                   err_msg=algo)
    print("2-axis reduce_scatter/all_gather roundtrip ok")


def check_sharded_segment_ids_multi_axis():
    """The layerwise optimizers derive each rank's leaf-segment ids from
    static offsets + iota (no params-sized table on device); on a (4, 2)
    nested mesh every rank's derived ids must equal the host-side
    ``ShardLayout.seg_rows`` reference row."""
    from repro.core import ShardLayout, SyncConfig
    from repro.core.grad_sync import sharded_plan_from_config
    from repro.optim.sharded import _my_segments

    mesh = jax.make_mesh((4, 2), ("data", "pod"),
                         axis_types=(AxisType.Auto,) * 2)
    params = {"a": jnp.ones((5, 3)), "b": jnp.ones((7,)),
              "c": jnp.ones((11,))}
    plan = sharded_plan_from_config(SyncConfig(bucket_bytes=48), params)
    lay = ShardLayout.from_plan(plan, params, (4, 2))

    def body():
        return tuple(s[None] for s in _my_segments(lay, ("data", "pod")))

    f = jax.shard_map(body, mesh=mesh, in_specs=(),
                      out_specs=tuple(P(("data", "pod"), None)
                                      for _ in lay.buckets),
                      axis_names={"data", "pod"}, check_vma=False)
    got = jax.jit(f)()
    for j in range(len(lay.buckets)):
        np.testing.assert_array_equal(np.asarray(got[j]), lay.seg_rows(j),
                                      err_msg=f"bucket {j}")
    print("sharded segment-id derivation ok (4x2 mesh, vs host reference)")


def check_topology_dispatched_collectives():
    """ISSUE 5 satellite: collectives under the axis→tier dispatch.  An
    8-device host realises ``node:2@datacenter,device:4@fast_ici`` as a
    (2, 4) tiered mesh (``make_topology_mesh``); ``axes_for_topology``
    lists the shard_map axes innermost-first, so ``hierarchical_allreduce``
    runs its ring phases on the ``device`` (fast) axis and the shard ring
    on ``node`` — and must match ``psum`` within ulp tolerance (the
    reductions contract in different orders).  ring/mesh2d/tree are held
    to the same bound under the same dispatch."""
    from repro.core.collectives import allreduce, axes_for_topology
    from repro.core.schedule.topology import Topology
    from repro.launch.mesh import make_topology_mesh

    topo = Topology.from_spec("node:2@datacenter,device:4@fast_ici")
    mesh = make_topology_mesh(topo)
    assert mesh.axis_names == ("node", "device") and mesh.shape["node"] == 2
    axes = axes_for_topology(topo)
    assert axes == ("device", "node")   # inner ring on the fast tier
    x = jax.random.normal(jax.random.PRNGKey(21), (8, 1031))

    def run(algo):
        f = jax.shard_map(lambda v: allreduce(v, algo, axes),
                          mesh=mesh, in_specs=P(("node", "device"), None),
                          out_specs=P(None, None),
                          axis_names=set(axes), check_vma=False)
        return np.asarray(jax.jit(f)(x))[0]

    want = run("psum")
    for algo in ("hierarchical", "ring", "mesh2d", "tree"):
        got = run(algo)
        denom = np.abs(want).max() + 1e-9
        rel = np.abs(got - want).max() / denom
        assert rel < 1e-5, (algo, rel)
        # the manual algorithms must really dispatch over both tier axes
        f = jax.shard_map(lambda v: allreduce(v, algo, axes),
                          mesh=mesh, in_specs=P(("node", "device"), None),
                          out_specs=P(None, None),
                          axis_names=set(axes), check_vma=False)
        txt = jax.jit(f).lower(x).compile().as_text()
        assert "collective-permute" in txt, algo

    # 3-tier topology (2x2x2): hierarchical's shard must ring over EVERY
    # outer axis (dropping one would silently leave pod groups diverged —
    # the bug class this check exists for); mesh2d must REFUSE 3 axes.
    topo3 = Topology.from_spec("pod:2@datacenter,node:2@commodity,"
                               "device:2@fast_ici")
    mesh3 = make_topology_mesh(topo3)
    axes3 = axes_for_topology(topo3)
    assert axes3 == ("device", "node", "pod")
    spec3 = P(("pod", "node", "device"), None)

    def run3(algo):
        f = jax.shard_map(lambda v: allreduce(v, algo, axes3),
                          mesh=mesh3, in_specs=spec3,
                          out_specs=P(None, None),
                          axis_names=set(axes3), check_vma=False)
        return np.asarray(jax.jit(f)(x))[0]

    want3 = run3("psum")
    for algo in ("hierarchical", "ring", "tree"):
        got = run3(algo)
        rel = np.abs(got - want3).max() / (np.abs(want3).max() + 1e-9)
        assert rel < 1e-5, (algo, rel)
    try:
        run3("mesh2d")
    except ValueError as e:
        assert "two-axis" in str(e), e
    else:
        raise AssertionError("mesh2d over 3 axes must raise ValueError")
    print("topology-dispatched collectives ok (node:2 x device:4 and "
          "2x2x2: hierarchical/ring/tree vs psum within ulp; mesh2d "
          "refuses 3 axes)")


def check_tree_nonpow2_raises_value_error():
    """Satellite: the tree collective on a non-power-of-two axis raises
    ValueError at trace time (was a bare assert, stripped under -O)."""
    from repro.core.collectives import allreduce

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:6]), ("data",))
    x = jax.random.normal(jax.random.PRNGKey(22), (6, 16))
    f = jax.shard_map(lambda v: allreduce(v, "tree", ("data",)),
                      mesh=mesh, in_specs=P("data", None),
                      out_specs=P(None, None),
                      axis_names={"data"}, check_vma=False)
    try:
        jax.jit(f).lower(x)
    except ValueError as e:
        assert "power-of-two" in str(e), e
    else:
        raise AssertionError("tree over 6 ranks must raise ValueError")
    print("tree non-power-of-two ValueError ok")


def check_hlo_collective_parse():
    from repro.launch.hlo_analysis import analyze
    mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
    xs = jax.device_put(jnp.ones((8, 1024), jnp.float32),
                        NamedSharding(mesh, P("data", None)))
    g = jax.jit(lambda x: x.sum(0), out_shardings=NamedSharding(mesh, P(None)))
    txt = g.lower(xs).compile().as_text()
    s = analyze(txt, total_devices=8)
    assert s.collective_counts.get("all-reduce") == 1
    assert s.collective_operand_bytes == 4096.0
    assert abs(s.collective_wire_bytes - 2 * 4096 * 7 / 8) < 1
    print("hlo parse ok")


def check_all_to_all_bit_identity():
    """ISSUE 9: the expert-dispatch edge.  ``all_to_all`` on an 8-rank axis
    must match the gather-and-slice reference (all_gather the full (p, p,
    m, ...) exchange, slice column i) BIT-EXACTLY for both wire variants —
    chunks move verbatim, no arithmetic — be an involution (the exchange is
    a rank<->chunk transpose), transpose under autodiff to the REVERSE
    all-to-all (the combine edge), and the ring variant must really lower
    to collective-permute rotations, not a fused all-to-all."""
    from repro.core.collectives.api import A2A_VARIANTS, all_to_all

    mesh = jax.make_mesh((8,), ("ep",), axis_types=(AxisType.Auto,))
    x = jax.random.normal(jax.random.PRNGKey(17), (8, 8, 5, 7))
    w = jax.random.normal(jax.random.PRNGKey(18), (8, 8, 5, 7))

    for variant in A2A_VARIANTS:
        def body(xs, ws):
            c = xs[0]                                   # (p, m, ...) chunks
            out = all_to_all(c, "ep", variant)
            # gather-and-slice reference: full[j] = rank j's chunk row;
            # my row of the exchange is column i of the gathered matrix
            full = jax.lax.all_gather(c, "ep")          # (p, p, m, ...)
            ref = full[:, jax.lax.axis_index("ep")]
            back = all_to_all(out, "ep", variant)       # involution
            g = jax.grad(lambda t: jnp.sum(
                ws[0] * all_to_all(t, "ep", variant)))(c)
            return out[None], ref[None], back[None], g[None]

        f = jax.shard_map(body, mesh=mesh,
                          in_specs=(P("ep"), P("ep")),
                          out_specs=(P("ep"),) * 4,
                          axis_names={"ep"}, check_vma=False)
        out, ref, back, g = jax.jit(f)(x, w)
        assert np.array_equal(np.asarray(out), np.asarray(ref)), variant
        # global view: out[r, j] = x[j, r] — the rank<->chunk transpose
        assert np.array_equal(np.asarray(out),
                              np.asarray(x).transpose(1, 0, 2, 3)), variant
        assert np.array_equal(np.asarray(back), np.asarray(x)), variant
        # d/dx sum(w * a2a(x)) = reverse-a2a(w) = a2a(w) (involution)
        assert np.array_equal(np.asarray(g),
                              np.asarray(w).transpose(1, 0, 2, 3)), variant
        txt = jax.jit(f).lower(x, w).compile().as_text()
        if variant == "ring":
            assert "collective-permute" in txt, "ring a2a must ppermute"
    print("all_to_all bit-identity ok (direct/ring vs gather-and-slice, "
          "involution, autodiff reverse edge)")


def _adam_sgd_step(p, g, m, v, t, lr=0.05, b1=0.9, b2=0.999, eps=1e-8):
    """Inline elementwise adam (same arithmetic on full arrays and on
    shards — the property the TP/EP bit-exactness checks lean on)."""
    upd = jax.tree.map(lambda mi, gi: b1 * mi + (1 - b1) * gi, m, g)
    vel = jax.tree.map(lambda vi, gi: b2 * vi + (1 - b2) * gi * gi, v, g)
    def leaf(pi, mi, vi):
        mh = mi / (1 - b1 ** t)
        vh = vi / (1 - b2 ** t)
        return pi - lr * mh / (jnp.sqrt(vh) + eps)
    return jax.tree.map(leaf, p, upd, vel), upd, vel


def check_tp_dp_bit_exact():
    """ISSUE 9's tentpole acceptance criterion, TP leg: a TP=2 x DP=4
    train step (Megatron f/g wire — ``mlp_tp`` under shard_map with
    wi_gate/wi_up column-sharded and wo row-sharded over the tp axis) must
    match the unsharded DP=4 step (``mlp_blocked(blocks=2)`` — the same
    contraction order a tp pair performs, on one device) BIT-EXACTLY:
    params AND adam moments over 3 steps on the 8-device (data=4, tp=2)
    mesh.  What makes this exact: tp_out's forward psum of p=2 partials is
    one commutative float add (== the blocked reference's pairwise sum),
    and tp_in's backward psum makes every non-tp parameter's gradient
    bit-identical across tp ranks, so BOTH programs reduce grads over the
    data axis only, with the same 4-way tree."""
    from repro.models.layers import mlp_blocked, mlp_tp

    d, dff, vocab = 16, 32, 64
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    params0 = {"emb": jax.random.normal(ks[0], (vocab, d)) * 0.1,
               "wi_gate": jax.random.normal(ks[1], (d, dff)) * 0.3,
               "wi_up": jax.random.normal(ks[2], (d, dff)) * 0.3,
               "wo": jax.random.normal(ks[3], (dff, d)) * 0.3,
               "out": jax.random.normal(ks[4], (d, vocab)) * 0.1,
               "b": jnp.zeros((vocab,))}

    def loss_with(mlp_fn, p, toks):
        x = p["emb"][toks[:, :-1]]
        # barrier at the swap boundary (the DESIGN.md §9 trick): keeps XLA
        # fusion from crossing into the mlp, so the embed/softmax graph —
        # and its backward — compiles identically whether the block inside
        # is mlp_tp or mlp_blocked
        xb = jax.lax.optimization_barrier(x)
        h = x + jax.lax.optimization_barrier(mlp_fn(p, xb))
        logits = h @ p["out"] + p["b"]
        lp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(lp, toks[:, 1:][..., None], -1))

    def make_body(mlp_fn):
        def body(p, m, v, toks, t):
            l, g = jax.value_and_grad(
                lambda q: loss_with(mlp_fn, q, toks))(p)
            g = jax.tree.map(lambda gi: jax.lax.psum(gi, "data") / 4.0, g)
            p, m, v = _adam_sgd_step(p, g, m, v, t)
            return jax.lax.psum(l, "data") / 4.0, p, m, v
        return body

    def run(mesh, specs, body):
        zeros = jax.tree.map(jnp.zeros_like, params0)
        p, m, v = params0, zeros, zeros
        f = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(specs, specs, specs, P("data"), P()),
            out_specs=(P(), specs, specs, specs),
            axis_names=set(mesh.axis_names), check_vma=False))
        for s in range(3):
            toks = _tiny_batch(s, batch=16, seq=12)["tokens"]
            l, p, m, v = f(p, m, v, toks, jnp.asarray(s + 1, jnp.float32))
        return float(l), p, m, v

    mesh_tp = jax.make_mesh((4, 2), ("data", "tp"),
                            axis_types=(AxisType.Auto,) * 2)
    specs_tp = {"emb": P(), "wi_gate": P(None, "tp"), "wi_up": P(None, "tp"),
                "wo": P("tp", None), "out": P(), "b": P()}
    l_tp, p_tp, m_tp, v_tp = run(
        mesh_tp, specs_tp,
        make_body(lambda p, x: mlp_tp(p, x, axis="tp")))

    mesh_dp = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))
    specs_dp = {k: P() for k in params0}
    l_dp, p_dp, m_dp, v_dp = run(
        mesh_dp, specs_dp,
        make_body(lambda p, x: mlp_blocked(p, x, blocks=2)))

    assert abs(l_tp - l_dp) < 1e-6, (l_tp, l_dp)
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path((p_tp, m_tp, v_tp)),
            jax.tree_util.tree_leaves_with_path((p_dp, m_dp, v_dp))):
        a, b = np.asarray(a), np.asarray(b)
        assert np.array_equal(a, b), \
            (jax.tree_util.keystr(path), np.abs(a - b).max())
    print("TP=2 x DP=4 bit-exact vs unsharded DP=4 ok "
          "(params + adam moments, 3 steps)")


def check_ep_dp_bit_exact():
    """ISSUE 9's tentpole acceptance criterion, EP leg: an EP=2 x DP=4
    MoE train step (``moe_ffn(ep_axis='ep')`` — experts sharded E/ep per
    rank, capacity buffer exchanged with ``all_to_all`` dispatch/combine)
    must match the unsharded DP=4 step (``moe_ffn(groups=2)`` — the same
    per-group capacity math with both of an ep pair's token groups
    source-batched on one device) BIT-EXACTLY: expert params AND adam
    moments over 3 steps, both wire variants.  Chunks move verbatim and
    the expert einsums treat e/s as batch dims, so the only float sums are
    the SAME contractions in both programs; expert grads reduce over the
    data axis only (ep contributions arrive through the combine edge's
    autodiff, already summed inside the einsum).  The router stays frozen:
    routing is pure-DP compute (each rank routes its own tokens, no ep
    wire), and training it would hang grad equality on an 8-way-vs-4-way
    psum tree rather than on the EP wire this check pins.  Loss scalars
    differ in the last bits for exactly that reason — compared loosely."""
    from repro.configs.base import ModelConfig
    from repro.models import moe

    cfg = ModelConfig(name="t", family="qwen3", num_layers=1, d_model=16,
                      num_heads=2, num_kv_heads=2, d_ff=32, vocab_size=64,
                      num_experts=4, top_k=2, moe_d_ff=24,
                      capacity_factor=1.5)
    d, E = 16, 4
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    router = jax.random.normal(ks[0], (d, E)) * 0.1
    ew0 = {"wi_gate": jax.random.normal(ks[1], (E, d, 24)) * 0.3,
           "wi_up": jax.random.normal(ks[2], (E, d, 24)) * 0.3,
           "wo": jax.random.normal(ks[3], (E, 24, d)) * 0.3}

    def batch(s):
        return jax.random.normal(jax.random.fold_in(ks[4], s), (8, 4, d))

    def make_body(moe_kwargs, loss_axes):
        def body(ew, m, v, xs, t):
            def loss_fn(w):
                out, _ = moe.moe_ffn(dict(w, router=router), cfg, xs,
                                     **moe_kwargs)
                return jnp.sum(out ** 2)
            l, g = jax.value_and_grad(loss_fn)(ew)
            g = jax.tree.map(lambda gi: jax.lax.psum(gi, "data") / 4.0, g)
            ew, m, v = _adam_sgd_step(ew, g, m, v, t)
            return jax.lax.psum(l, loss_axes), ew, m, v
        return body

    def run(mesh, espec, xspec, body):
        zeros = jax.tree.map(jnp.zeros_like, ew0)
        ew, m, v = ew0, zeros, zeros
        specs = {k: espec for k in ew0}
        f = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(specs, specs, specs, xspec, P()),
            out_specs=(P(), specs, specs, specs),
            axis_names=set(mesh.axis_names), check_vma=False))
        for s in range(3):
            l, ew, m, v = f(ew, m, v, batch(s),
                            jnp.asarray(s + 1, jnp.float32))
        return float(l), ew, m, v

    mesh_dp = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))
    l_dp, ew_dp, m_dp, v_dp = run(
        mesh_dp, P(), P("data"),
        make_body({"groups": 2}, ("data",)))

    mesh_ep = jax.make_mesh((4, 2), ("data", "ep"),
                            axis_types=(AxisType.Auto,) * 2)
    for variant in ("direct", "ring"):
        l_ep, ew_ep, m_ep, v_ep = run(
            mesh_ep, P("ep"), P(("data", "ep")),
            make_body({"ep_axis": "ep", "a2a_variant": variant},
                      ("data", "ep")))
        assert abs(l_ep - l_dp) < 1e-4 * max(abs(l_dp), 1.0), \
            (variant, l_ep, l_dp)
        for (path, a), (_, b) in zip(
                jax.tree_util.tree_leaves_with_path((ew_ep, m_ep, v_ep)),
                jax.tree_util.tree_leaves_with_path((ew_dp, m_dp, v_dp))):
            a, b = np.asarray(a), np.asarray(b)
            assert np.array_equal(a, b), \
                (variant, jax.tree_util.keystr(path), np.abs(a - b).max())
    print("EP=2 x DP=4 bit-exact vs unsharded DP=4 ok "
          "(direct/ring a2a, expert params + adam moments, 3 steps)")


def check_moe_counts_shard_map():
    """The MoE capacity counters (DESIGN.md §14) come back from a
    shard_map step body as device scalars: each data shard routes its own
    tokens against its own capacity, and the psum over the data axis is
    the sum of the shards' counts.  Checked against the same routing run
    shard by shard outside shard_map, with the size-1 model axis named
    manual (``data(8) x model(1)``, the standard session mesh) and with a
    live model axis left auto (``data(4) x model(2)``)."""
    from repro.configs.base import ModelConfig
    from repro.models import moe
    from repro.models.sharding_ctx import manual_axes, manual_region, mesh_ctx

    cfg = ModelConfig(name="t", family="qwen3", num_layers=1, d_model=16,
                      num_heads=2, num_kv_heads=2, d_ff=32, vocab_size=64,
                      num_experts=4, top_k=2, moe_d_ff=24,
                      capacity_factor=0.5)          # forced overflow
    d, E = 16, 4
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    params = {"router": jax.random.normal(ks[0], (d, E)) * 0.1,
              "wi_gate": jax.random.normal(ks[1], (E, d, 24)) * 0.3,
              "wi_up": jax.random.normal(ks[2], (E, d, 24)) * 0.3,
              "wo": jax.random.normal(ks[3], (E, 24, d)) * 0.3}
    x = jax.random.normal(ks[4], (8, 4, d))

    def body(p, xs):
        with manual_region():
            _, aux = moe.moe_ffn(p, cfg, xs)
        return jax.lax.psum((aux["dropped"], aux["routed"]), "data")

    for shape in ((8, 1), (4, 2)):
        n = shape[0]
        want_d, want_r = 0.0, 0.0
        for xs in np.split(np.asarray(x), n):
            _, aux = jax.jit(lambda v: moe.moe_ffn(params, cfg, v))(xs)
            want_d += float(aux["dropped"])
            want_r += float(aux["routed"])
        mesh = jax.make_mesh(shape, ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        with mesh_ctx(mesh, ("data",)):
            f = jax.jit(jax.shard_map(
                body, mesh=mesh,
                in_specs=({k: P() for k in params}, P("data")),
                out_specs=P(), axis_names=manual_axes(mesh, ("data",)),
                check_vma=False))
            dropped, routed = (float(v) for v in f(params, x))
        assert routed == want_r == x.shape[0] * x.shape[1] * cfg.top_k, \
            (shape, routed, want_r)
        assert dropped == want_d > 0, (shape, dropped, want_d)
    print("moe capacity counters under shard_map ok (data(8) x model(1), "
          "data(4) x model(2): the sum of the shards' counts)")


if __name__ == "__main__":
    check_collectives()
    check_ring_fused()
    check_fused_bit_trajectory()
    check_grad_sync()
    check_error_feedback_converges_distributed()
    check_plan_executor_heterogeneous()
    check_local_sgd()
    check_param_round_strategy()
    check_sharded_dp_bit_exact()
    check_pipeline_bit_exact()
    check_pipeline_matches_classic_dp_step()
    check_sharded_checkpoint_reshard()
    check_reduce_scatter_all_gather_roundtrip()
    check_sharded_segment_ids_multi_axis()
    check_topology_dispatched_collectives()
    check_tree_nonpow2_raises_value_error()
    check_hlo_collective_parse()
    check_all_to_all_bit_identity()
    check_tp_dp_bit_exact()
    check_ep_dp_bit_exact()
    check_moe_counts_shard_map()
    print("ALL MULTI-DEVICE CHECKS PASSED")
