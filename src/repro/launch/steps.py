"""Step functions wired for pjit: vanilla (paper-baseline BSP data parallel,
XLA-inserted collectives) and comm-optimized (shard_map manual over the data
axes with the GradientSynchronizer's explicit compress + collective path).

The vanilla step with FSDP sharding is what every (arch x shape) baseline
dry-run lowers; the comm-optimized step is the paper's §3/§4 machinery and
is exercised on archs whose parameters fit a pure DP+TP layout.

Every train-step program runs its parts under named scopes, which name the
compiled program's ops and so the ops of a device trace: ``forward`` for the
loss and its gradient (backward ops keep JAX's ``transpose(jvp(...))`` name
stack under it; ops that remat recomputes count there), ``optimizer`` for
the update and its apply, and ``grad_sync/bucket_<i>`` for each bucket a
``PlanExecutor`` exchanges.  Each returns the MoE capacity counters with
the loss: its loss output is ``{"loss", "moe_dropped", "moe_routed"}``
(``Model.loss_and_counts``; the loss a mean, the counts summed over the
data shards).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core import GradientSynchronizer, PlanExecutor, SyncConfig
from repro.core.schedule.planner import CommPlan
from repro.models import Model
from repro.models.sharding_ctx import manual_axes
from repro.optim import apply_updates, make_optimizer


def _forward(model, params, batch):
    """``(loss, counts, grads)`` under the ``forward`` scope."""
    with jax.named_scope("forward"):
        (loss, counts), grads = jax.value_and_grad(
            model.loss_and_counts, has_aux=True)(params, batch)
    return loss, counts, grads


def _optimize(optimizer, grads, opt_state, params, step):
    """``(params, opt_state)`` after the update, under ``optimizer``."""
    with jax.named_scope("optimizer"):
        updates, opt_state = optimizer.update(grads, opt_state, params, step)
        return apply_updates(params, updates), opt_state


def _step_out(loss, counts, axes=()):
    """A step's loss output: the loss (the mean over the manual ``axes``)
    with the counters (their sum)."""
    if axes:
        loss = jax.lax.pmean(loss, tuple(axes))
        counts = jax.lax.psum(counts, tuple(axes))
    return dict(counts, loss=loss)


# ---------------------------------------------------------------------------
# Vanilla BSP step (survey §2.4.1 baseline) — pjit/XLA collectives
# ---------------------------------------------------------------------------

def make_train_step(model: Model, optimizer, microbatches: int = 1):
    """BSP train step.  ``microbatches > 1`` runs gradient accumulation: the
    global batch is split along dim 0 and forward/backward runs as a scan,
    bounding activation memory at 1/M of the full batch (survey §3.1.1 —
    accumulation is how large-batch recipes actually execute) while keeping
    the optimizer step and gradient synchronization per-step identical."""
    def train_step(params, opt_state, batch, step):
        if microbatches <= 1:
            loss, counts, grads = _forward(model, params, batch)
        else:
            B = jax.tree.leaves(batch)[0].shape[0]
            assert B % microbatches == 0, (B, microbatches)
            mb = B // microbatches

            def body(acc, i):
                tot_loss, c_acc, g_acc = acc
                bslice = jax.tree.map(
                    lambda x: jax.lax.dynamic_slice_in_dim(x, i * mb, mb, 0),
                    batch)
                l, c, g = _forward(model, params, bslice)
                g_acc = jax.tree.map(
                    lambda a, gg: a + gg.astype(jnp.float32), g_acc, g)
                c_acc = jax.tree.map(jnp.add, c_acc, c)
                return (tot_loss + l, c_acc, g_acc), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            no_counts = {"moe_dropped": jnp.zeros(()),
                         "moe_routed": jnp.zeros(())}
            (loss_sum, counts, grads), _ = jax.lax.scan(
                body, (jnp.zeros(()), no_counts, zeros),
                jnp.arange(microbatches))
            loss = loss_sum / microbatches
            grads = jax.tree.map(lambda g: g / microbatches, grads)
        params, opt_state = _optimize(optimizer, grads, opt_state, params,
                                      step)
        return params, opt_state, _step_out(loss, counts)

    return train_step


def make_prefill_step(model: Model):
    def prefill_step(params, batch):
        return model.prefill(params, batch)

    return prefill_step


def make_decode_step(model: Model, mla_absorb: bool = False,
                     moe_dispatch: bool = False):
    def decode_step(params, tokens, cache, pos):
        return model.decode_step(params, tokens, cache, pos,
                                 mla_absorb=mla_absorb,
                                 moe_dispatch=moe_dispatch)

    return decode_step


# ---------------------------------------------------------------------------
# Comm-optimized step (survey §3 + §4) — manual data axes via shard_map
# ---------------------------------------------------------------------------

def make_comm_optimized_train_step(model: Model, optimizer, sync: SyncConfig,
                                   mesh, data_axes: Sequence[str] = ("data",)):
    """Per-shard loss/backward; gradient exchange through the
    GradientSynchronizer (compression + explicit collective algorithm).

    Params must be laid out replicated over the data axes (pure DP+TP):
    use ``model.partition_specs('serve')`` which shards over 'model' only.
    The 'model' mesh axis stays auto — XLA partitions tensor-parallel math
    inside the shard_map body.
    """
    synchronizer = GradientSynchronizer(sync, tuple(data_axes))
    return _make_synced_train_step(model, optimizer, synchronizer, mesh,
                                   data_axes)


def make_planned_train_step(model: Model, plan: CommPlan, optimizer, mesh,
                            data_axes: Sequence[str] = ("data",)):
    """Like :func:`make_comm_optimized_train_step` but driven by a
    ``CommPlan`` (heterogeneous per-bucket strategies, ``--sync auto``):
    the PlanExecutor may compress one bucket over an explicit ring while the
    next goes dense over psum."""
    executor = PlanExecutor(plan, tuple(data_axes))
    return _make_synced_train_step(model, optimizer, executor, mesh,
                                   data_axes)


def _world_of(mesh, data_axes: Sequence[str]) -> int:
    world = 1
    for a in data_axes:
        world *= mesh.shape[a]
    return world


def broadcast_worker_state(tree, world: int):
    """Give every leaf a leading device axis of length ``world`` (to be
    sharded over the data axes): the layout of anything carried PER WORKER —
    EF residuals, and params/optimizer state under strategies with local
    phases (local SGD, push-pull), where workers genuinely diverge."""
    return jax.tree.map(
        lambda s: jnp.broadcast_to(s, (world,) + s.shape), tree)


def worker_view(tree):
    """Worker-0 slice of a per-worker tree (checkpointing / inspection)."""
    return jax.tree.map(lambda s: s[0], tree)


def _make_synced_train_step(model: Model, optimizer, synchronizer, mesh,
                            data_axes: Sequence[str],
                            per_worker_params: bool = False):
    """Shared shard_map step around any grad-sync engine exposing
    ``init_state(grads)`` and ``__call__(grads, state, rng)``.

    ``per_worker_params=True`` carries params/optimizer state with a leading
    per-worker axis (push-pull: gradients are synced but parameters have
    diverged during local phases, so they may differ across workers)."""
    world = _world_of(mesh, data_axes)

    def body(params, opt_state, sync_state, batch, step, rng):
        from repro.models.sharding_ctx import manual_region
        # error-feedback state is PER WORKER: it arrives with a leading
        # device axis of length 1 (sharded over the data axes) — strip it,
        # use it, put it back.  This both matches EF semantics and shards
        # the f32 residual (a full parameter copy) across the data axes
        # instead of replicating it (§Perf pair-3 iteration 5 finding).
        sync_state = jax.tree.map(lambda s: s[0], sync_state)
        if per_worker_params:
            params = jax.tree.map(lambda s: s[0], params)
            opt_state = jax.tree.map(lambda s: s[0], opt_state)
        with manual_region():
            loss, counts, grads = _forward(model, params, batch)
        grads, sync_state = synchronizer(grads, sync_state, rng)
        params, opt_state = _optimize(optimizer, grads, opt_state, params,
                                      step)
        # local losses differ per shard only through data; report the mean
        out = _step_out(loss, counts, data_axes)
        sync_state = jax.tree.map(lambda s: s[None], sync_state)
        if per_worker_params:
            params = jax.tree.map(lambda s: s[None], params)
            opt_state = jax.tree.map(lambda s: s[None], opt_state)
        return params, opt_state, sync_state, out

    # Specs describe only the MANUAL (data) axes: params / optimizer state
    # are replicated across them (P() prefix); the batch and the EF state
    # are sharded.  The 'model' axis stays auto — its tensor-parallel
    # layout comes from the jit in_shardings outside this shard_map.
    batch_spec = {"tokens": P(tuple(data_axes), None)}
    state_spec = P(tuple(data_axes))
    p_spec = state_spec if per_worker_params else P()

    def step_fn(params, opt_state, sync_state, batch, step, rng):
        f = jax.shard_map(
            body, mesh=mesh,
            in_specs=(p_spec, p_spec, state_spec, batch_spec, P(), P()),
            out_specs=(p_spec, p_spec, state_spec, P(), ),
            axis_names=manual_axes(mesh, data_axes),
            check_vma=False)
        return f(params, opt_state, sync_state, batch, step, rng)

    def init_sync_state(params):
        """Per-worker EF state with a leading device axis (shard over data).
        Takes the PLAIN params pytree (no worker axis) in either mode."""
        return broadcast_worker_state(synchronizer.init_state(params), world)

    return step_fn, synchronizer, init_sync_state


# ---------------------------------------------------------------------------
# Sharded data parallelism (ZeRO-style, DESIGN.md §8)
# ---------------------------------------------------------------------------

def make_sharded_train_step(model: Model, executor, layout, sharded_opt,
                            mesh, data_axes: Sequence[str] = ("data",)):
    """Sharded-DP step: gradients reduce-scatter per bucket to canonical
    owners (``PlanExecutor.sync_shards``), each rank updates only its (m,)
    slice of f32 master params + optimizer moments (``sharded_opt``, from
    ``repro.optim.make_sharded_optimizer``), and the updated master shards
    all-gather back into full params for the next forward.

    Params enter and leave REPLICATED over the data axes (the forward needs
    them whole); what is partitioned — the ~2-3× params of optimizer state —
    is carried as per-bucket shard rows with a leading device axis of length
    world, sharded over the data axes (each device holds exactly its own
    (1, m) slice): ``{"master": [rows...], "opt": <moments of rows>}``.

    Bit-compatibility (the conformance suite's promise): for dense fp32
    plans on psum/ring, params and reconstructed optimizer state match the
    replicated ``_make_synced_train_step`` path bit-for-bit — the scatter
    chunks equal the allreduce slices, the elementwise update commutes with
    slicing, and the gather moves exact values.
    """
    world = _world_of(mesh, data_axes)
    axes = tuple(data_axes)
    if tuple(b.leaves for b in executor.plan.buckets) != \
            tuple(b.leaves for b in layout.buckets):
        raise ValueError("ShardLayout does not match the executor's plan "
                         "buckets — build it with ShardLayout.from_plan on "
                         "the same CommPlan")
    batch_spec = {"tokens": P(tuple(data_axes), None)}
    state_spec = P(tuple(data_axes))

    def body(params, opt_rows, sync_state, batch, step, rng):
        from repro.core.collectives import all_gather_shards
        from repro.models.sharding_ctx import manual_region
        sync_state = jax.tree.map(lambda s: s[0], sync_state)
        opt = jax.tree.map(lambda s: s[0], opt_rows)
        with manual_region():
            loss, counts, grads = _forward(model, params, batch)
        gshards, sync_state = executor.sync_shards(grads, sync_state, rng)
        with jax.named_scope("optimizer"):
            updates, inner = sharded_opt.update(gshards, opt["opt"],
                                                opt["master"], step)
            # the add mirrors apply_updates on the replicated path (masters
            # ARE the f32 params); XLA's per-graph FMA contraction of this
            # add is the one place the two modes may differ in the last
            # ulp — see the conformance suite's tolerance notes (DESIGN.md
            # §8)
            masters = [m + u for m, u in zip(opt["master"], updates)]

            # forward edge: gather the updated 1/p master shards back to
            # full params (in the leaves' own dtypes)
            leaves = jax.tree.leaves(params)
            out = [None] * len(leaves)
            for b, bl, shard in zip(executor.plan.buckets, layout.buckets,
                                    masters):
                full = all_gather_shards(shard, bl.n, b.algo, axes)
                off = 0
                for i, sz in zip(bl.leaves, bl.sizes):
                    out[i] = full[off:off + sz].reshape(
                        leaves[i].shape).astype(leaves[i].dtype)
                    off += sz
            new_params = jax.tree.unflatten(jax.tree.structure(params), out)

        lead = lambda t: jax.tree.map(lambda s: s[None], t)
        return (new_params, lead({"master": masters, "opt": inner}),
                lead(sync_state), _step_out(loss, counts, data_axes))

    def step_fn(params, opt_rows, sync_state, batch, step, rng):
        f = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), state_spec, state_spec, batch_spec, P(), P()),
            out_specs=(P(), state_spec, state_spec, P()),
            axis_names=manual_axes(mesh, data_axes),
            check_vma=False)
        return f(params, opt_rows, sync_state, batch, step, rng)

    def init_opt_rows(params):
        """Partitioned state: per-bucket f32 master rows (world, m) sliced
        canonically from the current params, plus the sharded optimizer's
        moments over them (zeros, same geometry)."""
        masters = layout.shard_rows(params)
        return {"master": masters, "opt": sharded_opt.init(masters)}

    def init_sync_state(params):
        return broadcast_worker_state(executor.init_state(params), world)

    return step_fn, init_opt_rows, init_sync_state


# ---------------------------------------------------------------------------
# Pipeline parallelism (1F1B micro-batching over a pipe axis, DESIGN.md §9)
# ---------------------------------------------------------------------------

def pipe_spec_tree(template, pipe_axis: str = "pipe"):
    """Per-leaf PartitionSpec tree for pipeline-mode state: any leaf under a
    ``"rows"`` key (per-stage layer rows, or optimizer moments over them)
    carries the leading stage axis sharded over ``pipe``; everything else
    (embed / final norm / lm head and their moments) is replicated."""
    def spec(path, _):
        if any(getattr(e, "key", None) == "rows" for e in path):
            return P(pipe_axis)
        return P()
    return jax.tree_util.tree_map_with_path(spec, template)


def unstack_rows(rows_local, rows_per_stage: int):
    """Stage rows (R/S, ...) -> list of R/S per-row trees: the DP gradient
    edge syncs PER LAYER ROW so compression granularity (int8 scales, top-k
    masks, EF residuals) is identical for every stage count — the
    bit-compatibility contract of the conformance suite (DESIGN.md §9)."""
    return [jax.tree.map(lambda x, i=i: x[i], rows_local)
            for i in range(rows_per_stage)]


def restack_rows(row_trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *row_trees)


def merge_opt_rows(state, rows: int):
    """Leaf-shaped view of pipeline optimizer state: wherever the state
    mirrors the stage tree (``{"shared": ..., "rows": [per-row trees,
    leaves (S, ...)]}``), stack the per-row entries back into the stack's
    ``(R, ...)`` leaves (row r lives at stage r // (R/S), slot r % (R/S)
    — the same stage-major order ``StagedModel.split`` cuts).  Shared by
    ``TrainSession.full_opt_state`` and the conformance checks, so the
    checkpoint merge and the bit-exactness comparison cannot drift."""
    def merge(node):
        if isinstance(node, dict):
            out = {}
            for k, v in node.items():
                if k == "rows" and isinstance(v, list):
                    st = jax.tree.map(lambda *xs: jnp.stack(xs, axis=1), *v)
                    out[k] = jax.tree.map(
                        lambda x: x.reshape((rows,) + x.shape[2:]), st)
                else:
                    out[k] = merge(v)
            return out
        if isinstance(node, list):
            return [merge(x) for x in node]
        return node

    return merge(state)


def make_pipeline_train_step(staged, optimizer, engine, mesh,
                             micro_batches: int,
                             data_axes: Sequence[str] = ("data",),
                             pipe_axis: str = "pipe"):
    """1F1B pipeline-parallel train step on a ``pipe × data`` mesh.

    ``staged`` is a :class:`repro.core.pipeline.StagedModel` (or any object
    with the same ``layout`` / ``split`` / ``embed_mb`` / ``stage_apply`` /
    ``loss_tail`` / ``aux_coef`` surface).  Params travel as
    ``{"shared": ..., "rows": ...}`` — shared replicated, rows with a
    leading (S,) stage axis sharded over ``pipe``.

    The body runs the 1F1B dataflow on an aligned slot grid of
    ``T = M + 2(S-1)`` ticks (``pipeline.aligned_ticks``): every tick each
    pipe rank executes one masked forward slot and one masked backward
    slot, then boundary payloads move one hop by ``send_recv`` (activations
    forward, grad-activations backward).  The ppermute is a rendezvous, so
    the slots are globally aligned — per-stage op order matches the
    canonical ``schedule_1f1b`` (warmup, steady 1F/1B, drain) with at most
    ``2(S-1-s)+1`` micro-batches in flight; backward slots rematerialize
    the stage forward from the buffered boundary input, exactly the remat
    policy the stack already uses per period (DESIGN.md §9).

    Gradients accumulate over micro-batches in ascending order (bit-equal
    to scan accumulation), shared-cell grads are combined across stages by
    one masked psum (adding exact zeros), and the DP edge syncs the
    per-row-unstacked pytree through ``engine`` over ``data_axes`` only —
    so per-bucket compression composes on the DP dimension of the 2-D
    mesh.  The optimizer then updates stage-locally (elementwise
    optimizers are bit-identical to the single-stage update restricted to
    the stage; layerwise norms see per-row leaves).
    """
    from repro.core.collectives import send_recv

    S = staged.layout.n_stages
    if mesh.shape[pipe_axis] != S:
        raise ValueError(f"mesh pipe axis {mesh.shape[pipe_axis]} != "
                         f"staged n_stages {S}")
    M = int(micro_batches)
    if M < 1:
        raise ValueError(f"micro_batches must be >= 1, got {M}")
    T = M + 2 * (S - 1)
    W = 2 * S - 1                        # live window of buffered F inputs
    axes = tuple(data_axes)
    rps = staged.layout.rows_per_stage
    world = _world_of(mesh, axes) * S    # sync/EF state is per (pipe, data)

    def body(params, opt_state, sync_state, batch, step, rng):
        from repro.models.sharding_ctx import manual_region
        with manual_region():
            return _body(params, opt_state, sync_state, batch, step, rng)

    def _body(params, opt_state, sync_state, batch, step, rng):
        from repro.models.moe import add_aux, no_aux
        shared = params["shared"]
        rows = jax.tree.map(lambda s: s[0], params["rows"])     # (R/S, ...)
        opt = jax.tree_util.tree_map_with_path(
            lambda p, s: s[0] if any(getattr(e, "key", None) == "rows"
                                     for e in p) else s, opt_state)
        sync_state_l = jax.tree.map(lambda s: s[0], sync_state)

        s_idx = jax.lax.axis_index(pipe_axis)
        is_first = s_idx == 0
        is_last = s_idx == S - 1
        tokens = batch["tokens"]                    # per-DP-shard slice
        b_dp, seq = tokens.shape
        assert b_dp % M == 0, (b_dp, M)
        toks_mb = tokens.reshape(M, b_dp // M, seq)

        def sel_mb(m):
            return jax.lax.dynamic_index_in_dim(
                toks_mb, jnp.clip(m, 0, M - 1), 0, keepdims=False)

        # the payload's aux (balance loss and MoE counters, summed over
        # the stages so far) rides with the activations
        def stage_fwd(rows_, payload):
            h, aux = staged.stage_apply(rows_, payload["h"])
            return {"h": h, "aux": add_aux(payload["aux"], aux)}

        def fwd_and_loss(rows_, shared_, payload, toks):
            out = stage_fwd(rows_, payload)
            l = (staged.loss_tail(shared_, out["h"], toks)
                 + staged.aux_coef * out["aux"]["balance"])
            return out, l

        f32 = jnp.float32
        zero_payload = {
            "h": jnp.zeros_like(staged.embed_mb(shared, sel_mb(
                jnp.zeros((), jnp.int32)))),
            "aux": no_aux()}
        buf = jax.tree.map(
            lambda x: jnp.zeros((W,) + x.shape, x.dtype), zero_payload)
        recv_f = zero_payload
        recv_b = zero_payload
        g_shared = jax.tree.map(lambda p: jnp.zeros(p.shape, f32), shared)
        g_rows = jax.tree.map(lambda p: jnp.zeros(p.shape, f32), rows)
        loss_sum = jnp.zeros((), f32)
        counts = {"moe_dropped": jnp.zeros((), f32),
                  "moe_routed": jnp.zeros((), f32)}

        def masked_add(acc, g, m):
            return jax.tree.map(
                lambda a, d: a + jnp.where(m, d.astype(f32), 0.0), acc, g)

        with jax.named_scope("forward"):
            for k in range(T):
                # ---- forward slot: F(k - s) ----
                m_f = k - s_idx
                x_first = {"h": staged.embed_mb(shared, sel_mb(m_f)),
                           "aux": no_aux()}
                x_in = jax.tree.map(lambda a, b: jnp.where(is_first, a, b),
                                    x_first, recv_f)
                # stage-interface barrier (paired with the per-row barriers in
                # stage_apply): the embed/recv select must not fuse into the
                # stage body, or the S=1 and S>1 backward graphs diverge in
                # the last ulp (DESIGN.md §9)
                x_in = jax.lax.optimization_barrier(x_in)
                out = stage_fwd(rows, x_in)
                buf = jax.tree.map(lambda b_, x: b_.at[k % W].set(x), buf,
                                   x_in)

                # ---- backward slot: B(k - 2(S-1) + s) on the F input buffered
                # at tick k - 2(S-1) + 2s (rematerialized forward) ----
                m_b = k - 2 * (S - 1) + s_idx
                valid_b = (m_b >= 0) & (m_b < M)
                k_f = k - 2 * (S - 1) + 2 * s_idx
                x_b = jax.tree.map(
                    lambda b_: jax.lax.dynamic_index_in_dim(
                        b_, jnp.mod(k_f, W), 0, keepdims=False), buf)
                toks_b = sel_mb(m_b)
                (out_b, l_b), vjp = jax.vjp(
                    lambda r_, s_, x_: fwd_and_loss(r_, s_, x_, toks_b),
                    rows, shared, x_b)
                # incoming grad-activation (zeros for the last stage, whose
                # backward is seeded by the loss cotangent instead)
                ct_out = jax.tree.map(
                    lambda t: jnp.where(valid_b & ~is_last, t,
                                        jnp.zeros((), t.dtype)), recv_b)
                ct_l = jnp.where(valid_b & is_last, jnp.ones((), l_b.dtype),
                                 jnp.zeros((), l_b.dtype))
                d_rows, d_shared, d_x = vjp((ct_out, ct_l))
                g_rows = masked_add(g_rows, d_rows, valid_b)
                g_shared = masked_add(g_shared, d_shared, valid_b)
                # chain the input cotangent into the embedding (stage 0
                # owns it)
                ct_emb = jax.tree.map(
                    lambda t: jnp.where(valid_b & is_first, t,
                                        jnp.zeros((), t.dtype)), d_x["h"])
                _, vjp_e = jax.vjp(
                    lambda s_: staged.embed_mb(s_, toks_b), shared)
                (d_emb,) = vjp_e(ct_emb)
                g_shared = masked_add(g_shared, d_emb, valid_b & is_first)
                loss_sum = loss_sum + jnp.where(valid_b & is_last, l_b, 0.0)
                # the last stage's remat forward holds the whole stack's
                # counts
                aux_b = out_b["aux"]
                counts = masked_add(counts,
                                    {"moe_dropped": aux_b["dropped"],
                                     "moe_routed": aux_b["routed"]},
                                    valid_b & is_last)

                # ---- boundary exchange: one hop each way ----
                if S > 1:
                    recv_f = send_recv(out, pipe_axis, +1)
                    recv_b = send_recv(d_x, pipe_axis, -1)

        # shared cells: stage 0 holds the embed grads, stage S-1 the
        # loss-tail grads, everyone else exact zeros — one psum combines
        g_shared = jax.tree.map(lambda g: jax.lax.psum(g, pipe_axis),
                                g_shared)
        inv_m = 1.0 / M
        g_shared = jax.tree.map(lambda g: g * inv_m, g_shared)
        g_rows = jax.tree.map(lambda g: g * inv_m, g_rows)

        # DP edge: per-row granularity, data axes only (stage-count
        # invariant compression — DESIGN.md §9)
        gtree = {"shared": g_shared, "rows": unstack_rows(g_rows, rps)}
        synced, sync_state_l = engine(gtree, sync_state_l, rng)
        # barrier: stop XLA fusing optimizer math into the gradient /
        # collective chain, which would let per-graph fusion choices leak
        # into the update arithmetic (same idiom as transformer._boundary)
        synced = jax.lax.optimization_barrier(synced)

        # the optimizer ALSO runs on the per-row-unstacked tree: every
        # row's update subgraph then has the same shapes at every stage
        # count, which (with the explicit-wire sync) makes params and
        # moments bit-exact across stage counts — updating the fused
        # (R/S, ...) stack instead lets XLA compile the elementwise chain
        # differently per shape (DESIGN.md §9)
        p_un = {"shared": shared, "rows": unstack_rows(rows, rps)}
        p_un, opt = _optimize(optimizer, synced, opt, p_un, step)

        loss = jax.lax.psum(loss_sum, pipe_axis) * inv_m
        out = _step_out(loss, jax.lax.psum(counts, pipe_axis), axes)

        lead_rows = jax.tree_util.tree_map_with_path(
            lambda p, s: s[None] if any(getattr(e, "key", None) == "rows"
                                        for e in p) else s, opt)
        return ({"shared": p_un["shared"],
                 "rows": jax.tree.map(lambda s: s[None],
                                      restack_rows(p_un["rows"]))},
                lead_rows,
                jax.tree.map(lambda s: s[None], sync_state_l), out)

    batch_spec = {"tokens": P(axes, None)}
    state_spec = P((pipe_axis,) + axes)
    params_spec = {"shared": P(), "rows": P(pipe_axis)}

    def step_fn(params, opt_state, sync_state, batch, step, rng):
        opt_spec = pipe_spec_tree(opt_state, pipe_axis)
        f = jax.shard_map(
            body, mesh=mesh,
            in_specs=(params_spec, opt_spec, state_spec, batch_spec, P(),
                      P()),
            out_specs=(params_spec, opt_spec, state_spec, P()),
            axis_names=manual_axes(mesh, (pipe_axis, *axes)),
            check_vma=False)
        return f(params, opt_state, sync_state, batch, step, rng)

    def init_opt_state(split_params):
        """Optimizer state over the per-row-unstacked stage tree, rows
        leaves carrying the leading (S,) stage axis (sharded over pipe):
        ``{"shared": ..., "rows": [row_0, ..., row_{R/S-1}]}`` where row i
        holds stage-s's i-th layer row at index s."""
        rows = split_params["rows"]          # (S, R/S, ...)
        template = {
            "shared": split_params["shared"],
            "rows": [jax.tree.map(lambda x, i=i: x[:, i], rows)
                     for i in range(rps)]}
        return optimizer.init(template)

    def init_sync_state(split_params):
        """Per-(pipe, data)-rank reducer state over the UNSTACKED gradient
        pytree (shared + one entry per layer row)."""
        rows_local = jax.tree.map(lambda s: s[0], split_params["rows"])
        template = {"shared": split_params["shared"],
                    "rows": unstack_rows(rows_local, rps)}
        return broadcast_worker_state(engine.init_state(template), world)

    return step_fn, init_opt_state, init_sync_state


# ---------------------------------------------------------------------------
# Strategy phase programs (SyncStrategy sessions — DESIGN.md §7)
# ---------------------------------------------------------------------------

def make_local_train_step(model: Model, optimizer, mesh,
                          data_axes: Sequence[str] = ("data",)):
    """Purely-local step: per-shard loss/backward/update with NO gradient
    collective (the skip program of local SGD / push-pull).  Params and
    optimizer state carry a leading per-worker axis sharded over the data
    axes, so workers genuinely diverge between rounds — the legacy
    ``--local-sgd`` path ran the BSP step, whose XLA-inserted gradient
    allreduce made the later averaging a no-op on real meshes.  Only the
    scalar loss is pmean-ed (reporting)."""
    batch_spec = {"tokens": P(tuple(data_axes), None)}
    state_spec = P(tuple(data_axes))

    def body(params, opt_state, batch, step):
        from repro.models.sharding_ctx import manual_region
        params = jax.tree.map(lambda s: s[0], params)
        opt_state = jax.tree.map(lambda s: s[0], opt_state)
        with manual_region():
            loss, counts, grads = _forward(model, params, batch)
        params, opt_state = _optimize(optimizer, grads, opt_state, params,
                                      step)
        params = jax.tree.map(lambda s: s[None], params)
        opt_state = jax.tree.map(lambda s: s[None], opt_state)
        return params, opt_state, _step_out(loss, counts, data_axes)

    def step_fn(params, opt_state, batch, step):
        f = jax.shard_map(
            body, mesh=mesh,
            in_specs=(state_spec, state_spec, batch_spec, P()),
            out_specs=(state_spec, state_spec, P()),
            axis_names=manual_axes(mesh, data_axes),
            check_vma=False)
        return f(params, opt_state, batch, step)

    return step_fn


def make_param_round_step(reducer, mesh, data_axes: Sequence[str] = ("data",),
                          algo: str = "psum"):
    """One parameter-reduce round (local SGD averaging / push-pull fetch).

    ``reducer=None``: plain dense ``average_params`` on ``algo``.  Otherwise
    the round moves the params-minus-anchor DELTA through the reducer (a
    ``PlanExecutor`` — per-bucket compression + error feedback) and rebuilds
    ``params = anchor + reduced_delta``; the anchor (the parameters agreed
    at the last round, identical on every worker) is what keeps compressed
    periodic averaging sound — compressing raw parameter values would, e.g.
    under top-k, zero most of the model.

    Returns ``round_fn(params_w, anchor, red_state, rng) -> (params_w,
    anchor, red_state)`` where ``params_w``/``red_state`` carry the leading
    per-worker axis and ``anchor`` is replicated (None when reducer is None).
    """
    from repro.core import average_params
    state_spec = P(tuple(data_axes))

    if reducer is None:
        def avg_body(params):
            p = jax.tree.map(lambda s: s[0], params)
            p = average_params(p, tuple(data_axes), algo)
            return jax.tree.map(lambda s: s[None], p)

        def round_fn(params, anchor, red_state, rng):
            f = jax.shard_map(avg_body, mesh=mesh, in_specs=(state_spec,),
                              out_specs=state_spec,
                              axis_names=manual_axes(mesh, data_axes),
                              check_vma=False)
            return f(params), anchor, red_state

        return round_fn

    def body(params, anchor, red_state, rng):
        p = jax.tree.map(lambda s: s[0], params)
        rs = jax.tree.map(lambda s: s[0], red_state)
        delta = jax.tree.map(
            lambda x, a: x.astype(jnp.float32) - a.astype(jnp.float32),
            p, anchor)
        reduced, rs = reducer(delta, rs, rng)   # mean over world (plan.mean)
        # params keep their ORIGINAL dtype (bf16 stays bf16); the f32 anchor
        # is rebuilt FROM the cast result so it equals what workers actually
        # hold entering the next local phase — otherwise the cast error
        # would sit in every future delta as a constant offset
        new_p = jax.tree.map(lambda a, d, x: (a + d).astype(x.dtype),
                             anchor, reduced, p)
        new_anchor = jax.tree.map(lambda x: x.astype(jnp.float32), new_p)
        return (jax.tree.map(lambda s: s[None], new_p), new_anchor,
                jax.tree.map(lambda s: s[None], rs))

    def round_fn(params, anchor, red_state, rng):
        f = jax.shard_map(
            body, mesh=mesh,
            in_specs=(state_spec, P(), state_spec, P()),
            out_specs=(state_spec, P(), state_spec),
            axis_names=manual_axes(mesh, data_axes),
            check_vma=False)
        return f(params, anchor, red_state, rng)

    return round_fn


def make_lag_programs(model: Model, optimizer, synchronizer, mesh,
                      data_axes: Sequence[str] = ("data",)):
    """The three LAG programs (host dispatch, DESIGN.md §5/§7):

      * ``probe(params, batch, g_last) -> (out, grads_w, delta, scale)`` —
        per-shard backward plus the two globally psum-ed scalars of LAG's
        trigger; the 8-byte scalars are the ONLY wire traffic of a skipped
        round.  ``out`` is the step's loss output (the loss with the MoE
        counters); ``grads_w`` returns per-worker (leading axis, sharded).
      * ``sync_apply(params, opt_state, sync_state, grads_w, step, rng)``
        — reduce this step's gradients through the strategy's reducer and
        update; also returns the synchronized gradient (the new ``g_last``).
      * ``reuse_apply(params, opt_state, g_last, step)`` — apply the last
        synchronized gradient with no collective at all.
    """
    batch_spec = {"tokens": P(tuple(data_axes), None)}
    state_spec = P(tuple(data_axes))
    axes = tuple(data_axes)

    def probe_body(params, batch, g_last):
        from repro.models.sharding_ctx import manual_region
        with manual_region():
            loss, counts, grads = _forward(model, params, batch)

        def sq(t):
            return sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                       for l in jax.tree.leaves(t))

        delta = jax.lax.psum(
            sq(jax.tree.map(lambda a, b: a.astype(jnp.float32) - b,
                            grads, g_last)), axes)
        scale = jax.lax.psum(sq(grads), axes)
        return (_step_out(loss, counts, axes),
                jax.tree.map(lambda g: g[None], grads), delta, scale)

    def probe(params, batch, g_last):
        f = jax.shard_map(
            probe_body, mesh=mesh,
            in_specs=(P(), batch_spec, P()),
            out_specs=(P(), state_spec, P(), P()),
            axis_names=manual_axes(mesh, data_axes),
            check_vma=False)
        return f(params, batch, g_last)

    def sync_body(params, opt_state, sync_state, grads_w, step, rng):
        g = jax.tree.map(lambda s: s[0], grads_w)
        ss = jax.tree.map(lambda s: s[0], sync_state)
        synced, ss = synchronizer(g, ss, rng)
        params, opt_state = _optimize(optimizer, synced, opt_state, params,
                                      step)
        return (params, opt_state, jax.tree.map(lambda s: s[None], ss),
                synced)

    def sync_apply(params, opt_state, sync_state, grads_w, step, rng):
        f = jax.shard_map(
            sync_body, mesh=mesh,
            in_specs=(P(), P(), state_spec, state_spec, P(), P()),
            out_specs=(P(), P(), state_spec, P()),
            axis_names=manual_axes(mesh, data_axes),
            check_vma=False)
        return f(params, opt_state, sync_state, grads_w, step, rng)

    def reuse_apply(params, opt_state, g_last, step):
        return _optimize(optimizer, g_last, opt_state, params, step)

    return probe, sync_apply, reuse_apply


# ---------------------------------------------------------------------------
# Sharding assembly for pjit dry-runs / training
# ---------------------------------------------------------------------------

def named(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))
