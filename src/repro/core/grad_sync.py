"""Gradient synchronization — the survey's taxonomy as one composable step.

Every data-parallel training step runs

    grads -> [bucket] -> [error-feedback + compress] -> collective
          -> [decompress/aggregate] -> synced grads

The execution engine is ``PlanExecutor``: it takes a ``CommPlan`` — an
ordered list of per-bucket ``BucketPlan(leaves, compressor, algo, ...)``
entries (``repro.core.schedule.planner``) — and runs a possibly
HETEROGENEOUS strategy per bucket: one bucket may go dense over psum while
another is top-k compressed over an explicit ring.  Plans come either from
the communication planner (``--sync auto``) or from a single global
``SyncConfig`` via ``plan_from_config`` (the degenerate one-entry-strategy
plan — ``GradientSynchronizer`` below keeps that legacy API).

``SyncConfig`` knobs (all become per-bucket fields of ``BucketPlan``):

  * ``compressor``: none | sign | terngrad | qsgd | int8 | topk | randomk |
    threshold | powersgd | svd                      (§3.2)
  * ``algo``: psum | ring | tree | hierarchical | mesh2d | mesh2d_split (§4.1)
  * ``error_feedback``: EF / residual accumulation  (§3.2.1 Eq. 2)
  * ``bucket_bytes``: MG-WFBP tensor fusion         (§3.3 / §4.2)

Wire semantics (DESIGN.md §5): gather-based compressors (sign, top-k, ...)
all-gather their compact payloads over the data axes and every rank
decompresses + averages — the pattern of 1-bit SGD/DGC, with collective
bytes proportional to the COMPRESSED size.  Aggregatable factorizations
(PowerSGD) allreduce their small factors directly on the selected
collective algorithm.  Must run inside a ``shard_map`` whose manual axes
are exactly ``axes``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.collectives import allreduce, local_chunk, reduce_scatter
from repro.core.compression import get_compressor
from repro.core.schedule.planner import (BucketPlan, CommPlan,
                                         form_bucket_indices)

DENSE_SMALL = 4096  # leaves smaller than this stay dense inside PowerSGD


@dataclasses.dataclass(frozen=True)
class SyncConfig:
    compressor: str = "none"
    compressor_args: Tuple[Tuple[str, Any], ...] = ()
    algo: str = "psum"
    error_feedback: bool = True
    ef_decay: float = 1.0
    bucket_bytes: int = 32 * 1024 * 1024   # MG-WFBP fusion granularity
    mean: bool = True                      # divide by world size after reduce

    def make_compressor(self):
        return get_compressor(self.compressor, **dict(self.compressor_args))


# ---------------------------------------------------------------------------
# Bucketing (tensor fusion, MG-WFBP / Horovod-style)
# ---------------------------------------------------------------------------

def bucketize(grads, bucket_bytes: int):
    """Split the flattened gradient pytree into ~bucket_bytes buckets.

    ``bucket_bytes == 0`` means per-leaf buckets WITHOUT concatenation-
    induced reshape: each leaf stays its own flat bucket, so a leaf's
    tensor-parallel sharding survives (flattening a TP-sharded matrix into
    a cross-leaf concat replicates it — the EF-residual memory finding in
    EXPERIMENTS.md §Perf pair 3).

    Returns (bucket_defs, pack, unpack) where bucket_defs is a list of lists
    of (leaf_index, size); buckets follow backward-pass order (last layer
    first) like WFBP — leaves are reversed so the first bucket to "arrive"
    holds the deepest layers.
    """
    leaves, treedef = jax.tree.flatten(grads)
    sizes = [int(np.prod(g.shape)) for g in leaves]
    buckets = [[(i, sizes[i]) for i in idxs]
               for idxs in form_bucket_indices([s * 4 for s in sizes],
                                               bucket_bytes)]

    def pack(gs):
        ls = jax.tree.leaves(gs)
        return [jnp.concatenate([ls[i].reshape(-1).astype(jnp.float32)
                                 for i, _ in b]) for b in buckets]

    def unpack(bufs):
        ls = jax.tree.leaves(grads)
        out = [None] * len(ls)
        for buf, b in zip(bufs, buckets):
            off = 0
            for i, sz in b:
                out[i] = buf[off:off + sz].reshape(ls[i].shape).astype(ls[i].dtype)
                off += sz
        return jax.tree.unflatten(treedef, out)

    return buckets, pack, unpack


# ---------------------------------------------------------------------------
# SyncConfig -> degenerate CommPlan (the legacy single-strategy path)
# ---------------------------------------------------------------------------

def plan_from_config(cfg: SyncConfig, grads) -> CommPlan:
    """The one-strategy ``CommPlan`` a global ``SyncConfig`` induces.

    Mirrors the historical GradientSynchronizer modes exactly (so executing
    the plan is bit-for-bit the old behaviour):

      * ``compressor='none'``     — one dense bucket, leaves synced in their
                                    natural shapes (sharding survives)
      * ``powersgd``              — per-leaf unpacked buckets in tree order
                                    (factorization is shape-aware)
      * ``bucket_bytes <= 0``     — per-leaf unpacked buckets in tree order
      * otherwise                 — ``bucketize`` fusion in backward order
    """
    leaves = jax.tree.leaves(grads)
    sizes = [int(np.prod(g.shape)) for g in leaves]
    if cfg.compressor == "none":
        # per-leaf unfused dense sync, leaves in their natural shapes —
        # the historical behaviour (sharding survives, output stays f32)
        buckets: Tuple[BucketPlan, ...] = (BucketPlan(
            leaves=tuple(range(len(leaves))), compressor="none",
            algo=cfg.algo, bucket_bytes=4 * sum(sizes), pack=False,
            error_feedback=False),)
    elif cfg.compressor == "powersgd":
        buckets = tuple(BucketPlan(
            leaves=(i,), compressor="powersgd",
            compressor_args=cfg.compressor_args, algo=cfg.algo,
            bucket_bytes=4 * sizes[i], pack=False, error_feedback=True,
            ef_decay=cfg.ef_decay) for i in range(len(leaves)))
    elif cfg.bucket_bytes <= 0:
        buckets = tuple(BucketPlan(
            leaves=(i,), compressor=cfg.compressor,
            compressor_args=cfg.compressor_args, algo=cfg.algo,
            bucket_bytes=4 * sizes[i], pack=False,
            error_feedback=cfg.error_feedback, ef_decay=cfg.ef_decay)
            for i in range(len(leaves)))
    else:
        defs, _, _ = bucketize(grads, cfg.bucket_bytes)
        buckets = tuple(BucketPlan(
            leaves=tuple(i for i, _ in b), compressor=cfg.compressor,
            compressor_args=cfg.compressor_args, algo=cfg.algo,
            bucket_bytes=4 * sum(sz for _, sz in b), pack=True,
            error_feedback=cfg.error_feedback, ef_decay=cfg.ef_decay)
            for b in defs)
    return CommPlan(buckets=buckets, mean=cfg.mean)


def sharded_plan_from_config(cfg: SyncConfig, grads) -> CommPlan:
    """The plan ``--shard-state`` induces from a global ``SyncConfig``:
    like :func:`plan_from_config` but dense buckets are PACKED at the
    config's fusion granularity, because the reduce-scatter edge operates
    on fused flat buffers (a bucket is the scatter unit).

    Bit-compat note (DESIGN.md §8): ring-allreduce sums each chunk in a
    ring order determined by the chunk's position, so replicated-vs-sharded
    exactness holds per BUCKET BOUNDARY — executing this same plan on the
    replicated path (PlanExecutor's fused dense exchange) is the reference
    the conformance suite compares against; the legacy per-leaf unpacked
    dense plan differs in the last ulp."""
    if cfg.compressor != "none":
        return dataclasses.replace(plan_from_config(cfg, grads),
                                   shard_state=True)
    bb = cfg.bucket_bytes if cfg.bucket_bytes > 0 else 32 * 2**20
    defs, _, _ = bucketize(grads, bb)
    buckets = tuple(BucketPlan(
        leaves=tuple(i for i, _ in b), compressor="none", algo=cfg.algo,
        bucket_bytes=4 * sum(sz for _, sz in b), pack=True,
        error_feedback=False) for b in defs)
    return CommPlan(buckets=buckets, mean=cfg.mean, shard_state=True)


# ---------------------------------------------------------------------------
# The executor
# ---------------------------------------------------------------------------

def _bucket_scopes(buckets):
    """``enumerate(buckets)``, the loop body of bucket ``j`` traced under
    the named scope ``grad_sync/bucket_<j>`` (it names the bucket's ops in
    the compiled program and the device trace)."""
    for j, bucket in enumerate(buckets):
        with jax.named_scope("grad_sync"), jax.named_scope(f"bucket_{j}"):
            yield j, bucket


class PlanExecutor:
    """Executes a ``CommPlan``: per-bucket (possibly heterogeneous)
    error-feedback + compression + collective exchange.

    State is carried per bucket: ``error`` holds the EF residual (flat
    buffer for packed buckets, leaf-shaped otherwise), ``q`` the PowerSGD
    warm-start factor; entries are None for buckets that need neither, and
    the keys are omitted entirely when no bucket uses them (preserving the
    legacy state schema of the single-config path)."""

    def __init__(self, plan: CommPlan, axes: Sequence[str]):
        self.plan = plan
        self.axes = tuple(axes)
        self.comps = [get_compressor(b.compressor, **dict(b.compressor_args))
                      for b in plan.buckets]
        for j, b in enumerate(plan.buckets):
            if (b.compressor == "powersgd" or
                    (not b.pack and b.compressor != "none")) \
                    and len(b.leaves) != 1:
                raise ValueError(
                    f"bucket {j}: pack=False / powersgd buckets operate on "
                    f"one leaf in its natural shape, got leaves={b.leaves}")

    @staticmethod
    def _bucket_uses_ef(b: BucketPlan) -> bool:
        return b.error_feedback and b.compressor not in ("none",)

    def _check_cover(self, n_leaves: int) -> None:
        """Every leaf must be claimed by exactly one bucket — a partial or
        overlapping plan would otherwise surface as a far-away unflatten /
        optimizer error on a None gradient."""
        claimed = sorted(i for b in self.plan.buckets for i in b.leaves)
        if claimed != list(range(n_leaves)):
            raise ValueError(
                f"CommPlan does not cover the gradient pytree exactly: "
                f"{n_leaves} leaves, bucket indices {claimed}")

    @staticmethod
    def _pack_bucket(leaves, idxs):
        return jnp.concatenate([leaves[i].reshape(-1).astype(jnp.float32)
                                for i in idxs])

    @staticmethod
    def _unpack_bucket(buf, leaves, idxs, out):
        off = 0
        for i in idxs:
            sz = int(np.prod(leaves[i].shape))
            out[i] = buf[off:off + sz].reshape(
                leaves[i].shape).astype(leaves[i].dtype)
            off += sz

    # -- state ---------------------------------------------------------------

    def _init_q(self, g, compressor_args) -> jnp.ndarray:
        if g.ndim < 2 or g.size < DENSE_SMALL:
            return jnp.zeros((0,), jnp.float32)
        rank = dict(compressor_args).get("rank", 4)
        n, d = g.shape[0], int(np.prod(g.shape[1:]))
        r = min(rank, n, d)
        return jax.random.normal(jax.random.PRNGKey(g.ndim * 7919 + d),
                                 (d, r), jnp.float32)

    def init_state(self, grads) -> Dict[str, Any]:
        leaves = jax.tree.leaves(grads)
        self._check_cover(len(leaves))
        state: Dict[str, Any] = {"step": jnp.zeros((), jnp.int32)}
        errors: List[Optional[jnp.ndarray]] = []
        qs: List[Optional[jnp.ndarray]] = []
        for b in self.plan.buckets:
            if b.compressor == "powersgd":
                g = leaves[b.leaves[0]]
                errors.append(jnp.zeros(g.shape, jnp.float32))
                qs.append(self._init_q(g, b.compressor_args))
                continue
            qs.append(None)
            if not self._bucket_uses_ef(b):
                errors.append(None)
            elif b.pack:
                sz = sum(int(np.prod(leaves[i].shape)) for i in b.leaves)
                errors.append(jnp.zeros((sz,), jnp.float32))
            else:
                g = leaves[b.leaves[0]]
                errors.append(jnp.zeros(g.shape, jnp.float32))
        if any(e is not None for e in errors):
            state["error"] = errors
        if any(q is not None for q in qs):
            state["q"] = qs
        return state

    # -- wire statistics (static) ---------------------------------------------

    def payload_bits(self, grads) -> int:
        """Bits leaving one rank per step (the survey's comparison metric)."""
        leaves = jax.tree.leaves(grads)
        total = 0
        for b, comp in zip(self.plan.buckets, self.comps):
            if b.pack and len(b.leaves) > 1:
                sz = sum(int(np.prod(leaves[i].shape)) for i in b.leaves)
                total += comp.payload_bits((sz,))
            else:
                total += sum(comp.payload_bits(leaves[i].shape)
                             for i in b.leaves)
        return total

    # -- sync ------------------------------------------------------------------

    def _world(self) -> float:
        world = 1
        for ax in self.axes:
            world *= jax.lax.axis_size(ax)
        return world

    def __call__(self, grads, state, rng):
        """Returns (synced_grads, new_state). Must run with ``self.axes``
        manual (inside shard_map) — or on a single device where the axes
        have size 1 (degenerate, for unit tests)."""
        plan = self.plan
        leaves, treedef = jax.tree.flatten(grads)
        self._check_cover(len(leaves))
        denom = float(self._world()) if plan.mean else 1.0
        nb = len(plan.buckets)
        rngs = jax.random.split(rng, nb) if nb else []
        errors = state.get("error", [None] * nb)
        qs = state.get("q", [None] * nb)

        out: List[Optional[jnp.ndarray]] = [None] * len(leaves)
        new_errors: List[Optional[jnp.ndarray]] = []
        new_qs: List[Optional[jnp.ndarray]] = []
        for j, (b, comp) in _bucket_scopes(zip(plan.buckets, self.comps)):
            if b.compressor == "none":
                if b.pack and len(b.leaves) > 1:
                    # fused dense exchange: ONE collective for the bucket —
                    # what the planner's cost model prices (one α per
                    # bucket, MG-WFBP)
                    buf = self._pack_bucket(leaves, b.leaves)
                    synced = allreduce(buf, b.algo, self.axes) / denom
                    self._unpack_bucket(synced, leaves, b.leaves, out)
                else:
                    # unfused: leaves keep their natural shape (and their
                    # tensor-parallel sharding)
                    for i in b.leaves:
                        out[i] = allreduce(leaves[i].astype(jnp.float32),
                                           b.algo, self.axes) / denom
                new_errors.append(errors[j])
                new_qs.append(qs[j])
            elif b.compressor == "powersgd":
                e, q, synced = self._sync_powersgd_leaf(
                    leaves[b.leaves[0]], errors[j], qs[j], b, comp, denom)
                out[b.leaves[0]] = synced
                new_errors.append(e)
                new_qs.append(q)
            elif not b.pack:
                e, synced = self._sync_buffer(
                    leaves[b.leaves[0]].astype(jnp.float32), errors[j],
                    rngs[j], b, comp, denom)
                out[b.leaves[0]] = synced      # f32, leaf-shaped
                new_errors.append(e)
                new_qs.append(None)
            else:
                buf = self._pack_bucket(leaves, b.leaves)
                e, synced = self._sync_buffer(buf, errors[j], rngs[j], b,
                                              comp, denom)
                self._unpack_bucket(synced, leaves, b.leaves, out)
                new_errors.append(e)
                new_qs.append(None)

        new_state: Dict[str, Any] = {"step": state["step"] + 1}
        if "error" in state:
            new_state["error"] = new_errors
        if "q" in state:
            new_state["q"] = new_qs
        return jax.tree.unflatten(treedef, out), new_state

    # -- sharded-DP sync (reduce-scatter edge, DESIGN.md §8) ------------------

    def sync_shards(self, grads, state, rng):
        """Sharded-DP gradient exchange: per bucket, this rank's CANONICAL
        shard of exactly the synced gradient ``__call__`` would return.

          * dense buckets: true ``reduce_scatter`` (ring / nested-ring; the
            psum algo is psum + local slice, XLA owning the wire) — chunk
            values are bit-identical to the matching allreduce slices;
          * aggregatable compressed (PowerSGD factors, qsgd): the payload
            exchange is unchanged, and the reconstructed approximation is
            sliced locally (zero extra wire);
          * gather-pattern compressed (sign/top-k/int8): the SAME compressed
            payload all-gather as replicated mode — every rank decompresses
            and keeps its owned slice of the sum — so EF residual dynamics
            are bit-identical to replicated mode (the residual corrects
            what this worker SENT, which sharding does not change).

        Returns ``(bucket_shards, new_state)`` where ``bucket_shards[j]`` is
        the (m_j,) f32 mean-gradient shard of plan bucket j; ``new_state``
        has the same schema as ``__call__``'s."""
        plan = self.plan
        leaves, _ = jax.tree.flatten(grads)
        self._check_cover(len(leaves))
        denom = float(self._world()) if plan.mean else 1.0
        nb = len(plan.buckets)
        rngs = jax.random.split(rng, nb) if nb else []
        errors = state.get("error", [None] * nb)
        qs = state.get("q", [None] * nb)

        shards: List[jnp.ndarray] = []
        new_errors: List[Optional[jnp.ndarray]] = []
        new_qs: List[Optional[jnp.ndarray]] = []
        for j, (b, comp) in _bucket_scopes(zip(plan.buckets, self.comps)):
            if b.compressor == "none":
                buf = self._pack_bucket(leaves, b.leaves)
                shards.append(reduce_scatter(buf, b.algo, self.axes) / denom)
                new_errors.append(errors[j])
                new_qs.append(qs[j])
            elif b.compressor == "powersgd":
                e, q, synced = self._sync_powersgd_leaf(
                    leaves[b.leaves[0]], errors[j], qs[j], b, comp, denom)
                # factors were already allreduced; the full approximation is
                # in hand on every rank — slice, no extra collective
                shards.append(local_chunk(
                    synced.reshape(-1).astype(jnp.float32), self.axes))
                new_errors.append(e)
                new_qs.append(q)
            else:
                buf = (self._pack_bucket(leaves, b.leaves) if b.pack
                       else leaves[b.leaves[0]].astype(jnp.float32))
                if comp.aggregatable:
                    # like _sync_buffer (fused hook included), but the
                    # dense decompressed sum goes out as a reduce-scatter
                    # instead of an allreduce
                    payload, meta, new_e, g_hat = self._compress_with_ef(
                        buf, errors[j], rngs[j], b, comp)
                    if g_hat is None:
                        g_hat = comp.decompress(payload, meta)
                    new_errors.append(new_e)
                    shards.append(
                        reduce_scatter(g_hat.reshape(-1), b.algo, self.axes)
                        / denom)
                else:
                    # gather-pattern wire: the replicated exchange verbatim
                    # (so EF residual dynamics are bit-identical), then the
                    # owner's slice of the decompressed sum
                    e, synced = self._sync_buffer(buf, errors[j], rngs[j],
                                                  b, comp, denom)
                    new_errors.append(e)
                    shards.append(local_chunk(synced.reshape(-1),
                                              self.axes))
                new_qs.append(None)

        new_state: Dict[str, Any] = {"step": state["step"] + 1}
        if "error" in state:
            new_state["error"] = new_errors
        if "q" in state:
            new_state["q"] = new_qs
        return shards, new_state

    # EF + compress of one flat/leaf-shaped f32 buffer.  Dispatches to the
    # compressor's fused one-pass hook (Pallas kernels, DESIGN.md §11)
    # when the plan allows it; otherwise runs the decomposed reference op
    # chain.  Both are bit-identical in payload and residual under jit —
    # the fused-wire conformance suites pin this.  Returns
    # (payload, meta, new_e, g_hat) with g_hat=None on the fused path
    # (the local reconstruction was folded into the kernel's residual).
    def _compress_with_ef(self, buf, e, rng, b: BucketPlan, comp):
        use_ef = self._bucket_uses_ef(b)
        if b.fused and use_ef and comp.fused_ef_compress is not None:
            payload, meta, new_e = comp.fused_ef_compress(buf, e, b.ef_decay)
            return payload, meta, new_e, None
        corrected = buf + b.ef_decay * e if use_ef else buf
        payload, meta = comp.compress(corrected, rng)
        g_hat = comp.decompress(payload, meta)
        new_e = corrected - g_hat if use_ef else e
        return payload, meta, new_e, g_hat

    # EF + compress + exchange of one flat/leaf-shaped f32 buffer.
    def _sync_buffer(self, buf, e, rng, b: BucketPlan, comp, denom):
        payload, meta, new_e, g_hat = self._compress_with_ef(
            buf, e, rng, b, comp)
        if comp.aggregatable or b.algo == "ring_fused":
            # ring_fused needs a dense f32 operand (it re-compresses per
            # hop), so gather-pattern wires also reconstruct locally and
            # ride the compressed ring instead of the payload all-gather.
            if g_hat is None:
                g_hat = comp.decompress(payload, meta)
            synced = allreduce(g_hat.astype(jnp.float32), b.algo,
                               self.axes) / denom
        else:
            synced = self._gather_mean(comp, payload, meta, buf.shape,
                                       denom, fused=b.fused)
        return new_e, synced

    # PowerSGD: allreduce the (P, Q) factors directly (aggregatable).
    def _sync_powersgd_leaf(self, g, e, q, b: BucketPlan, comp, denom):
        gf = g.astype(jnp.float32)
        if q.size == 0:  # small leaf: dense allreduce
            synced = allreduce(gf, b.algo, self.axes) / denom
            return e, q, synced.astype(g.dtype)
        corrected = gf + b.ef_decay * e
        (p_f, q_f), (shape, _) = comp.compress(corrected, q_prev=q)
        p_f = allreduce(p_f, b.algo, self.axes) / denom
        q_f = allreduce(q_f, b.algo, self.axes) / denom
        approx = comp.decompress((p_f, q_f), (shape, None))
        return corrected - approx, q_f, approx.astype(g.dtype)

    def _gather_mean(self, comp, payload, meta, shape, denom,
                     fused: bool = True):
        """All-gather the compact payloads over the data axes; every rank
        decompresses and averages (1-bit SGD / DGC wire pattern).  Payload
        pytrees are gathered leaf-wise so the wire carries int8/indices,
        not dense f32.  Static metadata (e.g. shapes) passes through.

        When the compressor provides ``fused_decode_sum`` (and the bucket
        runs fused), the per-rank decompress loop collapses into ONE
        fused dequantize+accumulate kernel pass over the gathered
        payloads — each payload read once, the dense sum written once."""
        def is_arr(x):
            return isinstance(x, (jax.Array, jax.core.Tracer))

        def gather(x):
            if not is_arr(x):
                return x
            orig = x.shape
            for ax in self.axes:
                x = jax.lax.all_gather(x, ax)
            return x.reshape((-1,) + orig)

        def index(x, i):
            return x[i] if is_arr(x) else x

        gathered_payload = jax.tree.map(gather, payload)
        gathered_meta = jax.tree.map(gather, meta) if meta is not None else None
        world = self._world()

        if fused and comp.fused_decode_sum is not None:
            return comp.fused_decode_sum(gathered_payload,
                                         gathered_meta) / denom

        def one(i):
            pl = jax.tree.map(lambda x: index(x, i), gathered_payload)
            mt = (jax.tree.map(lambda x: index(x, i), gathered_meta)
                  if gathered_meta is not None else None)
            return comp.decompress(pl, mt)

        total = jax.lax.fori_loop(
            0, world, lambda i, acc: acc + one(i),
            jnp.zeros(shape, jnp.float32))
        return total / denom


# ---------------------------------------------------------------------------
# Legacy single-config front-end (degenerate one-strategy plan)
# ---------------------------------------------------------------------------

class GradientSynchronizer:
    """Single global ``SyncConfig`` applied to every bucket — now a thin
    wrapper that lowers the config to a degenerate ``CommPlan`` (one strategy
    everywhere) and lets ``PlanExecutor`` run it.  Kept because a fixed
    config is the right tool when you already know the answer (benchmarks,
    ablations) and as the API every existing caller/test uses."""

    def __init__(self, cfg: SyncConfig, axes: Sequence[str]):
        self.cfg = cfg
        self.axes = tuple(axes)
        # eager validation (unknown compressor/args fail at construction,
        # not at the first traced call) + the legacy public attribute
        self.comp = cfg.make_compressor()
        self._executor: Optional[PlanExecutor] = None
        self._plan_key = None

    def _exec_for(self, grads) -> PlanExecutor:
        # plans depend on tree structure AND leaf shapes (bucketize)
        key = (jax.tree.structure(grads),
               tuple(g.shape for g in jax.tree.leaves(grads)))
        if self._executor is None or key != self._plan_key:
            self._executor = PlanExecutor(plan_from_config(self.cfg, grads),
                                          self.axes)
            self._plan_key = key
        return self._executor

    def init_state(self, grads) -> Dict[str, Any]:
        return self._exec_for(grads).init_state(grads)

    def payload_bits(self, grads) -> int:
        """Bits leaving one rank per step (the survey's comparison metric)."""
        return self._exec_for(grads).payload_bits(grads)

    def __call__(self, grads, state, rng):
        return self._exec_for(grads)(grads, state, rng)
