"""Overlap of the gradient exchange with the backward (wait-free
backpropagation): the counter that reads it from a scheduled program,
the synced step's choice of compile options, and the synced step's
results on the CPU against an oracle that exchanges after the backward.

``hlo_analysis.exchange_overlap`` reads the share of the bytes of the
collectives under ``grad_sync`` whose start and done have compute
scheduled between them.  ``steps.synced_step_compiler_options`` gives the
TPU's overlap options to a synced step whose buckets are all dense; the
CPU gets none, and a compressed wire keeps its exchange after the
backward.  What the options do on the TPU's compiler is checked in
``test_tpu_compile.py``.
"""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from repro.core import GradientSynchronizer, PlanExecutor, SyncConfig
from repro.core.schedule.planner import BucketPlan, CommPlan
from repro.launch.hlo_analysis import exchange_overlap, parse_hlo
from repro.launch.steps import OVERLAP_OPTIONS, synced_step_compiler_options

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

META = 'metadata={op_name="jit(step_fn)/shard_map/%s"}'
GRAD = META % "grad_sync/bucket_0/psum"
BWD = META % "forward/transpose(jvp(forward))/mlp/dot_general"

# an all-reduce-start/-done pair with a fusion scheduled between them
ASYNC = f"""HloModule m, is_scheduled=true

%fused_mlp (p0: f32[64,64]) -> f32[64,64] {{
  %p0 = f32[64,64]{{1,0:T(8,128)}} parameter(0)
  ROOT %t = f32[64,64]{{1,0:T(8,128)}} tanh(%p0)
}}

%sum (a: f32[], b: f32[]) -> f32[] {{
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add = f32[] add(%a, %b)
}}

ENTRY %main (g: f32[64,64], x: f32[64,64]) -> (f32[64,64], f32[64,64]) {{
  %g = f32[64,64]{{1,0:T(8,128)}} parameter(0)
  %x = f32[64,64]{{1,0:T(8,128)}} parameter(1)
  %all-reduce-start = f32[64,64]{{1,0:T(8,128)}} all-reduce-start(%g), channel_id=1, replica_groups={{{{0,1,2,3}}}}, to_apply=%sum, {GRAD}
  %fusion.1 = f32[64,64]{{1,0:T(8,128)}} fusion(%x), kind=kLoop, calls=%fused_mlp, {BWD}
  %all-reduce-done = f32[64,64]{{1,0:T(8,128)}} all-reduce-done(%all-reduce-start), {GRAD}
  ROOT %out = (f32[64,64]{{1,0:T(8,128)}}, f32[64,64]{{1,0:T(8,128)}}) tuple(%all-reduce-done, %fusion.1)
}}
"""

# a synchronous all-reduce scheduled after the backward's last op
SYNC = f"""HloModule m, is_scheduled=true

%fused_mlp (p0: f32[64,64]) -> f32[64,64] {{
  %p0 = f32[64,64]{{1,0:T(8,128)}} parameter(0)
  ROOT %t = f32[64,64]{{1,0:T(8,128)}} tanh(%p0)
}}

%sum (a: f32[], b: f32[]) -> f32[] {{
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add = f32[] add(%a, %b)
}}

ENTRY %main (x: f32[64,64]) -> f32[64,64] {{
  %x = f32[64,64]{{1,0:T(8,128)}} parameter(0)
  %fusion.1 = f32[64,64]{{1,0:T(8,128)}} fusion(%x), kind=kLoop, calls=%fused_mlp, {BWD}
  ROOT %psum.3 = f32[64,64]{{1,0:T(8,128)}} all-reduce(%fusion.1), channel_id=1, replica_groups={{{{0,1,2,3}}}}, to_apply=%sum, {GRAD}
}}
"""

# the TPU's async collective fusion: start, compute and done fusions that
# each hold the same all-reduce under one chain_id; and, beside it, a
# synchronous all-reduce of a quarter of the bytes
CHAIN = f"""HloModule m, is_scheduled=true

%sum (a: f32[], b: f32[]) -> f32[] {{
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add = f32[] add(%a, %b)
}}

%start_c (s0: f32[128,64]) -> f32[128,64] {{
  %s0 = f32[128,64]{{1,0:T(8,128)}} parameter(0)
  ROOT %all-reduce.1 = f32[128,64]{{1,0:T(8,128)}} all-reduce(%s0), channel_id=1, to_apply=%sum, frontend_attributes={{chain_id="0"}}, {GRAD}
}}

%host_c (h0: f32[128,64], h1: f32[64,64]) -> (f32[128,64], f32[64,64]) {{
  %h0 = f32[128,64]{{1,0:T(8,128)}} parameter(0)
  %h1 = f32[64,64]{{1,0:T(8,128)}} parameter(1)
  %all-reduce.2 = f32[128,64]{{1,0:T(8,128)}} all-reduce(%h0), channel_id=1, to_apply=%sum, frontend_attributes={{chain_id="0"}}, {GRAD}
  %t = f32[64,64]{{1,0:T(8,128)}} tanh(%h1)
  ROOT %r = (f32[128,64]{{1,0:T(8,128)}}, f32[64,64]{{1,0:T(8,128)}}) tuple(%all-reduce.2, %t)
}}

%done_c (d0: f32[128,64]) -> f32[128,64] {{
  %d0 = f32[128,64]{{1,0:T(8,128)}} parameter(0)
  ROOT %all-reduce.3 = f32[128,64]{{1,0:T(8,128)}} all-reduce(%d0), channel_id=1, to_apply=%sum, frontend_attributes={{chain_id="0"}}, {GRAD}
}}

ENTRY %main (g: f32[128,64], x: f32[64,64], y: f32[32,64]) -> (f32[128,64], f32[64,64], f32[32,64]) {{
  %g = f32[128,64]{{1,0:T(8,128)}} parameter(0)
  %x = f32[64,64]{{1,0:T(8,128)}} parameter(1)
  %y = f32[32,64]{{1,0:T(8,128)}} parameter(2)
  %async-collective-start = f32[128,64]{{1,0:T(8,128)}} fusion(%g), kind=kCustom, calls=%start_c
  %fusion.7 = (f32[128,64]{{1,0:T(8,128)}}, f32[64,64]{{1,0:T(8,128)}}) fusion(%async-collective-start, %x), kind=kOutput, calls=%host_c, {BWD}
  %get-tuple-element.1 = f32[128,64]{{1,0:T(8,128)}} get-tuple-element(%fusion.7), index=0
  %get-tuple-element.2 = f32[64,64]{{1,0:T(8,128)}} get-tuple-element(%fusion.7), index=1
  %async-collective-done = f32[128,64]{{1,0:T(8,128)}} fusion(%get-tuple-element.1), kind=kCustom, calls=%done_c, {GRAD}
  %psum.9 = f32[32,64]{{1,0:T(8,128)}} all-reduce(%y), channel_id=2, to_apply=%sum, {GRAD}
  ROOT %out = (f32[128,64]{{1,0:T(8,128)}}, f32[64,64]{{1,0:T(8,128)}}, f32[32,64]{{1,0:T(8,128)}}) tuple(%async-collective-done, %get-tuple-element.2, %psum.9)
}}
"""


def test_overlap_counts_a_start_done_pair_with_compute_between():
    assert exchange_overlap(ASYNC) == 1.0


def test_overlap_reads_zero_for_an_all_reduce_after_the_backward():
    assert exchange_overlap(SYNC) == 0.0


def test_overlap_follows_a_collective_fusion_chain_by_bytes():
    """The chain's 32 KiB overlap the compute fusion; the synchronous 8 KiB
    all-reduce does not: 0.8 of the bytes."""
    assert exchange_overlap(CHAIN) == pytest.approx(0.8)


def test_overlap_is_none_without_a_gradient_collective():
    assert exchange_overlap(SYNC, scope="no_such_scope") is None
    assert exchange_overlap(SYNC.replace("grad_sync", "other")) is None


def test_parse_hlo_reads_opcodes_past_tiled_layouts():
    """A TPU layout's tiles (``{1,0:T(8,128)}``) are not an opcode."""
    comps, entry = parse_hlo(CHAIN)
    ops = [i.op for i in comps[entry].instructions]
    assert ops == ["parameter"] * 3 + ["fusion", "fusion",
                                       "get-tuple-element",
                                       "get-tuple-element", "fusion",
                                       "all-reduce", "tuple"]


def _mesh(platform):
    return types.SimpleNamespace(devices=np.array(
        [types.SimpleNamespace(platform=platform)] * 4))


def _plan(*compressors):
    return CommPlan(buckets=tuple(
        BucketPlan(leaves=(i,), compressor=c, algo="psum", bucket_bytes=4,
                   pack=False, error_feedback=c != "none")
        for i, c in enumerate(compressors)))


@pytest.mark.parametrize("engine,platform,overlaps", [
    (GradientSynchronizer(SyncConfig(), ("data",)), "tpu", True),
    (GradientSynchronizer(SyncConfig(), ("data",)), "cpu", False),
    (GradientSynchronizer(SyncConfig(compressor="int8"), ("data",)), "tpu",
     False),
    (GradientSynchronizer(SyncConfig(compressor="topk"), ("data",)), "tpu",
     False),
    (PlanExecutor(_plan("none", "none"), ("data",)), "tpu", True),
    (PlanExecutor(_plan("none", "int8"), ("data",)), "tpu", False),
    (types.SimpleNamespace(), "tpu", False),
], ids=["dense-tpu", "dense-cpu", "int8-tpu", "topk-tpu", "plan-dense-tpu",
        "plan-mixed-tpu", "other-engine-tpu"])
def test_overlap_options_only_for_dense_plans_on_tpu(engine, platform,
                                                     overlaps):
    opts = synced_step_compiler_options(engine, _mesh(platform))
    assert opts == (OVERLAP_OPTIONS if overlaps else {})


ORACLE_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.api import SessionConfig, TrainSession
from repro.core import PlanExecutor, SyncConfig, make_strategy
from repro.core.grad_sync import plan_from_config
from repro.models.sharding_ctx import manual_axes, manual_region
from repro.optim import apply_updates

cfg = SyncConfig(**json.loads(sys.argv[1]))
s = TrainSession(SessionConfig(arch="deepseek-v2-lite-16b", reduced=True,
                               batch=4, seq=16, steps=8, data_parallel=4),
                 strategy=make_strategy("every_step", sync=cfg))
model, opt, mesh, axes = s.model, s.optimizer, s.mesh, tuple(s.axes)
host = lambda t: jax.tree.map(np.asarray, t)
p0, o0 = host(s._params), host(s._opt_state)
executor = PlanExecutor(plan_from_config(cfg, p0), axes)


def body(params, opt_state, sync_state, batch, step, rng):
    # the order before overlap: the whole backward, then the one-bucket
    # plan's exchange, then Adam
    sync_state = jax.tree.map(lambda x: x[0], sync_state)
    with manual_region():
        (loss, _), grads = jax.value_and_grad(
            model.loss_and_counts, has_aux=True)(params, batch)
    grads, sync_state = executor(grads, sync_state, rng)
    updates, opt_state = opt.update(grads, opt_state, params, step)
    return (apply_updates(params, updates), opt_state,
            jax.tree.map(lambda x: x[None], sync_state),
            jax.lax.pmean(loss, axes))


oracle = jax.jit(jax.shard_map(
    body, mesh=mesh,
    in_specs=(P(), P(), P(axes), {"tokens": P(axes, None)}, P(), P()),
    out_specs=(P(), P(), P(axes), P()), axis_names=manual_axes(mesh, axes),
    check_vma=False))
rep = NamedSharding(mesh, P())
p = jax.device_put(p0, rep)
o = jax.device_put(o0, rep)
ss = jax.device_put(jax.tree.map(
    lambda x: np.broadcast_to(x, (4,) + x.shape),
    host(executor.init_state(p0))), NamedSharding(mesh, P(axes)))
ref_losses, losses = [], []
for step in range(3):
    batch = jax.device_put(s.data.batch(step), s._batch_sharding())
    p, o, ss, loss = oracle(p, o, ss, batch, jnp.asarray(step, jnp.int32),
                            jax.random.fold_in(s.rng, step))
    ref_losses.append(float(loss))
    losses.append(s.step_once())


def same(a, b):
    a, b = jax.tree.leaves(host(a)), jax.tree.leaves(host(b))
    return len(a) == len(b) and all(
        x.dtype == y.dtype and np.array_equal(x, y) for x, y in zip(a, b))


print("RESULT", json.dumps({
    "losses": losses == ref_losses, "params": same(s._params, p),
    "opt": same(s._opt_state, o), "sync": same(s._sync_state, ss),
    "error_state": "error" in s._sync_state}))
"""


@pytest.mark.parametrize("sync,error_state", [
    (dict(compressor="none", algo="psum"), False),
    (dict(compressor="int8", algo="ring", bucket_bytes=1 << 16,
          error_feedback=True), True),
], ids=["dense-psum", "int8-ef"])
def test_synced_step_matches_exchange_after_backward(sync, error_state):
    """On 4 CPU devices, 3 steps of the session's synced step give the
    loss, params, Adam state and exchange state, bit for bit, of an oracle
    that takes every gradient first, then runs the one-strategy plan's
    exchange, then Adam (the CPU compiles the same sums in the same
    order).  The int8 wire with error feedback keeps its residuals.  This
    covers the CPU path only: the overlap options are TPU compile options
    and the CPU step gets none, so what they do to the sums is read on the
    chip (the benchmark's ``correct`` check and ``replica_gap``)."""
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "-c", ORACLE_SCRIPT,
                          json.dumps(sync)], env=env, capture_output=True,
                         text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    line = [l for l in res.stdout.splitlines() if l.startswith("RESULT")]
    assert json.loads(line[0].split(" ", 1)[1]) == {
        "losses": True, "params": True, "opt": True, "sync": True,
        "error_state": error_state}
