"""The unified parallelism surface (ISSUE 9): ParallelismSpec round-trips,
the planner's TP/EP arms + divisibility/budget guards, the MoE capacity
drop tap, and the single ``--parallelism`` CLI flag with its warned shims.

The wire-level checks (all_to_all bit-identity, TP=2×DP=4 and EP=2×DP=4
step bit-exactness) need 8 host devices configured before jax initializes,
so they live in the multi_device_checks.py subprocess; the a2a identity
check is driven from here so this file is the satellite's entry point.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ParallelismSpec
from repro.core.schedule import (ExpertAxis, LayerProfile, LinkParams,
                                 TensorAxis, expert_parallel_arm, plan_rounds,
                                 tensor_parallel_arm)
from repro.core.schedule.topology import Topology


# ---------------------------------------------------------------------------
# ParallelismSpec: parse / validate round-trips
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec", [
    "dp=4,tp=2@fast_ici,pp=2@node,micro=8",
    "ep=2@device,shard",
    "tp=8",
    "dp=32",
    "dp=2,tp=2@device,ep=2",
    "micro=4",
    "shard",
    "",
])
def test_spec_string_roundtrip(spec):
    ps = ParallelismSpec.from_spec(spec)
    assert ParallelismSpec.from_spec(ps.spec()) == ps
    # the record block round-trips too (the DESIGN.md §14 schema)
    assert ParallelismSpec.from_record(ps.to_record()) == ps


def test_spec_parse_and_construction_errors():
    for bad in ("tp=0", "pp=-1", "tp=two", "nope=2", "tp=2,tp=4",
                "dp=2@node",          # dp takes no tier placement
                "micro=4@node",       # micro takes no tier placement
                "pp=2,shard"):        # competing optimizer-memory answers
        with pytest.raises(ValueError):
            ParallelismSpec.from_spec(bad)
    with pytest.raises(ValueError, match="meaningless"):
        ParallelismSpec(tp=1, tp_tier="device")


def test_spec_resolve_fills_dp_and_guards_divisibility():
    ps = ParallelismSpec.from_spec("tp=2,ep=2").resolve(32)
    assert (ps.dp, ps.world, ps.model_world) == (8, 32, 4)
    assert ps.spec() == "dp=8,tp=2,ep=2"
    with pytest.raises(ValueError, match="do not divide world"):
        ParallelismSpec.from_spec("tp=3").resolve(32)
    with pytest.raises(ValueError, match="!= world"):
        ParallelismSpec.from_spec("dp=4,tp=2").resolve(32)
    with pytest.raises(ValueError, match="unresolved dp=0"):
        ParallelismSpec.from_spec("tp=2").world


def test_spec_resolve_against_topology_tiers():
    topo = Topology.from_spec("node:4@datacenter,device:8@fast_ici")
    ps = ParallelismSpec.from_spec("tp=2@device").resolve(topo)
    assert ps.dp == 16
    with pytest.raises(ValueError, match="no tier named"):
        ParallelismSpec.from_spec("tp=2@pod").resolve(topo)
    with pytest.raises(ValueError, match="does not divide tier"):
        ParallelismSpec.from_spec("tp=16@device").resolve(topo)


def test_spec_legacy_bridge_and_trivial():
    assert ParallelismSpec.legacy(pipeline_stages=2, micro_batches=4,
                                  pipe_tier="node").spec() == \
        "pp=2@node,micro=4"
    assert ParallelismSpec.legacy(shard_state=True).shard_state
    assert ParallelismSpec().is_trivial
    assert not ParallelismSpec(micro_batches=4).is_trivial
    assert ParallelismSpec(micro_batches=1).is_trivial


# ---------------------------------------------------------------------------
# all_to_all bit-identity (8 fake devices -> subprocess, like every
# multi-device check; see multi_device_checks.check_all_to_all_bit_identity)
# ---------------------------------------------------------------------------

def test_all_to_all_bit_identity_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    res = subprocess.run(
        [sys.executable, "-c",
         "import multi_device_checks as m; m.check_all_to_all_bit_identity()"],
        cwd=os.path.dirname(__file__), env=env,
        capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "all_to_all bit-identity ok" in res.stdout


# ---------------------------------------------------------------------------
# TP/EP arm pricing
# ---------------------------------------------------------------------------

def _profiles(n=8, mb=4.0, t=2e-4):
    return [LayerProfile(t_backward_s=t, grad_bytes=mb * 2**20)
            for _ in range(n)]


def _tensor_axis(tokens=4096 * 512):
    return TensorAxis(global_tokens=float(tokens),
                      bytes_per_token=1024 * 4.0, n_layers=8)


def test_tp_arm_monotone_in_beta():
    """The activation edge is bandwidth traffic: model_comm_s (and with it
    the arm's modeled step) must be nondecreasing in β."""
    profs = _profiles()
    axis = _tensor_axis()
    prev = None
    for beta_gbps in (400, 100, 25, 6.25, 1.5):
        link = LinkParams(alpha_s=1e-6, beta_s_per_byte=1 / (beta_gbps * 1e9))
        arm = tensor_parallel_arm(profs, link, world=8, tp=2, axis=axis)
        assert arm.model_comm_s > 0
        if prev is not None:
            assert arm.model_comm_s > prev.model_comm_s
            assert arm.modeled_step_s >= prev.modeled_step_s
        prev = arm


def test_tp_arm_never_faster_than_dp_at_world_eq_tp():
    """At world == tp there is no DP edge left to shrink: the tp arm keeps
    the full backward and ADDS 4 serial activation allreduces per layer,
    so it must never be modeled faster than the every-step DP arm at the
    same world (at these configs — token-heavy activations, the regime
    the grid actually prices)."""
    profs = _profiles()
    axis = _tensor_axis()
    for beta_gbps in (100, 25, 1.5):
        for world in (2, 4, 8):
            link = LinkParams(alpha_s=1e-6,
                              beta_s_per_byte=1 / (beta_gbps * 1e9))
            best, arms = plan_rounds(profs, link, world, tensor=TensorAxis(
                global_tokens=axis.global_tokens,
                bytes_per_token=axis.bytes_per_token, n_layers=8,
                tp_grid=(world,)))
            key = f"tp({world})"
            assert key in arms, sorted(arms)
            assert arms[key].modeled_step_s >= \
                arms["every_step"].modeled_step_s


def test_tp_ep_arms_are_memory_levers():
    """tp shards ALL weights 1/tp; ep shards the expert fraction 1/ep —
    both must show up in opt_mem_bytes (how they win under a budget)."""
    profs = _profiles()
    link = LinkParams()
    tp_arm = tensor_parallel_arm(profs, link, world=8, tp=4,
                                 axis=_tensor_axis())
    ep_arm = expert_parallel_arm(
        profs, link, world=8, ep=4,
        axis=ExpertAxis(global_tokens=4096.0, bytes_per_token=128.0,
                        n_moe_layers=4, expert_fraction=0.8))
    _, arms = plan_rounds(profs, link, 8)
    repl = arms["every_step"].opt_mem_bytes
    assert tp_arm.opt_mem_bytes == pytest.approx(repl / 4)
    assert ep_arm.opt_mem_bytes == pytest.approx(repl * (0.8 / 4 + 0.2))


def test_tp_placement_prefers_fast_tier():
    """On a tiered topology the same tp size is priced once per hosting
    tier; the serial activation edge makes the fast inner tier strictly
    cheaper (why TP belongs on ICI)."""
    topo = Topology.from_spec("node:4@datacenter,device:8@fast_ici")
    _, arms = plan_rounds(_profiles(), topo, 32, tensor=_tensor_axis())
    assert arms["tp(4)@device"].model_comm_s < \
        arms["tp(4)@node"].model_comm_s


def test_plan_rounds_pinned_spec_guards():
    profs = _profiles()
    link = LinkParams()
    taxis = _tensor_axis()
    eaxis = ExpertAxis(global_tokens=4096.0, bytes_per_token=128.0,
                       n_moe_layers=4)
    # pinned model axis without its pricing descriptor
    with pytest.raises(ValueError, match="no TensorAxis"):
        plan_rounds(profs, link, 8, parallelism="tp=2")
    with pytest.raises(ValueError, match="no ExpertAxis"):
        plan_rounds(profs, link, 8, parallelism="ep=2")
    with pytest.raises(ValueError, match="no PipelineAxis"):
        plan_rounds(profs, link, 8, parallelism="pp=2")
    # divisibility guard fires before any pricing
    with pytest.raises(ValueError, match="do not divide world"):
        plan_rounds(profs, link, 8, parallelism="tp=3", tensor=taxis)
    # tier guard on a topology
    topo = Topology.from_spec("node:4@datacenter,device:8@fast_ici")
    with pytest.raises(ValueError, match="no tier named"):
        plan_rounds(profs, topo, 32, parallelism="tp=2@pod", tensor=taxis)
    # tp/ep arms never carry shard_state: the combination is outside the
    # search space and must fail loudly, not silently plan something else
    with pytest.raises(ValueError, match="matches no priced arm"):
        plan_rounds(profs, link, 8, parallelism="ep=2,shard", expert=eaxis)
    # a pinned, reachable spec filters the pool to matching arms only
    best, _ = plan_rounds(profs, link, 8, parallelism="tp=2", tensor=taxis)
    assert (best.tp, best.parallelism.spec()) == (2, "dp=4,tp=2")


def test_memory_budget_can_select_model_axis():
    """A budget below the replicated footprint must move the winner onto a
    memory-shrinking arm (shard/tp/ep), never silently keep replicated."""
    profs = _profiles()
    link = LinkParams()
    _, arms = plan_rounds(profs, link, 8)
    repl = arms["every_step"].opt_mem_bytes
    best, _ = plan_rounds(profs, link, 8, tensor=_tensor_axis(),
                          memory_budget_bytes=repl * 0.6)
    assert best.opt_mem_bytes <= repl * 0.6
    assert best.tp > 1 or best.shard_state or best.ep > 1


# ---------------------------------------------------------------------------
# MoE capacity overflow: the device counter (DESIGN.md §14)
# ---------------------------------------------------------------------------

def _moe_cfg(capacity_factor):
    from repro.configs.base import ModelConfig
    return ModelConfig(name="t", family="qwen3", num_layers=1, d_model=16,
                       num_heads=2, num_kv_heads=2, d_ff=32, vocab_size=64,
                       num_experts=4, top_k=2, moe_d_ff=24,
                       capacity_factor=capacity_factor)


def _moe_params(cfg):
    d, E, ff = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    return {"router": jax.random.normal(ks[0], (d, E)) * 0.1,
            "wi_gate": jax.random.normal(ks[1], (E, d, ff)),
            "wi_up": jax.random.normal(ks[2], (E, d, ff)),
            "wo": jax.random.normal(ks[3], (E, ff, d))}


def _recount_drops(cfg, params, x):
    """NumPy recount of the capacity dispatch: each token's k choices take
    the next free slot of their expert in (token, choice) order, and a
    choice past the expert's capacity is dropped.  Returns (dropped,
    routed)."""
    xf = np.asarray(x, np.float32).reshape(-1, cfg.d_model)
    logits = xf @ np.asarray(params["router"], np.float32)
    experts = np.argsort(-logits, axis=-1, kind="stable")[:, :cfg.top_k]
    n = xf.shape[0]
    cap = int(max(1, n * cfg.top_k / cfg.num_experts * cfg.capacity_factor))
    load = np.zeros(cfg.num_experts, np.int64)
    dropped = 0
    for e in experts.reshape(-1):
        dropped += load[e] >= cap
        load[e] += 1
    return float(dropped), float(n * cfg.top_k)


def test_moe_forced_overflow_surfaces_dropped_tokens():
    """capacity_factor far below the routing skew MUST report drops, as
    device scalars beside the balance loss: exactly the NumPy recount of
    the same routing, in the forward program and in the grad program
    (where training meets them), and zero drops at ample capacity."""
    from repro.models import moe

    x = jax.random.normal(jax.random.PRNGKey(4), (8, 4, 16))
    cfg = _moe_cfg(0.25)                          # forced overflow
    params = _moe_params(cfg)
    _, aux = jax.jit(lambda v: moe.moe_ffn(params, cfg, v))(x)
    want = _recount_drops(cfg, params, x)
    assert want[0] > 0 and want[1] == 8 * 4 * cfg.top_k
    assert (float(aux["dropped"]), float(aux["routed"])) == want

    def loss(v):
        out, a = moe.moe_ffn(params, cfg, v)
        return jnp.sum(out ** 2) + a["balance"], a
    (_, aux), g = jax.jit(jax.value_and_grad(loss, has_aux=True))(x)
    assert np.all(np.isfinite(np.asarray(g)))
    assert (float(aux["dropped"]), float(aux["routed"])) == want

    cfg3 = _moe_cfg(8.0)                          # ample capacity
    _, aux = jax.jit(lambda v: moe.moe_ffn(_moe_params(cfg3), cfg3, v))(x)
    assert (float(aux["dropped"]), float(aux["routed"])) == (
        0.0, 8 * 4 * cfg3.top_k)


def _session(arch, **kw):
    from repro.api import SessionConfig, TrainSession
    return TrainSession(SessionConfig(arch=arch, reduced=True, batch=2,
                                      seq=16, steps=4, **kw))


def test_moe_drop_tap_disabled_counts_nothing():
    """The session's counters read only what its own steps returned: a
    dense model routes nothing, and an MoE session's counts do not leak
    into the next session (the same first step counts the same)."""
    dense = _session("gemma-2b")
    dense.step_once()
    assert (dense.dropped_tokens, dense.routed_tokens) == (0.0, 0.0)

    a = _session("deepseek-v2-lite-16b")
    a.step_once()
    first = (a.dropped_tokens, a.routed_tokens)
    a.step_once()
    assert a.routed_tokens == 2 * first[1] > 0
    b = _session("deepseek-v2-lite-16b")
    b.step_once()
    assert (b.dropped_tokens, b.routed_tokens) == first


def test_render_moe_drops_report():
    from repro.launch.report import render_moe_drops

    over = render_moe_drops(26.0, 768.0, 1.25)
    assert "26" in over and "768" in over and "3.4%" in over
    assert "capacity_factor" in over
    clean = render_moe_drops(0.0, 768.0, 1.25)
    assert "no overflow" in clean


# ---------------------------------------------------------------------------
# CLI: the unified --parallelism flag + warned shims (satellite b)
# ---------------------------------------------------------------------------

def _resolve(argv, capsys=None):
    from repro.launch.train import parse_args, resolve_cli_parallelism
    return resolve_cli_parallelism(parse_args(argv))


def test_cli_plan_world_is_gone():
    from repro.launch.train import parse_args
    with pytest.raises(SystemExit):
        parse_args(["--plan-world", "256"])


def test_cli_parallelism_spec_parses():
    spec, shard, pipe, micro = _resolve(
        ["--parallelism", "dp=4,tp=2@device,micro=2"])
    assert (spec.dp, spec.tp, spec.tp_tier) == (4, 2, "device")
    assert (shard, pipe, micro) == (False, 1, 2)
    # a real pipeline with no micro=M gets the executor's default M=8
    spec, _, pipe, micro = _resolve(["--parallelism", "pp=2"])
    assert (spec.micro_batches, pipe, micro) == (8, 2, 8)
    with pytest.raises(SystemExit, match="--parallelism:"):
        _resolve(["--parallelism", "tp=0"])
    with pytest.raises(SystemExit, match="--parallelism:"):
        _resolve(["--parallelism", "pp=2,shard"])


def test_cli_shim_shard_state(capsys):
    spec, shard, pipe, micro = _resolve(["--shard-state"])
    assert shard and spec.shard_state and spec.spec() == "shard"
    assert "--shard-state" in capsys.readouterr().out


def test_cli_shim_pipeline_stages(capsys):
    spec, shard, pipe, micro = _resolve(["--pipeline-stages", "2"])
    assert (pipe, micro) == (2, 8)
    assert (spec.pp, spec.micro_batches) == (2, 8)
    assert "--pipeline-stages" in capsys.readouterr().out


def test_cli_shim_micro_batches(capsys):
    spec, shard, pipe, micro = _resolve(["--micro-batches", "4"])
    assert (pipe, micro) == (1, 4)
    assert spec.spec() == "micro=4"
    assert "--micro-batches" in capsys.readouterr().out


def test_cli_no_flags_no_warning(capsys):
    spec, shard, pipe, micro = _resolve([])
    assert spec.is_trivial and (shard, pipe, micro) == (False, 1, 1)
    assert "deprecated" not in capsys.readouterr().out


@pytest.mark.parametrize("shim", [["--shard-state"],
                                  ["--pipeline-stages", "2"],
                                  ["--micro-batches", "4"]])
def test_cli_spec_refuses_each_shim(shim):
    with pytest.raises(SystemExit, match="subsumes"):
        _resolve(["--parallelism", "dp=2"] + shim)


def test_cli_legacy_pipe_shard_conflict():
    with pytest.raises(SystemExit, match="pick one"):
        _resolve(["--pipeline-stages", "2", "--shard-state"])
