"""A run of the harness on the CPU at a small size, with the timed path
broken underneath: ``correct`` has to come out false for each fault a
training cell can have, and true for the program as it is.  The control
(the reference one precision step below the cell's, in the program's
place) has to fail as well.  The cells here state float32, so their
control is bfloat16; their limits sit between the two kinds of reading.
"""
import jax
import jax.numpy as jnp
import pytest

import check
import harness
import tiny

LIMITS = {"loss_gap": 1e-4, "grad_gap": 1e-2, "delta_gap": 0.3}
CPU = {"platform": "cpu", "kind": "cpu", "count": 0}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
SEED = 2 ** 35 + 3


def ds_cell():
    return tiny.cell(tiny.DEEPSEEK, tiny.job("train-4k", rows_per_chip=4,
                                             seq_len=64), LIMITS, f32=True)


SYNCS = {
    "psum": {"compressor": "none", "algo": "psum", "error_feedback": False},
    "int8_fused-ring": {"compressor": "int8_fused", "algo": "ring",
                        "bucket_bytes": 32 * 1024, "error_feedback": True},
}
DP4_LIMITS = dict(LIMITS, replica_gap=0.0)


def ds_dp4_cell(wire="psum"):
    """Four virtual devices, the gradient exchanged by the job's sync, at
    the configuration's own capacity factor and balance loss.  Each device
    routes its own rows, and at these sizes some choices overflow their
    expert's capacity, so the reference has to route each device's rows
    alone to agree."""
    sync = {"scheduler": "every_step", "config": SYNCS[wire]}
    return tiny.cell(tiny.DEEPSEEK, tiny.job("train-4k", chips=4,
                                             rows_per_chip=2, seq_len=64,
                                             sync=sync),
                     DP4_LIMITS, f32=True)


def run(cell, tamper=None):
    return harness.run(cell, SEED, 0.5, False, 0.0,
                       device=dict(CPU, count=cell.chips), peaks=PEAKS,
                       tamper=tamper)


def _wrap_step(session, make):
    """Once the session is built, replace its step program (``_base``, or
    ``_sync`` where the job syncs gradients) with ``make(program)``."""
    build = session._build

    def patched():
        if not session._built:
            build()
            name = "_base" if session.strategy is None else "_sync"
            setattr(session, name, make(getattr(session, name)))
    session._build = patched


def unchanged_state(session):
    """A step that returns its state unchanged."""
    def make(f):
        def step(*args):
            out = f(*(jax.tree.map(jnp.copy, a) for a in args))
            return (*args[:len(out) - 1], out[-1])
        return step
    _wrap_step(session, make)


def half_batch(session):
    """Half of the batch left out, the mean taken over the rest."""
    def make(f):
        def step(*args):
            return f(*({"tokens": a["tokens"][:a["tokens"].shape[0] // 2]}
                       if isinstance(a, dict) and "tokens" in a else a
                       for a in args))
        return step
    _wrap_step(session, make)


class NoExchange:
    """A gradient reducer that leaves out the exchange between chips."""

    def init_state(self, grads):
        return {"step": jnp.zeros((), jnp.int32)}

    def __call__(self, grads, state, rng):
        return grads, {"step": state["step"] + 1}


def no_exchange(session):
    from repro.core import SyncStrategy, get_scheduler
    session.strategy = SyncStrategy(scheduler=get_scheduler("every_step"),
                                    grad_reducer=NoExchange())


class LeftOut:
    """The exchange with the last replica left out of it: that device
    keeps its own gradient, the others get the synced one."""

    def __init__(self, reducer):
        self.reducer = reducer

    def init_state(self, grads):
        return self.reducer.init_state(grads)

    def __call__(self, grads, state, rng):
        synced, state = self.reducer(grads, state, rng)
        last = jax.lax.axis_index("data") == jax.lax.axis_size("data") - 1
        return jax.tree.map(lambda s, g: jnp.where(last, g.astype(s.dtype), s),
                            synced, grads), state


def replica_left_out(session):
    from repro.core import SyncStrategy
    session.strategy = SyncStrategy(
        scheduler=session.strategy.scheduler,
        grad_reducer=LeftOut(session.strategy.grad_reducer))


def test_sound_program_is_correct():
    r = run(ds_cell())
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0


@pytest.mark.parametrize("fault", [unchanged_state, half_batch],
                         ids=["unchanged_state", "half_batch"])
def test_fault_is_not_correct(fault):
    r = run(ds_cell(), tamper=fault)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("wire", sorted(SYNCS))
def test_sound_exchange_is_correct_and_drops_choices(wire):
    sessions = []
    r = run(ds_dp4_cell(wire), tamper=sessions.append)
    assert r["correct"], r["checks"]
    assert r["checks"]["replica_gap"]["value"] == 0.0
    assert sessions[0].dropped_tokens > 0


@pytest.mark.parametrize("fault", [no_exchange, replica_left_out,
                                   unchanged_state, half_batch],
                         ids=["no_exchange", "replica_left_out",
                              "unchanged_state", "half_batch"])
def test_exchange_fault_is_not_correct(fault):
    r = run(ds_dp4_cell(), tamper=fault)
    assert not r["correct"], r["checks"]


def test_control_is_not_correct():
    cell = ds_cell()
    params = harness.Params(cell)
    feed = cell.traffic(SEED)
    batches = [feed.batch(k) for k in range(cell.job["checked_steps"])]
    ref = harness.reference_steps(cell, params, SEED, batches)
    ctrl = harness.reference_steps(cell, params, SEED, batches,
                                   lowp=jnp.bfloat16)
    values = {k: v for k, (v, _) in check.readings(ctrl, ref).items()}
    ok, rows = check.judge(values, LIMITS)
    assert not ok, rows
