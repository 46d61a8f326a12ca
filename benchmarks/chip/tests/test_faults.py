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


def ds_dp4_cell():
    """Four virtual devices, the gradient exchanged by the job's sync.  Each
    device routes its own rows, so expert capacity and the balance loss
    would differ from the reference's over the whole batch: this cell has
    capacity for every choice and no balance loss."""
    config = dict(tiny.DEEPSEEK, capacity_factor=4.0, aux_coef=0.0,
                  overrides=dict(tiny.DEEPSEEK["overrides"],
                                 capacity_factor=4.0, router_aux_coef=0.0))
    sync = {"scheduler": "every_step",
            "config": {"compressor": "none", "algo": "psum",
                       "error_feedback": False}}
    return tiny.cell(config, tiny.job("train-4k", chips=4, rows_per_chip=2,
                                      seq_len=64, sync=sync), LIMITS, f32=True)


def run(cell, tamper=None):
    return harness.run(cell, SEED, 0.5, False, 0.0,
                       device=dict(CPU, count=cell.chips), peaks=PEAKS,
                       tamper=tamper)


def _after_build(session, wrap):
    build = session._build

    def patched():
        if not session._built:
            build()
            wrap(session)
    session._build = patched


def unchanged_state(session):
    """A step that returns its state unchanged."""
    def wrap(s):
        f = s._base

        def step(p, o, b, i):
            loss = f(jax.tree.map(jnp.copy, p), jax.tree.map(jnp.copy, o),
                     b, i)[2]
            return p, o, loss
        s._base = step
    _after_build(session, wrap)


def half_batch(session):
    """Half of the batch left out, the mean taken over the rest."""
    def wrap(s):
        f = s._base

        def step(p, o, b, i):
            n = b["tokens"].shape[0] // 2
            return f(p, o, {"tokens": b["tokens"][:n]}, i)
        s._base = step
    _after_build(session, wrap)


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


def test_sound_program_is_correct():
    r = run(ds_cell())
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0


@pytest.mark.parametrize("fault", [unchanged_state, half_batch],
                         ids=["unchanged_state", "half_batch"])
def test_fault_is_not_correct(fault):
    r = run(ds_cell(), tamper=fault)
    assert not r["correct"], r["checks"]


def test_sound_exchange_is_correct_and_no_exchange_is_not():
    cell = ds_dp4_cell()
    assert run(cell)["correct"]
    r = run(cell, tamper=no_exchange)
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
