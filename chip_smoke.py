#!/usr/bin/env python3
"""Chip smoke test: the training path at full width on TPU.

    python chip_smoke.py              # one chip (the default)
    python chip_smoke.py --chips 4    # data-parallel gradient sync, 4 chips

One chip runs three phases in this one process (a chip belongs to one
process at a time):

  * kernels — the compiled Pallas communication kernels (``quantize_ef``,
    ``quantize_tiles``, ``dequant_accum`` over 1 and 4 payloads,
    ``topk_ef``) on a 32 MiB f32 bucket and on a ragged length, each
    against its ``impl="xla"`` twin from ``kernels/ref.py`` within the
    bounds ``tests/test_kernels.py`` states;
  * vanilla — full-width xlstm-125m (bf16, batch 8, seq 1024) through the
    training CLI ``repro.launch.train`` with ``--sync vanilla``;
  * int8 — the same with ``--sync comm --compressor int8_fused --algo
    ring``; its compiled step must hold the Pallas kernels
    (``tpu_custom_call``).

``--chips 4`` runs only the data-parallel phase: global batch 16 over
``data=4``, 3 steps each of dense ring, dense psum, int8_fused ring and
vanilla, with the batch and the error-feedback state checked to span all
four devices.

Losses must be finite, and runs that start from the same params and batch
must agree within ``LOSS_RTOL``.  Times printed are smoke numbers, not
benchmark results.  Any failure exits non-zero without a result; no TPU,
or ``REPRO_KERNELS_IMPL`` set, is a failure.  The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

ARCH = ["--arch", "xlstm-125m"]
SEQ = 1024
STEPS = 3
SEED = 0
BUCKET = 8 * 1024 * 1024          # 32 MiB of f32: the default sync bucket
RAGGED = 3 * 1024 * 1024 + 17     # not a multiple of the 1024-element tile
# One bf16 ulp, relative: two runs from the same params and batch may
# differ only by the rounding of differently ordered bf16 sums, far less
# than this on a loss averaged in f32 over 8192+ tokens.
LOSS_RTOL = 2.0 ** -8


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def device_check(n_chips: int):
    """The chip, and nothing else: no CPU fallback, compiled kernels."""
    import jax

    from repro.kernels.dispatch import IMPL_ENV, resolve_impl
    require(not os.environ.get(IMPL_ENV),
            f"{IMPL_ENV} is set; unset it so the kernels run compiled")
    devs = jax.devices()
    d0 = devs[0]
    log(f"device platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devs)}")
    require(d0.platform == "tpu", f"no TPU found (platform {d0.platform})")
    require(len(devs) == n_chips,
            f"--chips {n_chips} but JAX sees {len(devs)} device(s)")
    require(resolve_impl(None) == "pallas",
            f"kernels resolve to {resolve_impl(None)!r}, not 'pallas'")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def _compare(name, labels, got, want, atols, failures):
    """Max |got - want| per output against its bound (0 = bit-equal; a
    non-zero bound also allows 1e-6 relative)."""
    import numpy as np
    parts = []
    for label, a, b, atol in zip(labels, got, want, atols):
        a = np.asarray(a, np.float64)
        b = np.asarray(b, np.float64)
        require(a.shape == b.shape, f"{name}: shape {a.shape} != {b.shape}")
        diff = np.abs(a - b)
        worst = float(diff.max()) if diff.size else 0.0
        bad = int(np.sum(diff > atol + 1e-6 * np.abs(b) * (atol > 0)))
        parts.append(f"{label} max|d|={worst:.3g} over={bad}")
        if bad or not np.all(np.isfinite(a)):
            failures.append(f"{name} {label}: {bad} elements past {atol}")
    log(f"kernel {name}: " + ", ".join(parts))


def kernel_phase():
    """Compiled kernels vs their xla twins; bounds as in test_kernels."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    failures = []
    key = jax.random.PRNGKey(SEED)
    t0 = time.time()
    for n in (BUCKET, RAGGED):
        kg, ke = jax.random.split(jax.random.fold_in(key, n))
        g = jax.random.normal(kg, (n,), jnp.float32) * 2.0
        e = jax.random.normal(ke, (n,), jnp.float32) * 0.3
        # q and scales bit-equal, the residual within test_kernels' 3e-6
        _compare(f"quantize_ef n={n}", ("q", "e_new", "scales"),
                 ops.quantize_ef(g, e),
                 ops.quantize_ef(g, e, impl="xla"), (0, 3e-6, 0), failures)
        _compare(f"quantize_tiles n={n}", ("q", "scales"),
                 ops.quantize_tiles(g),
                 ops.quantize_tiles(g, impl="xla"), (0, 0), failures)
        for w in (1, 4):
            payloads = [ops.quantize_tiles(g * (j + 1) - e, impl="xla")
                        for j in range(w)]
            q = jnp.stack([p[0] for p in payloads])
            s = jnp.stack([p[1] for p in payloads])
            # test_dequant_accum_matches_per_payload_loop's rtol/atol
            _compare(f"dequant_accum w={w} n={n}", ("sum",),
                     (ops.dequant_accum(q, s),),
                     (ops.dequant_accum(q, s, impl="xla"),), (1e-5,),
                     failures)
        _compare(f"topk_ef n={n}", ("y", "e_new"), ops.topk_ef(g, e),
                 ops.topk_ef(g, e, impl="xla"), (0, 0), failures)
    require(not failures, "; ".join(failures))
    log(f"kernels ok in {time.time() - t0:.1f}s (compile included)")


def train(label: str, batch: int, extra):
    """One run of the training CLI; returns (session, losses)."""
    from repro.launch import train as train_cli
    argv = [*ARCH, "--batch", str(batch), "--seq", str(SEQ), "--steps",
            str(STEPS), "--log-every", "1", *extra]
    session, losses = train_cli.run(argv)
    times = session.step_times
    log(f"{label}: losses {losses}")
    log(f"{label}: first step {times[0]:.2f}s (compile included), later "
        f"steps {[round(t, 4) for t in times[1:]]}s — smoke numbers, not "
        f"benchmark results")
    require(len(losses) == STEPS and all(math.isfinite(x) for x in losses),
            f"{label}: non-finite or missing losses {losses}")
    return session, losses


def agree(a_label, a, b_label, b, steps=None) -> None:
    steps = len(a) if steps is None else steps
    worst = max(abs(x - y) / abs(y) for x, y in zip(a[:steps], b[:steps]))
    log(f"{a_label} vs {b_label}: max relative loss gap {worst:.3g} over "
        f"{steps} step(s) (bound {LOSS_RTOL:.3g})")
    require(worst <= LOSS_RTOL,
            f"{a_label} and {b_label} losses differ by {worst:.3g}")


def step_hlo(session) -> str:
    """Compiled text of the session's synced step, for the arguments its
    steps were called with (the same program the run executed)."""
    import jax
    import jax.numpy as jnp
    batch = jax.device_put(session.data.batch(0), session._batch_sharding())
    return session._sync.lower(
        session._params, session._opt_state, session._sync_state, batch,
        jnp.asarray(0, jnp.int32), jax.random.fold_in(session.rng, 0)
    ).compile().as_text()


def one_chip() -> None:
    kernel_phase()
    _, dense = train("vanilla", 8, ["--sync", "vanilla"])
    session, int8 = train("int8_fused/ring", 8,
                          ["--sync", "comm", "--compressor", "int8_fused",
                           "--algo", "ring"])
    t0 = time.time()
    calls = step_hlo(session).count("tpu_custom_call")
    log(f"int8_fused/ring step holds {calls} tpu_custom_call(s) "
        f"(lookup {time.time() - t0:.1f}s)")
    require(calls > 0, "int8_fused/ring step holds no compiled kernel")
    agree("int8_fused/ring", int8, "vanilla", dense, steps=1)


def spans(tree, n: int) -> bool:
    import jax
    leaves = jax.tree.leaves(tree)
    return bool(leaves) and all(len(x.sharding.device_set) == n
                                for x in leaves)


def four_chips() -> None:
    import jax
    runs = {}
    for label, extra in (
            ("ring", ["--sync", "comm", "--algo", "ring"]),
            ("psum", ["--sync", "comm", "--algo", "psum"]),
            ("int8_fused/ring", ["--sync", "comm", "--compressor",
                                 "int8_fused", "--algo", "ring"]),
            ("vanilla", ["--sync", "vanilla"])):
        session, losses = train(label, 16, extra)
        batch = jax.device_put(session.data.batch(session.step),
                               session._batch_sharding())
        state = (session._params if session.strategy is None
                 else session._sync_state)
        what = "params" if session.strategy is None else "EF state"
        split = not batch["tokens"].sharding.is_fully_replicated
        log(f"{label}: batch spans {len(batch['tokens'].sharding.device_set)}"
            f" devices (split={split}), {what} spans 4: {spans(state, 4)}")
        require(spans(batch, 4) and split, f"{label}: batch not split over 4")
        require(spans(state, 4), f"{label}: {what} not on all 4 devices")
        runs[label] = losses
    agree("ring", runs["ring"], "psum", runs["psum"])
    agree("vanilla", runs["vanilla"], "psum", runs["psum"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}; run it from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    from repro.kernels.dispatch import IMPL_ENV
    if os.environ.get(IMPL_ENV):
        print(f"chip_smoke: {IMPL_ENV} is set; unset it so the kernels "
              f"run compiled", file=sys.stderr)
        return 2
    from repro.launch.paths import use_compile_cache
    t0 = time.time()
    try:
        device = device_check(args.chips)
        log(f"compile cache: {use_compile_cache() or 'off'}")
        one_chip() if args.chips == 1 else four_chips()
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    log(f"all phases ok in {time.time() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
