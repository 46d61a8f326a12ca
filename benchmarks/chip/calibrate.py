"""Readings for setting a cell's limits, in one process on the chip:

    python3 benchmarks/chip/calibrate.py --workload <name> \
        --seeds 12 --faults 3 [--first-seed N] [--look]

For each seed the program's first steps (through the session the
benchmark builds, its compiled step reused from seed to seed) and the
reference's; for the first ``--faults`` seeds also the control (the
reference with every matmul computed in int8 with one scale per tensor,
the step below the configuration's bfloat16) and two planted faults, each
compared with the float32 reference: half of the batch left out (the
mean over the rest), and on several chips, where the job syncs gradients,
the exchange left out (chip 0's rows alone).  Each routes its data shards
as the program would (``harness.reference_steps``).  A state left
unchanged reads 1 on ``delta_gap`` by its definition and needs no run;
on several chips each seed's ``replica_gap`` is read too (limit 0: the
replicas must stay equal), and a replica left out of the exchange is a
fault the CPU tests plant.  ``--look`` also traces two steps and
writes what the trace holds.  Writes JSON lines to
``chiprun_out/calibrate/<workload>.jsonl``.  Not part of a benchmark
run.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402


def reset(session, cell, params, seed):
    """Fresh state for ``seed`` in an already built session."""
    import jax
    from repro.launch.steps import broadcast_worker_state
    p = session._place(params(seed), False)
    session._opt_state = session._place(session.optimizer.init(p), False)
    if session.strategy is not None:
        eng = session.strategy.grad_reducer
        session._sync_state = session._place(broadcast_worker_state(
            eng.init_state(p), session.world), True)
    session._params = p
    session.step = 0
    session.losses = []
    session.rng = jax.random.PRNGKey(seed % 2 ** 31)
    session.data = harness.AnnotatedTraffic(cell.traffic(seed))


def compiled_flops(session, cell):
    """The compiled step's operations by ``cost_analysis`` (each loop body
    once) and by ``hlo_flops`` (matmuls, each loop body once per trip),
    beside ``flops.py``'s count, per chip."""
    import jax
    import jax.numpy as jnp
    import hlo_flops
    from flops import train_flops_per_token
    batch = jax.device_put(session.data.batch(0), session._batch_sharding())
    step = jnp.asarray(0, jnp.int32)
    if session.strategy is None:
        low = session._base.lower(session._params, session._opt_state,
                                  batch, step)
    else:
        low = session._sync.lower(session._params, session._opt_state,
                                  session._sync_state, batch, step,
                                  jax.random.PRNGKey(0))
    comp = low.compile()
    ca = comp.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    text = comp.as_text()
    per_chip_tokens = cell.tokens_per_step / cell.chips
    model = train_flops_per_token(cell.config, cell.family,
                                  cell.job["seq_len"]) * per_chip_tokens
    return {"flops_py_per_chip": model,
            "cost_analysis_flops": ca.get("flops"),
            "hlo_matmul_flops": hlo_flops.matmul_flops(text),
            "custom_calls": text.count("tpu_custom_call")}


def look(session, cell, out_dir):
    """Trace two steps; write plane/line names, event counts, the ops
    that took most time, sample event stats, the time ``tracefile.load``
    takes, and the compact trace where it is small enough to keep."""
    import jax
    import tracefile as tr
    from jax.profiler import ProfileData
    tdir = os.path.join(harness.OUT, "look", cell.name)
    with jax.profiler.trace(tdir):
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(2):
                with jax.profiler.TraceAnnotation("bench.step"):
                    session.step_once()
    import glob
    f = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True)[0]
    info = {"xplane_bytes": os.path.getsize(f), "planes": {}}
    t0 = time.time()
    data = ProfileData.from_file(f)
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            cnt, dur, sample = collections.Counter(), collections.Counter(), []
            for e in line.events:
                cnt[e.name] += 1
                dur[e.name] += e.duration_ns
                if len(sample) < 3:
                    sample.append([e.name, [str(s)[:120] for s in e.stats]])
            lines[line.name] = {"n": sum(cnt.values()),
                                "top": [(k, dur[k] / 1e6, cnt[k])
                                        for k, _ in dur.most_common(30)],
                                "sample": sample}
        info["planes"][plane.name] = lines
    info["parse_s"] = time.time() - t0
    t0 = time.time()
    compact = tr.load(tdir)
    info["load_s"] = time.time() - t0
    info["device_ops"] = {p: len(ops) for p, ops in compact.devices.items()}
    if sum(info["device_ops"].values()) <= 100_000:
        tr.save_json(compact,
                     os.path.join(out_dir, f"{cell.name}.trace.json.gz"))
    with open(os.path.join(out_dir, f"{cell.name}.look.json"), "w") as fh:
        json.dump(info, fh, indent=1)
    print("look:", json.dumps({p: {l: v["n"] for l, v in ls.items()}
                               for p, ls in info["planes"].items()}),
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--look", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, harness.SRC)
    harness.place_caches()
    import jax
    import check
    from repro.launch.paths import use_compile_cache
    cell = harness.load_cell(args.workload)
    harness.device_check(cell.chips)
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    out_dir = os.path.join(harness.ROOT, "chiprun_out", "calibrate")
    os.makedirs(out_dir, exist_ok=True)
    out = open(os.path.join(out_dir, f"{cell.name}.jsonl"), "a")

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    t0 = time.time()
    params = harness.Params(cell)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    session = harness.build_session(cell, seeds[0], params)
    prog = harness.first_steps(session, cell, params, seeds[0])
    emit({"event": "built", "s": time.time() - t0})
    emit({"event": "flops", **compiled_flops(session, cell)})
    if args.look:
        look(session, cell, out_dir)
    emit({"event": "memory_peak_bytes", "value": harness.memory_peak()})
    for i, seed in enumerate(seeds):
        t = time.time()
        if i:
            reset(session, cell, params, seed)
            prog = harness.first_steps(session, cell, params, seed)
        session._params = session._opt_state = session._sync_state = None
        t_prog = time.time() - t
        feed = cell.traffic(seed)
        batches = [feed.batch(k) for k in range(cell.job["checked_steps"])]
        t = time.time()
        ref = harness.reference_steps(cell, params, seed, batches)
        rec = {"seed": seed, "prog_s": t_prog, "ref_s": time.time() - t,
               "prog_losses": prog["losses"], "ref_losses": ref["losses"],
               "prog": check.readings(prog, ref),
               "replica_gap": prog.get("replica_gap")}
        if i < args.faults:
            ctrl = harness.reference_steps(cell, params, seed, batches,
                                           lowp=jax.numpy.int8)
            rec["control"] = check.readings(ctrl, ref)
            rows = cell.job["rows_per_chip"] * cell.chips
            half = harness.reference_steps(
                cell, params, seed, batches, rows=slice(0, rows // 2))
            rec["half_batch"] = check.readings(half, ref)
            if cell.chips > 1:
                mine = cell.job["rows_per_chip"]
                alone = harness.reference_steps(
                    cell, params, seed, batches, rows=slice(0, mine))
                rec["no_exchange"] = check.readings(alone, ref)
        rec["names"] = {k: params.names[v[1]] for k, v in rec["prog"].items()
                        if k != "loss_gap"}
        emit(rec)
    emit({"event": "done", "s": time.time() - t0})
    return 0


if __name__ == "__main__":
    sys.exit(main())
