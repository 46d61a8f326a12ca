"""Trace a cell's steps on the chip and say where the time goes, by the
program's own scopes and spans:

    python3 benchmarks/chip/record_trace.py --workload <cell> --seed <n> \
        [--steps 8] [--keep 2]

Set-up as a benchmark run (session, checked steps, warm-up), then traces
``--steps`` steps inside ``bench.window``/``bench.step`` spans and prints
one JSON line: the session's compile counters, the device time by part
(``scopes.split``), the ops with most self time named
``<scope>:<instruction>``, the longest idle gaps named by the innermost
host span (the program's ``repro.*`` spans and the harness's ``bench.*``),
and the mean length of each ``repro.*`` span.  A second trace of
``--keep`` steps is written, with the scope of each of its ops, to the
output directory's ``record/`` as ``trace_<cell>-scoped.json.gz``,
``scopes_<cell>.json.gz`` and ``collectives_<cell>.json.gz`` (its
instructions whose opcode is a collective): the recorded trace the CPU
tests read.
``--hlo`` also writes each compiled step program's text there
(``hlo_<cell>.<program>.txt.gz``).  A step that syncs gradients also gets
its ``grad_sync`` time per step by bucket, collectives apart.
"""
from __future__ import annotations

import argparse
import collections
import glob
import gzip
import json
import os
import re
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402
import tracefile as tr  # noqa: E402

PREFIXES = ("bench.", "repro.")
BUCKET = re.compile(r"bucket_\d+")


def host_spans(trace_dir):
    """Every ``bench.*`` and ``repro.*`` event of the profile's host
    planes: (name, start_ns, end_ns)."""
    from jax.profiler import ProfileData
    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(tr.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    out.append((e.name, int(e.start_ns), int(e.end_ns)))
    return sorted(out, key=lambda s: s[1])


def innermost(spans, t):
    cover = [s for s in spans if s[1] <= t < s[2] and s[0] != "bench.window"]
    return min(cover, key=lambda s: s[2] - s[1])[0] if cover else "host"


def trace_steps(session, n, tdir):
    import jax
    shutil.rmtree(tdir, ignore_errors=True)
    os.makedirs(tdir, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(tdir, profiler_options=options):
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(n):
                with jax.profiler.TraceAnnotation("bench.step"):
                    session.step_once()


def breakdown(session, tdir, steps):
    import scopes
    trace = tr.load(tdir)
    spans = host_spans(tdir)
    plane = tr.busiest(trace)
    lo, hi = tr.window(trace)
    names, collectives = scopes.program_scopes(session) or ({}, set())
    parts = scopes.split(trace, plane, names, collectives)
    per_step = {k: v / steps / 1e6 for k, v in parts.items()}
    tot = collections.Counter()
    inside = [(n, max(s, lo), min(e, hi)) for n, s, e in trace.devices[plane]
              if e > lo and s < hi]
    buckets = collections.Counter()
    for name, t in tr.self_times(inside):
        op = names.get(name.split(":", 1)[-1])
        scope = "/".join(p for p in op.split("/")[1:]) if op else "?"
        tot[f"{scope}:{name}"] += t
        bucket = BUCKET.search(scope)
        if bucket and "grad_sync" in scope:
            kind = ("collective" if name.split(":", 1)[-1] in collectives
                    else "other")
            buckets[f"{bucket.group(0)}:{kind}"] += t
    gaps = tr.subtract([(lo, hi)], tr.busy(trace, plane))
    named = collections.defaultdict(list)
    for s, e in gaps:
        named[innermost(spans, (s + e) // 2)].append((e - s) / 1e6)
    lengths = collections.defaultdict(list)
    for n, s, e in spans:
        if n.startswith("repro."):
            lengths[n].append((e - s) / 1e6)
    return {
        "steps": steps,
        "window_ms": (hi - lo) / 1e6,
        "busy_ms": tr.total(tr.busy(trace, plane)) / 1e6,
        "per_step_ms": per_step,
        "top_ops_ms": [(k, v / 1e6) for k, v in tot.most_common(25)],
        "grad_sync_by_bucket_ms": {k: v / steps / 1e6
                                   for k, v in sorted(buckets.items())},
        "idle_by_span_ms": {k: {"n": len(v), "total": sum(v),
                                "max": max(v)}
                            for k, v in sorted(named.items(),
                                               key=lambda kv: -sum(kv[1]))},
        "longest_gaps_ms": sorted(((innermost(spans, (s + e) // 2),
                                    (e - s) / 1e6) for s, e in gaps),
                                  key=lambda g: -g[1])[:12],
        "repro_span_mean_ms": {k: sum(v) / len(v)
                               for k, v in sorted(lengths.items())},
    }, trace, (names, collectives)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--keep", type=int, default=2)
    ap.add_argument("--hlo", action="store_true")
    args = ap.parse_args(argv)
    t_start = time.time()
    sys.path.insert(0, harness.SRC)
    harness.place_caches()
    import jax
    from repro.launch.paths import use_compile_cache
    cell = harness.load_cell(args.workload)
    harness.device_check(cell.chips)
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = harness.CompileCounter()
    params = harness.Params(cell)
    session = harness.build_session(cell, args.seed, params)
    harness.first_steps(session, cell, params, args.seed)
    harness.warm_up(session, counter)
    out = {"setup_s": time.time() - t_start,
           "compile_s": getattr(session, "compile_s", None),
           "compiles": getattr(session, "compiles", None),
           "cache_hits": getattr(session, "cache_hits", None)}
    tdir = os.path.join(harness.OUT, "record", cell.name)
    trace_steps(session, args.steps, tdir)
    out["breakdown"], _, _ = breakdown(session, tdir, args.steps)
    trace_steps(session, args.keep, tdir)
    _, trace, (names, collectives) = breakdown(session, tdir, args.keep)
    dest = os.path.join(harness.ROOT, "chiprun_out", "record")
    os.makedirs(dest, exist_ok=True)
    tr.save_json(trace, os.path.join(dest,
                                     f"trace_{cell.name}-scoped.json.gz"))
    used = {n.split(":", 1)[-1] for ops in trace.devices.values()
            for n, _, _ in ops}
    with gzip.open(os.path.join(dest, f"scopes_{cell.name}.json.gz"),
                   "wt") as f:
        json.dump({k: v for k, v in names.items() if k in used}, f)
    with gzip.open(os.path.join(dest, f"collectives_{cell.name}.json.gz"),
                   "wt") as f:
        json.dump(sorted(collectives & used), f)
    if args.hlo:
        for name, compiled in getattr(session, "programs", {}).items():
            with gzip.open(os.path.join(
                    dest, f"hlo_{cell.name}.{name}.txt.gz"), "wt") as f:
                f.write(compiled.as_text())
    shutil.rmtree(tdir, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
