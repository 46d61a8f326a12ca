"""Paper Fig. 10-12 + §4.1 — allreduce algorithm comparison.

(a) the alpha-beta cost model across p and message size (ring vs tree/PS vs
hierarchical vs 2D-mesh — Tables/figures 10-12's shapes), (b) MEASURED
wall times of our ppermute implementations on an 8-device CPU host mesh,
run in a ``JAX_PLATFORMS=cpu`` subprocess so this process keeps its own
devices (rows ``fig10/measured_8dev_cpu/*``: CPU times, not device
metrics), and (c) the
PER-BUCKET {compress, permute, decompress} breakdown of the fused
compressed wires (DESIGN.md §11) — fused one-pass kernels vs the
decomposed op chain, per wire × bucket size.

Standalone invocation can additionally record the measured compression
cost table the planner consumes (``plan_auto(compression_costs=...)``)::

    PYTHONPATH=src python -m benchmarks.bench_collectives \
        --write-compression-costs artifacts/compression_costs.json
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time

from benchmarks.common import LINK_PRESETS, emit
from repro.core.collectives import allreduce_cost_s

MEASURE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import time
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, AxisType
from repro.core.collectives import allreduce

def median_us(f, *args):
    jax.block_until_ready(f(*args))
    ts = []
    for _ in range(5):
        t0 = time.perf_counter(); jax.block_until_ready(f(*args))
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[2] * 1e6

mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
x = jax.random.normal(jax.random.PRNGKey(0), (8, 1 << 20))
for algo in ("psum", "ring", "tree", "hierarchical", "ring_fused"):
    f = jax.jit(jax.shard_map(lambda v: allreduce(v, algo, ("data",)),
                mesh=mesh, in_specs=P("data", None), out_specs=P(None),
                axis_names={"data"}, check_vma=False))
    print(f"MEASURED,{algo},{median_us(f, x):.1f}")
# the fused int8 gather wire's permute phase: all-gather of the (q int8,
# per-tile f32 scales) payload — the wire grad_sync actually moves for the
# int8_fused gather pattern (a quarter of the dense bytes + scales)
q = jnp.zeros((8, 1 << 20), jnp.int8)
sc = jnp.ones((8, (1 << 20) // 1024), jnp.float32)
g = jax.jit(jax.shard_map(
    lambda a, b: (jax.lax.all_gather(a, "data"),
                  jax.lax.all_gather(b, "data")),
    mesh=mesh, in_specs=(P("data", None), P("data", None)),
    out_specs=(P(None), P(None)), axis_names={"data"}, check_vma=False))
print(f"MEASURED,gather_int8_payload,{median_us(g, q, sc):.1f}")
"""

# Bucket sizes of the kernel breakdown (f32 elements): 1 MiB shows the
# cache-resident regime (below the LLC the decomposed chain's extra
# passes are nearly free on CPU backends and can even win — the off-TPU
# gap DESIGN.md §11 documents); 32 MiB is the planner's DEFAULT bucket
# size, above the LLC, where one-pass fusion wins on every backend and
# scripts/bench_ci.py gates the ratio.
KERNEL_SIZES = ((1 << 18, "1MiB"), (1 << 23, "32MiB"))
KERNEL_WORLD = 8


def _best_us(fn, *args, repeats: int = 5) -> float:
    import jax
    jax.block_until_ready(fn(*args))          # compile / warm
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def fused_wire_breakdown():
    """Rows ``fig10/kernels/<wire>/<size>/<stage>``: fused one-pass kernels
    vs the decomposed chain (one jitted op per stage, every intermediate
    materialized — the multi-pass HBM traffic the fusion removes)."""
    import jax
    import jax.numpy as jnp

    from repro.core.compression import get_compressor
    from repro.kernels import ops
    from repro.kernels import ref as kref

    tile = ops.TILE
    add = jax.jit(jnp.add)
    sub = jax.jit(jnp.subtract)
    quant = jax.jit(lambda c: kref.quantize_tiles_ref(c, tile=tile))
    deq = jax.jit(lambda q, s: kref.dequantize_ref(q, s, tile=tile))
    mask = jax.jit(lambda c: kref.topk_mask_bisect_ref(c, ratio=0.01,
                                                       tile=tile, iters=16))
    i8 = get_compressor("int8_fused")
    tk = get_compressor("topk_fused")
    f_enc_i8 = jax.jit(lambda g, e: i8.fused_ef_compress(g, e, 1.0))
    f_enc_tk = jax.jit(lambda g, e: tk.fused_ef_compress(g, e, 1.0))

    for n, tag in KERNEL_SIZES:
        g = jax.random.normal(jax.random.PRNGKey(0), (n,), jnp.float32)
        e = jnp.zeros_like(g)

        def unfused_enc_i8(g, e):
            c = add(g, e)
            q, s = quant(c)
            return q, s, sub(c, deq(q, s))

        def unfused_enc_tk(g, e):
            c = add(g, e)
            y = mask(c)
            return y, sub(c, y)

        fu = _best_us(f_enc_i8, g, e)
        uu = _best_us(unfused_enc_i8, g, e)
        emit(f"fig10/kernels/int8_fused/{tag}/compress", fu,
             f"one-pass quantize+pack+EF; decomposed {uu:.1f}us "
             f"(x{uu / fu:.2f})")
        fu = _best_us(f_enc_tk, g, e)
        uu = _best_us(unfused_enc_tk, g, e)
        emit(f"fig10/kernels/topk_fused/{tag}/compress", fu,
             f"one-pass bisect-topk+EF; decomposed {uu:.1f}us "
             f"(x{uu / fu:.2f})")

        (q1, s1), meta, _ = i8.fused_ef_compress(g, e, 1.0)
        qg = jnp.stack([q1] * KERNEL_WORLD)
        sg = jnp.stack([s1] * KERNEL_WORLD)
        f_dec = jax.jit(lambda q, s: i8.fused_decode_sum((q, s), meta))

        def unfused_dec(q, s):
            acc = jnp.zeros((n,), jnp.float32)
            for w in range(KERNEL_WORLD):
                acc = add(acc, deq(q[w], s[w]))
            return acc

        fu = _best_us(f_dec, qg, sg)
        uu = _best_us(unfused_dec, qg, sg)
        emit(f"fig10/kernels/int8_fused/{tag}/decompress", fu,
             f"one-pass dequant+accum x{KERNEL_WORLD} payloads; "
             f"decomposed {uu:.1f}us (x{uu / fu:.2f})")


def run():
    link = LINK_PRESETS["fast_ici"]
    for p in (16, 256, 512):
        for nbytes, tag in ((1e4, "10KB"), (1e8, "100MB")):
            for algo in ("ring", "tree", "hierarchical", "mesh2d",
                         "mesh2d_split"):
                t = allreduce_cost_s(algo, nbytes, p, link)
                emit(f"fig10/{algo}/p{p}/{tag}", t * 1e6,
                     f"alpha-beta model")
    fused_wire_breakdown()
    # 8 fake CPU devices in a child: this process may hold the chip, and a
    # child that reached for it would fail or hang
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    res = subprocess.run([sys.executable, "-c", MEASURE_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    if res.returncode:
        raise RuntimeError(f"8-device CPU measurement failed "
                           f"(rc {res.returncode}):\n{res.stderr[-2000:]}")
    for line in res.stdout.splitlines():
        if line.startswith("MEASURED,"):
            _, algo, us = line.split(",")
            what = ("int8+scales payload permute" if algo ==
                    "gather_int8_payload" else "4MiB allreduce")
            emit(f"fig10/measured_8dev_cpu/{algo}", float(us),
                 f"{what}, 8 host CPU devices")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--write-compression-costs", default="", metavar="PATH",
                    help="measure per-compressor encode/decode fits "
                         "(schedule/calibration.py) and record the cost "
                         "table the planner consumes "
                         "(train --compression-costs PATH)")
    args = ap.parse_args(argv)
    print("name,us_per_call,derived")
    run()
    if args.write_compression_costs:
        from repro.core.schedule import measure_compression_costs
        table = measure_compression_costs()
        os.makedirs(os.path.dirname(
            os.path.abspath(args.write_compression_costs)), exist_ok=True)
        table.save(args.write_compression_costs)
        print(f"compression cost table written: "
              f"{args.write_compression_costs} "
              f"({len(table.entries)} stage fits)", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
