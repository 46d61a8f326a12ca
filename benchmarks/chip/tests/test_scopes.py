"""Device time by the program's named scopes (``scopes.py`` and the readers
``fwd_ms``, ``bwd_ms``, ``opt_ms``, ``attn_ms``, ``moe_ms``,
``step_compile_s``, ``grad_sync_ms``, ``collective_exposed_ms``), on
hand-made HLO and traces with answers worked out by hand, on real sessions'
compiled steps, and on the recorded traces."""
import glob
import gzip
import importlib
import json
import os
import types

import pytest

import scopes
import tracefile as tr

DEV = "/device:TPU:0"
READERS = ("fwd_ms", "bwd_ms", "opt_ms", "attn_ms", "moe_ms")

# A step program in the form the TPU compiler prints it: a fusion whose
# metadata is on its fused computation's root only, a while loop whose body
# holds a copy with no metadata of its own, a Pallas kernel, and a scatter
# fusion a compiler pass made, whose own op_name is bare ("scatter") and
# whose scope lies in its combiner.
HLO = """\
HloModule jit_train_step, is_scheduled=true

%fused_computation.1 (param_0: bf16[8,8]) -> bf16[8,8] {
  %param_0 = bf16[8,8]{1,0} parameter(0)
  ROOT %tanh.2 = bf16[8,8]{1,0} tanh(%param_0), metadata={op_name="jit(train_step)/forward/jvp()/while/body/closed_call/attn/mla/tanh" stack_frame_id=3}
}

%region.13 (a: bf16[], b: bf16[]) -> bf16[] {
  %a = bf16[] parameter(0), metadata={op_name="scatter-add"}
  %b = bf16[] parameter(1)
  ROOT %add.14 = bf16[] add(%a, %b), metadata={op_name="jit(train_step)/forward/jvp(moe)/dispatch/vmap()/add"}
}

%fused_computation.15 (param_0: bf16[8,8], param_1: s32[8]) -> bf16[8,8] {
  %param_0 = bf16[8,8]{1,0} parameter(0)
  %param_1 = s32[8]{0} parameter(1)
  ROOT %scatter.16 = bf16[8,8]{1,0} scatter(%param_0, %param_1, %param_0), to_apply=%region.13, metadata={op_name="scatter"}
}

%body.3 (arg: (s32[], bf16[8,8])) -> (s32[], bf16[8,8]) {
  %arg = (s32[], bf16[8,8]{1,0}) parameter(0)
  %copy.4 = bf16[8,8]{1,0} copy(%arg)
  %fusion.5 = bf16[8,8]{1,0} fusion(%copy.4), kind=kLoop, calls=%fused_computation.1
  ROOT %t = (s32[], bf16[8,8]{1,0}) tuple(%fusion.5)
}

%cond.6 (arg: (s32[], bf16[8,8])) -> pred[] {
  %arg.1 = (s32[], bf16[8,8]{1,0}) parameter(0)
  ROOT %lt = pred[] compare(%arg.1, %arg.1), direction=LT
}

ENTRY %main.7 (p: (s32[], bf16[8,8])) -> bf16[8,8] {
  %p = (s32[], bf16[8,8]{1,0}) parameter(0)
  %while.8 = (s32[], bf16[8,8]{1,0}) while(%p), condition=%cond.6, body=%body.3, metadata={op_name="jit(train_step)/forward/jvp()/while"}
  %fusion.9 = bf16[8,8]{1,0} fusion(%while.8), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(train_step)/forward/transpose(jvp())/closed_call/moe/experts/dot_general"}
  %fusion.10 = bf16[8,8]{1,0} fusion(%fusion.9), kind=kLoop, metadata={op_name="jit(train_step)/forward/transpose(jvp(embed))/scatter-add"}
  %custom-call.11 = bf16[8,8]{1,0} custom-call(%fusion.10), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/optimizer/mul"}
  %copy.12 = bf16[8,8]{1,0} copy(%custom-call.11)
  ROOT %fusion.17 = bf16[8,8]{1,0} fusion(%copy.12, %p), kind=kCustom, calls=%fused_computation.15, metadata={op_name="scatter"}
}
"""

# two steps: the while (0-100) holds copy.4 (10-20) and fusion.5 (20-60);
# then fusion.9 (100-160), fusion.10 (160-170), the kernel (170-190),
# copy.12 (190-200), which no scope covers, and the scatter fusion.17
# (200-230); step 2 repeats it at +300
ONE = [("while.8", 0, 100), ("copy.4", 10, 20), ("fusion.5", 20, 60),
       ("fusion.9", 100, 160), ("fusion.10", 160, 170),
       ("tpu_custom_call:custom-call.11", 170, 190), ("copy.12", 190, 200),
       ("fusion.17", 200, 230)]
HAND = tr.Trace(
    {DEV: [(n, s + k, e + k) for k in (1000, 1300) for n, s, e in ONE]},
    [("bench.window", 990, 1540), ("bench.step", 995, 1250),
     ("bench.step", 1290, 1535)])


class Compiled:
    def __init__(self, text):
        self.text = text

    def as_text(self):
        return self.text


def ctx(trace, session, steps=2):
    return types.SimpleNamespace(trace=trace, session=session, steps=steps)


def read(name, c):
    return importlib.import_module(f"metrics.{name}").read(c)


def test_scope_map_joins_instructions_to_their_scopes():
    m = scopes.scope_map(HLO)
    attn = "jit(train_step)/forward/jvp()/while/body/closed_call/attn/mla/tanh"
    assert m["fusion.5"] == attn              # its fused root's metadata
    assert m["copy.4"] == "jit(train_step)/forward/jvp()/while"  # the loop's
    assert m["while.8"] == "jit(train_step)/forward/jvp()/while"
    assert m["custom-call.11"] == "jit(train_step)/optimizer/mul"
    assert "copy.12" not in m                 # nothing names it
    assert m["fusion.17"] == (                # its combiner's, not "scatter"
        "jit(train_step)/forward/jvp(moe)/dispatch/vmap()/add")
    assert scopes.scope_names(m["fusion.10"]) >= {"forward", "embed"}
    assert scopes.scope_names(
        "jit(f)/forward/transpose(jvp(head))/mul") >= {"forward", "head"}


def test_readers_on_a_hand_made_trace():
    c = ctx(HAND, types.SimpleNamespace(programs={"base": Compiled(HLO)},
                                        compile_s=12.5))
    # per step: forward = while self 50 + copy 10 + fusion.5 40 +
    # fusion.17 30 = 130 ns; backward = fusion.9 60 + fusion.10 10 = 70;
    # optimizer = kernel 20; attn = fusion.5 40; moe = fusion.9 60 +
    # fusion.17 30 = 90; copy.12's 10 ns is unscoped
    want = {"fwd_ms": 130e-6, "bwd_ms": 70e-6, "opt_ms": 20e-6,
            "attn_ms": 40e-6, "moe_ms": 90e-6}
    for name, v in want.items():
        assert read(name, c) == pytest.approx(v), name
    got = scopes.split(HAND, DEV, scopes.scope_map(HLO))
    assert got["busy"] == 2 * 230 and got["unscoped"] == 2 * 10
    assert read("step_compile_s", c) == 12.5


def test_readers_give_nothing_without_scopes():
    """A program that keeps no compiled steps, or whose ops carry no
    scopes, gives no reading: the readers return None and do not raise."""
    bare = HLO.replace("/forward", "").replace("/optimizer", "")
    for session in (types.SimpleNamespace(),
                    types.SimpleNamespace(programs={}),
                    types.SimpleNamespace(programs={"b": Compiled(bare)})):
        c = ctx(tr.Trace(dict(HAND.devices), list(HAND.spans)), session)
        for name in READERS + ("step_compile_s",):
            assert read(name, c) is None, name


def test_a_real_step_program_is_covered_by_its_scopes():
    """The compiled step of a tiny DeepSeek session: every part has ops,
    and the scope map names all but a few of its fusions (on the CPU the
    compiler adds float32 converts of the bfloat16 parameters that carry no
    metadata: 50 of 412 fusions here)."""
    import harness
    import tiny
    cell = tiny.cell(tiny.DEEPSEEK, tiny.job("train-4k", rows_per_chip=2,
                                             seq_len=32))
    params = harness.Params(cell)
    session = harness.build_session(cell, 7, params)
    session.step_once()
    text = session.programs["base"].as_text()
    m = scopes.scope_map(text)
    parts = {p: 0 for p in scopes.PARTS}
    for op in m.values():
        for p, test in scopes.PARTS.items():
            parts[p] += test(op, scopes.scope_names(op))
    # one device syncs no gradient: every part but grad_sync has ops
    assert parts.pop("grad_sync") == 0 and all(parts.values()), parts
    fusions = [l.split(" = ")[0].strip().lstrip("%").replace("ROOT ", "")
               for l in text.splitlines() if " fusion(" in l]
    named = sum(f.lstrip("%") in m for f in fusions)
    assert fusions and named >= 0.85 * len(fusions), (named, len(fusions))


# A synced step: a backward fusion, then the gradient exchange under
# grad_sync (a synchronous all-reduce that the compiler named after its
# JAX primitive, an async all-gather's start and done,
# a fusion that divides by the world size), the loss's pmean, which is a
# collective outside grad_sync, and the optimizer.
SYNC_HLO = """\
HloModule jit_step_fn, is_scheduled=true

ENTRY %main.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(step_fn)/shard_map/forward/transpose(jvp())/mul"}
  %psum.3 = f32[8]{0} all-reduce(%fusion.2), to_apply=%sum, metadata={op_name="jit(step_fn)/shard_map/grad_sync/bucket_0/psum"}
  %all-gather-start.4 = s8[4,8]{1,0} all-gather-start(%p), metadata={op_name="jit(step_fn)/shard_map/grad_sync/bucket_1/all_gather"}
  %all-gather-done.5 = s8[4,8]{1,0} all-gather-done(%all-gather-start.4), metadata={op_name="jit(step_fn)/shard_map/grad_sync/bucket_1/all_gather"}
  %fusion.6 = f32[8]{0} fusion(%psum.3), kind=kLoop, metadata={op_name="jit(step_fn)/shard_map/grad_sync/bucket_0/div"}
  %all-reduce.7 = f32[] all-reduce(%p), to_apply=%sum, metadata={op_name="jit(step_fn)/shard_map/pmean"}
  ROOT %fusion.8 = f32[8]{0} fusion(%fusion.6), kind=kLoop, metadata={op_name="jit(step_fn)/shard_map/optimizer/sub"}
}
"""
SYNC_ONE = [("fusion.2", 0, 40), ("psum.3", 40, 70),
            ("all-gather-start.4", 70, 72), ("all-gather-done.5", 72, 80),
            ("fusion.6", 80, 85), ("all-reduce.7", 85, 90),
            ("fusion.8", 90, 100)]
SYNC_TRACE = tr.Trace(
    {DEV: [(n, s + k, e + k) for k in (1000, 1300) for n, s, e in SYNC_ONE]},
    [("bench.window", 990, 1410), ("bench.step", 995, 1200),
     ("bench.step", 1290, 1405)])


def test_grad_sync_readers_on_a_hand_made_trace():
    """Per step: grad_sync = psum 30 + all-gather start 2 and done 8
    + the division's fusion 5 = 45 ns, of which the collectives are 40; the
    loss's pmean (5 ns) is no gradient exchange.  A step without grad_sync
    gives neither reading."""
    c = ctx(SYNC_TRACE, types.SimpleNamespace(
        programs={"sync": Compiled(SYNC_HLO)}))
    assert read("grad_sync_ms", c) == pytest.approx(45e-6)
    assert read("collective_exposed_ms", c) == pytest.approx(40e-6)
    assert read("bwd_ms", c) == pytest.approx(40e-6)
    plain = ctx(tr.Trace(dict(HAND.devices), list(HAND.spans)),
                types.SimpleNamespace(programs={"base": Compiled(HLO)}))
    assert read("grad_sync_ms", plain) is None
    assert read("collective_exposed_ms", plain) is None


def test_a_real_synced_step_names_its_exchange():
    """The compiled step of a tiny DeepSeek session on four devices with a
    psum sync: its all-reduces of the gradients lie under grad_sync."""
    import harness
    import tiny
    sync = {"scheduler": "every_step", "config": {
        "compressor": "none", "algo": "psum", "error_feedback": False}}
    cell = tiny.cell(tiny.DEEPSEEK, tiny.job(
        "train-4k", chips=4, rows_per_chip=2, seq_len=32, sync=sync))
    session = harness.build_session(cell, 7, harness.Params(cell))
    session.step_once()
    text = session.programs["sync"].as_text()
    m = scopes.scope_map(text)
    synced = [i for i in scopes.collective_names(text)
              if "grad_sync" in scopes.scope_names(m.get(i, ""))]
    assert synced, sorted(scopes.collective_names(text))


RECORDED = sorted(glob.glob(os.path.join(os.path.dirname(__file__),
                                         "trace_*-scoped.json.gz")))


def _recorded_session(path):
    """A session stand-in whose compiled step is rebuilt from the scope
    map recorded beside the trace (one instruction per line), with the
    opcode ``all-reduce`` for the instructions listed as collectives."""
    name = os.path.basename(path)[len("trace_"):-len("-scoped.json.gz")]
    here = os.path.dirname(path)
    with gzip.open(os.path.join(here, f"scopes_{name}.json.gz"), "rt") as f:
        m = json.load(f)
    collectives = set()
    if os.path.exists(os.path.join(here, f"collectives_{name}.json.gz")):
        with gzip.open(os.path.join(here, f"collectives_{name}.json.gz"),
                       "rt") as f:
            collectives = set(json.load(f))
    text = "ENTRY %main (p: f32[]) -> f32[] {\n" + "".join(
        f'  %{k} = f32[] {"all-reduce" if k in collectives else "add"}(), '
        f'metadata={{op_name="{v}"}}\n' for k, v in m.items()) + "}\n"
    return types.SimpleNamespace(programs={"base": Compiled(text)})


@pytest.mark.parametrize("path", RECORDED,
                         ids=[os.path.basename(p) for p in RECORDED])
def test_recorded_scoped_trace(path):
    """Two steps of the cell on the chip, with its scopes: forward,
    backward, optimizer and gradient sync cover at least 95% of the busy
    time, attention and MoE lie inside forward plus backward, and the
    backward is longer than the forward."""
    t = tr.load_json(path)
    c = ctx(t, _recorded_session(path))
    got = {n: read(n, c) for n in READERS + ("grad_sync_ms",)}
    plane = tr.busiest(t)
    busy = tr.total(tr.busy(t, plane)) / 2 / 1e6
    assert got["fwd_ms"] + got["bwd_ms"] + got["opt_ms"] + (
        got["grad_sync_ms"] or 0.0) >= 0.95 * busy
    assert got["attn_ms"] + got["moe_ms"] < got["fwd_ms"] + got["bwd_ms"]
    assert got["bwd_ms"] > got["fwd_ms"] > 0 and got["opt_ms"] > 0


# what the readings of the first recorded trace (no scopes) were when it
# was recorded, before the program had any: tracefile and these readers
# are unchanged, so the readings are too
OLD = os.path.join(os.path.dirname(__file__),
                   "trace_deepseek-v2-lite-2l.json.gz")


def test_old_recorded_trace_reads_as_before():
    t = tr.load_json(OLD)
    c = types.SimpleNamespace(
        trace=t, steps=2, session=types.SimpleNamespace(),
        cell=types.SimpleNamespace(chips=1, tokens_per_step=8192),
        flops_per_token=1.425e9, peaks={"bf16_flops_per_s": 197e12})
    assert read("host_gap_ms", c) == pytest.approx(4.139381)
    assert read("device_idle_frac", c) == pytest.approx(
        1 - 455326142 / 481366516)
    # the two steps' first ops start 240,381,156 ns apart
    assert read("step_mfu", c) == pytest.approx(
        100 * 1.425e9 * 8192 / 0.240381156 / 197e12)
    for name in READERS + ("step_compile_s",):
        assert read(name, c) is None


DP4 = os.path.join(os.path.dirname(__file__),
                   "trace_deepseek-v2-lite-2l.dp4-psum.4chip-scoped.json.gz")


def test_recorded_four_chip_trace_reads_its_exchange():
    """Two steps of the four-chip psum cell on TPU v5 lite chips (seed
    3415100003).  The collectives listed beside it are the trace's
    instructions whose opcode is ``all-reduce`` in the compiled step (of
    the same program compiled for a described v5e:2x2: every scoped
    instruction of the trace has the same name and scope there); five of
    them are named ``psum.<n>``.  The exchange is all of the gradient
    sync's time (its converts and the division fuse into the backward and
    the optimizer), at most the busy step; the readings are those of the
    recording."""
    t = tr.load_json(DP4)
    c = ctx(t, _recorded_session(DP4))
    sync, exposed = read("grad_sync_ms", c), read("collective_exposed_ms", c)
    busy = tr.total(tr.busy(t, tr.busiest(t))) / 2 / 1e6
    assert exposed <= sync <= busy
    assert sync == pytest.approx(54.090723)
    assert exposed == pytest.approx(54.090723)
    assert busy == pytest.approx(242.765954)
    assert len(t.devices) == 4
