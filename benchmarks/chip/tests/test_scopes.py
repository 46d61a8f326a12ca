"""Device time by the program's named scopes (``scopes.py`` and the readers
``fwd_ms``, ``bwd_ms``, ``opt_ms``, ``attn_ms``, ``moe_ms``,
``step_compile_s``), on hand-made HLO and traces with answers worked out by
hand, on a real session's compiled step, and on the recorded traces."""
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
    assert all(parts.values()), parts
    fusions = [l.split(" = ")[0].strip().lstrip("%").replace("ROOT ", "")
               for l in text.splitlines() if " fusion(" in l]
    named = sum(f.lstrip("%") in m for f in fusions)
    assert fusions and named >= 0.85 * len(fusions), (named, len(fusions))


RECORDED = sorted(glob.glob(os.path.join(os.path.dirname(__file__),
                                         "trace_*-scoped.json.gz")))


def _recorded_session(path):
    """A session stand-in whose compiled step is rebuilt from the scope
    map recorded beside the trace (one instruction per line)."""
    name = os.path.basename(path)[len("trace_"):-len("-scoped.json.gz")]
    with gzip.open(os.path.join(os.path.dirname(path),
                                f"scopes_{name}.json.gz"), "rt") as f:
        m = json.load(f)
    text = "ENTRY %main (p: f32[]) -> f32[] {\n" + "".join(
        f'  %{k} = f32[] add(), metadata={{op_name="{v}"}}\n'
        for k, v in m.items()) + "}\n"
    return types.SimpleNamespace(programs={"base": Compiled(text)})


@pytest.mark.parametrize("path", RECORDED,
                         ids=[os.path.basename(p) for p in RECORDED])
def test_recorded_scoped_trace(path):
    """Two steps of the cell on the chip, with its scopes: forward,
    backward and optimizer cover at least 95% of the busy time, attention
    and MoE lie inside forward plus backward, and the backward is longer
    than the forward."""
    t = tr.load_json(path)
    c = ctx(t, _recorded_session(path))
    got = {n: read(n, c) for n in READERS}
    plane = tr.busiest(t)
    busy = tr.total(tr.busy(t, plane)) / 2 / 1e6
    assert got["fwd_ms"] + got["bwd_ms"] + got["opt_ms"] >= 0.95 * busy
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
