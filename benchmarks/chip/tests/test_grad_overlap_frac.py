"""``grad_overlap_frac`` reads the overlap from the session's compiled
``sync`` program, and nothing where the session compiled none."""
import sys
import types

import harness
from metrics import grad_overlap_frac

HLO = """HloModule m, is_scheduled=true

%sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add = f32[] add(%a, %b)
}

%fused (p0: f32[64,64]) -> f32[64,64] {
  %p0 = f32[64,64]{1,0} parameter(0)
  ROOT %t = f32[64,64]{1,0} tanh(%p0)
}

ENTRY %main (g: f32[64,64], x: f32[64,64]) -> (f32[64,64], f32[64,64]) {
  %g = f32[64,64]{1,0} parameter(0)
  %x = f32[64,64]{1,0} parameter(1)
  %all-reduce-start = f32[64,64]{1,0} all-reduce-start(%g), to_apply=%sum, metadata={op_name="jit(step)/grad_sync/bucket_0/psum"}
  %fusion.1 = f32[64,64]{1,0} fusion(%x), kind=kLoop, calls=%fused
  %all-reduce-done = f32[64,64]{1,0} all-reduce-done(%all-reduce-start), metadata={op_name="jit(step)/grad_sync/bucket_0/psum"}
  ROOT %out = (f32[64,64]{1,0}, f32[64,64]{1,0}) tuple(%all-reduce-done, %fusion.1)
}
"""


def _ctx(session):
    return harness.Context(trace=None, cell=None, session=session, peaks={},
                           flops_per_token=0.0, steps=1)


def _program(text):
    return types.SimpleNamespace(as_text=lambda: text)


def test_reads_the_sync_programs_overlap():
    session = types.SimpleNamespace(programs={"sync": _program(HLO)})
    assert grad_overlap_frac.read(_ctx(session)) == 1.0


def test_reads_nothing_without_a_synced_step():
    assert grad_overlap_frac.read(_ctx(types.SimpleNamespace())) is None
    session = types.SimpleNamespace(programs={"base": _program(HLO)})
    assert grad_overlap_frac.read(_ctx(session)) is None


def test_reads_nothing_from_a_program_without_the_reader(monkeypatch):
    """A program whose ``hlo_analysis`` has no ``exchange_overlap`` (the
    commits before it) reads nothing, even with a synced step."""
    monkeypatch.setitem(sys.modules,
                        "repro.launch.hlo_analysis",
                        types.ModuleType("repro.launch.hlo_analysis"))
    session = types.SimpleNamespace(programs={"sync": _program(HLO)})
    assert grad_overlap_frac.read(_ctx(session)) is None
