"""The measurement inside the program: named scopes in the step programs,
the session's ``repro.*`` spans on the profiler's clock, and its counters
(MoE capacity counts from the device, compiles and cache loads).

The scopes name every op of a compiled step (HLO ``op_name`` metadata),
which is what lets a device trace split the step by part: ``forward``
(backward ops under JAX's ``transpose(jvp(...))``), ``optimizer``,
``attn/<mixer>``, ``moe``, ``mlp``, ``embed``, ``head`` and
``grad_sync/bucket_<i>``.
"""
import json
import os
import re
import subprocess
import sys

import jax
import pytest

from repro.api import SessionConfig, TrainSession
from repro.core import SyncConfig, make_strategy

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _session(arch="deepseek-v2-lite-16b", strategy=None, **kw):
    cfg = dict(arch=arch, reduced=True, batch=2, seq=16, steps=4)
    cfg.update(kw)
    return TrainSession(SessionConfig(**cfg), strategy=strategy)


def _op_names(text):
    return set(re.findall(r'op_name="([^"]*)"', text))


def _scopes(op_name):
    """The scope names on an op's name stack, with JAX's transform
    wrappers taken off: ``jvp(embed)`` and ``transpose(jvp(head))`` both
    give their inner name."""
    out = set()
    for part in op_name.split("/"):
        while re.fullmatch(r"[\w.-]+\(.*\)", part):
            part = part[part.index("(") + 1:-1]
        out.add(part)
    return out


def test_step_program_names_its_parts():
    s = _session()
    s.step_once()
    names = _op_names(s.programs["base"].as_text())
    scoped = [_scopes(n) for n in names]
    for scope in ("forward", "optimizer", "attn", "mla", "moe", "mlp",
                  "embed", "head", "router", "dispatch", "experts",
                  "combine"):
        assert any(scope in sc for sc in scoped), scope
    backward = [n for n in names if "transpose(" in n]
    assert backward and all("forward" in _scopes(n) for n in backward)
    # the backward of each layer part keeps its scope
    for scope in ("attn", "moe", "embed", "head"):
        assert any(scope in _scopes(n) for n in backward), scope
    assert not any("optimizer" in _scopes(n) for n in backward)


BUCKETS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import re
from repro.api import SessionConfig, TrainSession
from repro.core import SyncConfig, make_strategy
s = TrainSession(SessionConfig(arch="gemma-2b", reduced=True, batch=4,
                               seq=16, steps=4, data_parallel=4),
                 strategy=make_strategy("every_step", sync=SyncConfig(
                     compressor="int8", algo="ring", bucket_bytes=1 << 16)))
s.step_once()
text = s.programs["sync"].as_text()
found = sorted({int(m) for m in re.findall(r"grad_sync/bucket_(\d+)/",
                                            text)})
plan = s.strategy.grad_reducer._exec_for(s.params).plan
print("BUCKETS", len(plan.buckets), found)
"""


def test_grad_sync_bucket_scopes_in_synced_step():
    """Each bucket the executor exchanges names its ops
    ``grad_sync/bucket_<i>`` in a synced step over 4 devices."""
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "-c", BUCKETS_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    line = [l for l in res.stdout.splitlines() if l.startswith("BUCKETS")]
    n, found = line[0].split(" ", 2)[1:]
    n = int(n)
    assert n > 1
    assert json.loads(found) == list(range(n))


def _spans(trace_dir):
    """Every ``repro.*`` event on the host planes of a profile:
    (name, start_ns, end_ns, {stat: str})."""
    import glob
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    out.append((e.name, int(e.start_ns), int(e.end_ns),
                                {str(k): str(v) for k, v in e.stats}))
    return out


def test_session_spans_on_the_profiler_clock(tmp_path):
    """Two steps under the profiler: one ``repro.step`` per step (a step
    annotation with its ``step_num``) holding ``repro.input``,
    ``repro.dispatch``, ``repro.loss_wait`` and ``repro.counters`` with
    its ``step``; the first step also holds ``repro.build``."""
    s = _session()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=options):
        s.step_once()
        s.step_once()
    spans = _spans(str(tmp_path))
    steps = sorted((sp for sp in spans if sp[0] == "repro.step"),
                   key=lambda sp: sp[1])
    assert [sp[3]["step_num"] for sp in steps] == ["0", "1"]
    children = ("repro.input", "repro.dispatch", "repro.loss_wait",
                "repro.counters")
    for n, (_, lo, hi, _) in enumerate(steps):
        inside = [sp for sp in spans if lo <= sp[1] and sp[2] <= hi
                  and sp[0] != "repro.step"]
        for name in children:
            mine = [sp for sp in inside if sp[0] == name]
            assert len(mine) == 1, (n, name, inside)
            assert mine[0][3]["step"] == str(n)
        order = sorted((sp for sp in inside if sp[0] in children),
                       key=lambda sp: sp[1])
        assert [sp[0] for sp in order] == list(children)
        builds = [sp for sp in inside if sp[0] == "repro.build"]
        assert len(builds) == (1 if n == 0 else 0)
    assert steps[0][2] <= steps[1][1]


@pytest.mark.parametrize("scheduler,kw,programs", [
    (None, {}, {"base"}),
    ("every_step", {}, {"sync"}),
    ("local_sgd", {"period": 2}, {"local", "param_round"}),
    ("lag", {"threshold": 1e9}, {"probe", "sync", "reuse"}),
], ids=["vanilla", "every_step", "local_sgd", "lag"])
def test_no_host_callback_in_moe_step_programs(scheduler, kw, programs):
    """The MoE counters come back with the loss: no step program of an MoE
    session crosses to the host in the middle of the step."""
    strategy = None if scheduler is None else make_strategy(
        scheduler, sync=SyncConfig(), **kw)
    s = _session(strategy=strategy)
    for _ in range(3):
        s.step_once()
    assert set(s.programs) == programs
    for name, compiled in s.programs.items():
        text = compiled.as_text()
        assert not re.search(r'custom_call_target="[^"]*callback', text), \
            name
    assert s.routed_tokens > 0


def test_pipeline_step_returns_the_moe_counts():
    """The 1F1B executor carries the counters with the stage payload: an
    MoE session micro-batched through it routes every token choice of
    every MoE layer once a step, as the vanilla step does."""
    strategy = make_strategy("every_step", sync=SyncConfig(),
                             micro_batches=2)
    s = _session("qwen3-moe-30b-a3b", strategy=strategy, batch=4)
    s.step_once()
    s.step_once()
    mc = s.model_cfg
    n_moe = sum(mc.layer_spec(i).ffn == "moe" for i in range(mc.num_layers))
    assert n_moe and s.routed_tokens == 2 * 4 * 16 * mc.top_k * n_moe
    assert 0 <= s.dropped_tokens < s.routed_tokens
    text = s.programs["sync"].as_text()
    assert not re.search(r'custom_call_target="[^"]*callback', text)


CACHE_SCRIPT = r"""
import json
import jax
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
from repro.api import SessionConfig, TrainSession
s = TrainSession(SessionConfig(arch="deepseek-v2-lite-16b", reduced=True,
                               batch=2, seq=16, steps=4))
s.step_once()
first = (s.compiles, s.cache_hits, s.compile_s)
s.step_once()
print("COUNTS", json.dumps({"first": first, "second": (
    s.compiles, s.cache_hits, s.compile_s)}))
"""


def test_second_process_loads_the_moe_step_from_the_cache(tmp_path):
    """The session counts its step programs' compiles and cache loads; a
    second process on the same cache directory loads the MoE step (which
    a host callback used to keep out of the persistent cache)."""
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))

    def run():
        res = subprocess.run([sys.executable, "-c", CACHE_SCRIPT], env=env,
                             capture_output=True, text=True, timeout=600)
        assert res.returncode == 0, res.stderr[-3000:]
        line = [l for l in res.stdout.splitlines()
                if l.startswith("COUNTS")][0]
        return json.loads(line.split(" ", 1)[1])

    cold, warm = run(), run()
    assert cold["first"][:2] == [1, 0] and cold["first"][2] > 0
    assert warm["first"][:2] == [0, 1]
    # a warm step adds nothing
    assert cold["second"] == cold["first"]
    assert warm["second"] == warm["first"]
