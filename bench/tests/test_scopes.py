"""The join of trace events with the program's scopes (bench/scopes.py)."""

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import scopes, trace  # noqa: E402
from bench.trace import Device, Event  # noqa: E402

# A scheduled module cut to what the join reads. Entry ops under each of
# the round's scopes; a scoped loop whose body holds a copy with no
# metadata; a loop XLA made, with no metadata anywhere, over what a scoped
# op produced; copies with no metadata of a scoped result and of a
# parameter; the printer's /*index=N*/ comments.
HLO = """\
HloModule jit_train_steps, is_scheduled=true

%fused_computation.1 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %multiply.9 = f32[4]{0} multiply(%param_0, %param_0), metadata={op_name="jit(train_steps)/deleda.mix/mul"}
}

%body.2 (p.1: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p.1 = (s32[], f32[4]{0}) parameter(0)
  %fusion.3 = f32[4]{0} fusion(%p.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_steps)/while/body/deleda.estep/estep.sweeps/mul"}
  %copy.4 = f32[4]{0} copy(%fusion.3)
  ROOT %tuple.5 = (s32[], f32[4]{0}) tuple(%copy.4)
}

%xla_body.20 (p.2: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p.2 = (s32[], f32[4]{0}) parameter(0)
  %get-tuple-element.21 = f32[4]{0} get-tuple-element(%p.2), index=1
  %dynamic-update-slice.22 = f32[4]{0} dynamic-update-slice(%get-tuple-element.21, %get-tuple-element.21)
  ROOT %tuple.26 = (s32[], f32[4]{0}) tuple(%dynamic-update-slice.22)
}

ENTRY %main.6 (a.1: f32[4]) -> f32[4] {
  %a.1 = f32[4]{0} parameter(0), metadata={op_name="state.stats"}
  %constant.25 = s32[] constant(0)
  %fusion.7 = f32[4]{0} fusion(%a.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_steps)/deleda.mix/mul"}
  %fusion.8 = f32[4]{0} fusion(%fusion.7), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_steps)/deleda.estep/vmap(estep.gather)/add"}
  %while.10 = (s32[], f32[4]{0}) while(%fusion.8), condition=%cond.1, body=%body.2, metadata={op_name="jit(train_steps)/while/body/deleda.estep/estep.sweeps/while"}
  %fusion.11 = f32[4]{0} fusion(%while.10), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_steps)/deleda.blend/mul"}
  %reduce.12 = f32[] reduce(%fusion.11), metadata={op_name="jit(train_steps)/while/body/closed_call/deleda.record/reduce_sum"}
  %copy.13 = f32[4]{0} copy(%a.1)
  %copy.14 = f32[4]{0} copy(%fusion.7)
  %tuple.24 = (s32[], f32[4]{0}) tuple(%constant.25, /*index=1*/%fusion.8)
  %while.23 = (s32[], f32[4]{0}) while(%tuple.24), condition=%cond.2, body=%xla_body.20
  ROOT %copy.27 = f32[4]{0} copy(%fusion.11)
}
"""

SWEEPS = "jit(train_steps)/while/body/deleda.estep/estep.sweeps/while"
GATHER = "jit(train_steps)/deleda.estep/vmap(estep.gather)/add"


def op(name, start, dur):
    """An XLA Ops event named as the TPU names it: the instruction's line."""
    return Event(f"%{name} = f32[4]{{0}} {name.split('.')[0]}(...)", start,
                 dur)


def test_op_names_for_instructions_without_metadata():
    paths = scopes.op_names(HLO)
    assert paths["fusion.7"] == "jit(train_steps)/deleda.mix/mul"
    # the scoped loop that runs its body lends it its path
    assert paths["copy.4"] == SWEEPS
    # a copy of a scoped result is charged to that scope ...
    assert paths["copy.14"] == "jit(train_steps)/deleda.mix/mul"
    # ... and one of a path with no scope stays out of every scope
    assert paths["copy.13"] == ""
    # a loop XLA made: the scope of what it reads, through the loop's
    # parameter and the tuple it was started with
    assert paths["while.23"] == GATHER
    assert paths["dynamic-update-slice.22"] == GATHER
    assert "fused_computation.1" not in paths


def test_scope_seconds_by_hand():
    # device 0: a train_steps execution over [0, 100), one op outside it
    d0 = Device("/device:TPU:0",
                ops=[op("fusion.7", 0, 10), op("fusion.8", 10, 4),
                     op("while.10", 14, 40), op("fusion.3", 14, 20),
                     op("copy.4", 34, 20), op("fusion.11", 54, 6),
                     op("reduce.12", 60, 2), op("copy.13", 62, 8),
                     op("fusion.99", 70, 10), op("copy.14", 80, 3),
                     op("dynamic-update-slice.22", 83, 5),
                     op("fusion.7", 150, 50)],
                modules=[Event("jit_train_steps(42)", 0, 100),
                         Event("jit_other(7)", 150, 50)])
    # device 1: every op twice as long
    d1 = Device("/device:TPU:1",
                ops=[Event(e.name, 2 * e.start_ns, 2 * e.dur_ns)
                     for e in d0.ops],
                modules=[Event(m.name, 2 * m.start_ns, 2 * m.dur_ns)
                         for m in d0.modules])
    s = trace.Summary([d0, d1], [])
    t = scopes.scope_seconds(s, HLO)
    ns = 1.5e-9            # the mean over the two devices of 1 and 2 ns
    per = {k: v / ns for k, v in t.per_scope.items()}
    assert per["deleda.mix"] == pytest.approx(10 + 3)   # not jit_other's
    assert per["estep.gather"] == pytest.approx(4 + 5)
    assert per["estep.sweeps"] == pytest.approx(20 + 20)   # while left out
    assert per["estep.scatter"] == 0
    assert per["deleda.estep"] == pytest.approx(9 + 40)    # inclusive
    assert per["deleda.blend"] == pytest.approx(6)
    assert per["deleda.record"] == pytest.approx(2)
    assert t.unresolved_s / ns == pytest.approx(10)        # fusion.99
    assert [[n, v / ns] for n, v in t.unscoped] == [
        ["copy.13 f32[4]", pytest.approx(8)]]
    assert t.total_s / ns == pytest.approx(13 + 9 + 40 + 6 + 2 + 8 + 10)
    assert t.scoped_s / ns == pytest.approx(13 + 9 + 40 + 6 + 2)


def test_scope_seconds_without_scopes_finds_nothing():
    # the program before it had scopes: every op unscoped, no scope time
    bare = HLO.replace("deleda.", "x_").replace("estep.", "y_")
    d0 = Device("/device:TPU:0", ops=[op("fusion.7", 0, 10)],
                modules=[Event("jit_train_steps(1)", 0, 10)])
    t = scopes.scope_seconds(trace.Summary([d0], []), bare)
    assert not any(t.per_scope.values())
    assert t.scoped_s == 0


def test_scope_seconds_on_a_compiled_program():
    """A scoped program compiled on the CPU: events named after its own
    instruction lines resolve, and land in the scopes they were traced
    under."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def train_steps(x, y):
        with jax.named_scope("deleda.mix"):
            a = jnp.sin(x) * 2.0
        with jax.named_scope("deleda.estep"):
            with jax.named_scope("estep.sweeps"):
                b = jnp.cumsum(a @ y, axis=0)
        return a, b

    x = jnp.ones((8, 8), jnp.float32)
    text = train_steps.lower(x, x).compile().as_text()
    entry = text[text.index("\nENTRY "):].split("\n}", 1)[0]
    lines = [ln.strip().removeprefix("ROOT ") for ln in entry.splitlines()
             if " = " in ln]
    ops = [Event(ln, 10 * i, 10) for i, ln in enumerate(lines)
           if " parameter(" not in ln and " tuple(" not in ln]
    d0 = Device("/device:TPU:0", ops=ops,
                modules=[Event("jit_train_steps(3)", 0, 10 * len(lines))])
    t = scopes.scope_seconds(trace.Summary([d0], []), text)
    assert t.unresolved_s == 0
    assert t.per_scope["deleda.mix"] > 0
    assert t.per_scope["estep.sweeps"] > 0
    assert t.per_scope["deleda.estep"] >= t.per_scope["estep.sweeps"]


def test_train_steps_text_is_the_windows_program():
    """The text the readers join is that of the program the window runs:
    ``train_steps`` lowered at the trainer's own arguments (a tiny cell on
    the CPU) compiles to the same text as at the shapes ``scopes`` builds
    from the configuration."""
    import jax

    from bench import common, control
    from bench.drivers import train_rounds
    from repro.core import deleda
    config, traffic = control.setup("pubmed-k100.train", tiny=True)
    run = common.Run(workload="pubmed-k100.train", seed=2**33 + 7,
                     seconds=1.0, trace=True, config=config, traffic=traffic,
                     devices=jax.devices()[:1], peaks={}, t_process=0.0)
    tr = train_rounds.Trainer(run, *train_rounds.make_inputs(run))
    tr.segment()
    window = deleda.train_steps.lower(
        tr.cfg, tr.state, tr.words, tr.mask, tr.sched[0], tr.corr, tr.live,
        record_every=tr.seg).compile().as_text()
    assert scopes.train_steps_text(config, tr.seg) == window
