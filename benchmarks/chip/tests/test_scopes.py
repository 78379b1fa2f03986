"""The scope reduction: each device event's scope from its HLO
``op_name``, and each scope's self time, on hand-made events."""
from __future__ import annotations

import run

scopes = run.load_file(run.HERE / "scopes.py")

HLO = """\
ENTRY %main.1 (p: f32[8]) -> f32[8] {
  %while.290 = (s32[], f32[8]{0}) while(%tuple.1), condition=%c, body=%b, metadata={op_name="jit(<lambda>)/while/body/closed_call/while" stack_frame_id=16}
  %_wc_step.9 = f32[8]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(<lambda>)/while/body/doppler.oracle/jit(_makespan_fifo_batch_pallas)/while/body/jit(_wc_step)/pallas_call" stack_frame_id=448}
  %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(<lambda>)/doppler.grad/transpose(doppler.grad)/jvp(doppler.encoder)/mul"}
  ROOT %copy.3 = f32[8]{0} copy(%fusion.7), metadata={op_name="jit(<lambda>)/while/body/doppler.sample/jit(sample_episodes)/doppler.encoder/dot_general"}
  %tuple.1 = (s32[], f32[8]{0}) tuple(%c0, %p)
}
"""


def test_scope_is_the_outermost_doppler_component():
    assert scopes.scope_of("jit(f)/while/body/doppler.sample/jit(g)/"
                           "doppler.encoder/dot") == "doppler.sample"
    assert scopes.scope_of("jit(f)/doppler.grad/transpose(doppler.grad)/"
                           "mul") == "doppler.grad"
    assert scopes.scope_of("jit(f)/while/body/closed_call/add") == ""
    assert scopes.scope_of("") == ""


def test_op_names_from_the_compiled_text():
    names = scopes.hlo_op_names(HLO)
    assert set(names) == {"while.290", "_wc_step.9", "fusion.7", "copy.3",
                          "tuple.1"}
    assert names["tuple.1"] == ""
    assert {k: scopes.scope_of(v) for k, v in names.items()} == {
        "while.290": "", "_wc_step.9": "doppler.oracle",
        "fusion.7": "doppler.grad", "copy.3": "doppler.sample",
        "tuple.1": ""}
    ev = ("%_wc_step.9 = (f32[128,8,128]{2,1,0:T(8,128)S(1)}, "
          "s32[128,128]) custom-call(%a, %b), custom_call_target=\"x\"")
    assert scopes.instruction(ev) == "_wc_step.9"
    assert scopes.instruction("fusion.12") == "fusion.12"


def _scope(name):
    return {"loop": "", "samp": "doppler.sample", "orc": "doppler.oracle",
            "orc_k": "doppler.oracle", "grad": "doppler.grad",
            "copy": None}[name]


def test_self_time_counts_each_instant_once():
    # a loop (unscoped) whose body runs a sample op, then an oracle loop
    # that holds a kernel; a gradient op after the loop ends
    evs = [("loop", 0, 100), ("samp", 10, 30), ("orc", 40, 90),
           ("orc_k", 50, 60), ("orc_k", 70, 80), ("grad", 120, 150)]
    got = scopes.self_ns(evs, _scope, 0, 200)
    assert got == {"": 30, "doppler.sample": 20, "doppler.oracle": 50,
                   "doppler.grad": 30}
    # the scopes add up to the busy time: the loop and its body are not
    # counted twice, and the idle 100-120 and 150-200 go nowhere
    assert sum(got.values()) == 130


def test_self_time_is_clipped_to_the_window():
    evs = [("loop", 0, 100), ("samp", 10, 30), ("orc", 40, 90),
           ("grad", 120, 150)]
    got = scopes.self_ns(evs, _scope, 20, 130)
    assert got == {"doppler.sample": 10, "": 20, "doppler.oracle": 50,
                   "doppler.grad": 10}
    assert scopes.self_ns(evs, _scope, 200, 300) == {}


def test_self_time_of_overlapping_siblings():
    # two ops that overlap without nesting: the later one is innermost
    # while both run, the earlier one has the rest
    got = scopes.self_ns([("samp", 0, 50), ("grad", 30, 80)], _scope, 0, 100)
    assert got == {"doppler.sample": 30, "doppler.grad": 50}
    # an op that ends under a later, longer one is not brought back
    got = scopes.self_ns([("loop", 0, 100), ("samp", 10, 20),
                          ("orc", 15, 60)], _scope, 0, 100)
    assert got == {"": 50, "doppler.sample": 5, "doppler.oracle": 45}


def test_an_op_without_op_name_takes_its_enclosing_scope():
    # a layout copy inside the oracle's loop, one inside the unscoped
    # loop, and one that nothing encloses
    evs = [("loop", 0, 100), ("orc", 10, 60), ("copy", 20, 30),
           ("copy", 70, 80), ("copy", 120, 125)]
    got = scopes.self_ns(evs, _scope, 0, 200)
    assert got == {"": 55, "doppler.oracle": 50}
    # the copy starts as its enclosing op ends: nothing encloses it
    got = scopes.self_ns([("orc", 0, 10), ("copy", 10, 20)], _scope, 0, 20)
    assert got == {"doppler.oracle": 10, "": 10}
