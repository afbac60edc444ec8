"""The scope classification and the leaf-time reduction on fixed op names
and a fixed event list."""
import pytest

from chipbench import scopes
from chipbench.trace import Event, Span

J = "jit(step_fn)/jit(main)"


@pytest.mark.parametrize("name, cls", [
    # the forward, backward and recomputed forms of one layer's matmul
    (f"{J}/jvp(blocks)/while/body/closed_call/dot_general", "forward"),
    (f"{J}/jvp()/while/body/closed_call/blocks/dot_general", "forward"),
    (f"{J}/transpose(jvp(blocks))/while/body/closed_call/checkpoint/"
     "dot_general", "backward"),
    (f"{J}/transpose(jvp(blocks))/while/body/closed_call/checkpoint/"
     "rematted_computation/dot_general", "recompute"),
    (f"{J}/transpose(jvp())/while/body/checkpoint/rematted_computation/"
     "blocks/dot_general", "recompute"),
    (f"{J}/jvp(embed)/gather", "forward"),
    (f"{J}/transpose(jvp(embed))/scatter-add", "backward"),
    # the head, both directions and its own recomputation
    (f"{J}/jvp(head_loss)/while/body/closed_call/dot_general", "head_loss"),
    (f"{J}/transpose(jvp(head_loss))/while/body/closed_call/checkpoint/"
     "rematted_computation/dot_general", "head_loss"),
    # the optimizer, and the refresh chain nested in it: innermost wins
    (f"{J}/opt_update/...dr,...dn->...rn/dot_general", "opt_update"),
    (f"{J}/opt_update/opt_refresh/bdn,bdo->bno/dot_general", "opt_refresh"),
    (f"{J}/opt_update/opt_refresh/qr/jit(qr)/geqrf", "qr"),
    (f"{J}/opt_update/opt_refresh/power_iter/pallas_call", "power_iter"),
    (f"{J}/opt_update/opt_refresh/small_svd/jit(svd)/svd", "small_svd"),
    (f"{J}/opt_update/opt_refresh/sketch/bmn,bnk->bmk/dot_general",
     "sketch"),
    (f"{J}/opt_update/opt_refresh/vmap(sara_sample)/top_k", "sara_sample"),
    # a function that JAX names after a scope is no scope
    (f"{J}/opt_update/opt_refresh/jit(qr)/geqrf", "opt_refresh"),
    (f"{J}/jvp()/while/body/squeeze", None),
    ("", None),
    (None, None),
])
def test_classify(name, cls):
    assert scopes.classify(name) == cls


# two instructions of a compiled TPU module as ``as_text()`` prints them,
# and the device event of the first as the profiler names it
HLO = f"""HloModule jit_step_fn, entry_computation_layout={{...}}
  %fusion.478 = (f32[2,6,4096]{{2,1,0:T(8,128)S(1)}}) fusion(%p.1), \
kind=kLoop, calls=%fused_computation.3, metadata={{op_name="{J}/\
transpose(jvp(blocks))/while/body/closed_call/checkpoint/mul" \
stack_frame_id=15}}, backend_config={{"flag_configs":[]}}
  ROOT %copy.2 = f32[8]{{0}} copy(%fusion.478)
"""
EVENT = Event(
    "%fusion.478 = (f32[2,6,4096]{2,1,0:T(8,128)S(1)}) fusion(f32[1,2,6]"
    " %p.1), kind=kLoop, calls=%fused_computation.3", 0.0, 1.0,
    "device_offset_ps=0 device_duration_ps=1", "/device:TPU:0")


def test_op_names_from_the_compiled_module():
    names = scopes.op_names_from_hlo(HLO)
    want = (f"{J}/transpose(jvp(blocks))/while/body/closed_call/checkpoint/"
            "mul")
    assert names == {"fusion.478": want}
    assert scopes.op_name(EVENT, names) == want
    assert scopes.classify(scopes.op_name(EVENT, names)) == "backward"
    # an instruction with no metadata, or an event of another module
    assert scopes.op_name(EVENT._replace(name="%copy.2 = f32[8]"),
                          names) is None
    assert scopes.op_name(EVENT._replace(name="%while.9 = s32[]"),
                          names) is None


NAMES = {}  # instruction -> op_name, as ``op_names_from_hlo`` gives it


def _ev(name, a, b, op, plane="/device:TPU:0"):
    if op:
        NAMES[name] = op
    return Event(f"%{name} = f32[8]{{0}} op()", a, b, "", plane)


# a while loop [0, 10] holds its body's ops: two forward ops that overlap
# on [2, 3], one backward op; after it a head op and an unscoped copy
EVENTS = [
    _ev("while.1", 0.0, 10.0, f"{J}/jvp(blocks)/while"),
    _ev("fusion.1", 1.0, 3.0, f"{J}/jvp(blocks)/while/body/add"),
    _ev("fusion.2", 2.0, 4.0, f"{J}/jvp(blocks)/while/body/mul"),
    _ev("fusion.3", 6.0, 9.0, f"{J}/transpose(jvp(blocks))/while/body/dot"),
    _ev("fusion.4", 10.0, 12.0, f"{J}/jvp(head_loss)/dot"),
    _ev("copy.1", 12.0, 13.0, ""),
    _ev("fusion.5", 13.0, 20.0, f"{J}/opt_update/add"),  # past the window
]


def test_a_leaf_of_no_scope_takes_its_loops():
    # an instruction XLA made inside the forward loop, with no name, and
    # one outside any loop
    made = _ev("copy.7", 4.5, 5.5, "")
    got = scopes.seconds(EVENTS + [made], 0.0, 15.0, NAMES)
    assert got["forward"] == pytest.approx(4.0)  # [1, 4] and [4.5, 5.5]
    assert got[None] == pytest.approx(1.0)  # copy.1 alone


def test_leaves_drop_the_loop_that_holds_them():
    names = sorted(e.name.split(" ")[0] for e in scopes.leaves(EVENTS))
    assert names == ["%copy.1", "%fusion.1", "%fusion.2", "%fusion.3",
                     "%fusion.4", "%fusion.5"]
    # an op on another device is not inside this device's loop
    other = _ev("fusion.9", 1.0, 2.0, "", plane="/device:TPU:1")
    assert other in scopes.leaves(EVENTS + [other])


def test_seconds_are_the_union_of_leaf_intervals_in_the_window():
    got = scopes.seconds(EVENTS, 0.0, 15.0, NAMES)
    assert got["forward"] == pytest.approx(3.0)  # [1, 4]
    assert got["backward"] == pytest.approx(3.0)
    assert got["head_loss"] == pytest.approx(2.0)
    assert got[None] == pytest.approx(1.0)
    assert got["opt_update"] == pytest.approx(2.0)  # [13, 15]
    # busy all of [0, 15] (the loop's own span counts), 1 s of it unscoped
    assert scopes.unscoped_share(EVENTS, 0.0, 15.0, NAMES) == pytest.approx(
        1 / 15)


class _Ctx:
    def __init__(self, events, t0, t1, steps, op_names):
        self.events, self.t0, self.t1, self.steps = events, t0, t1, steps
        self.op_names = op_names


def test_per_step_and_a_program_without_scopes():
    ctx = _Ctx(EVENTS, 0.0, 15.0, 2, NAMES)
    assert scopes.per_step(ctx, ("forward",)) == pytest.approx(1.5)
    assert scopes.per_step(ctx, ("forward", "backward")) == pytest.approx(3.0)
    assert scopes.per_step(ctx, ("small_svd",)) is None
    # a program without the scopes, or a harness with no map
    assert scopes.per_step(_Ctx(EVENTS, 0.0, 15.0, 2, {}),
                           ("forward",)) is None
    del ctx.op_names
    assert scopes.per_step(ctx, ("forward",)) is None
    # an event named by its instruction, with its module's map
    ctx = _Ctx([EVENT], 0.0, 0.5, 1, scopes.op_names_from_hlo(HLO))
    assert scopes.per_step(ctx, ("backward",)) == pytest.approx(0.5)


def test_gaps_named_by_the_loop_spans():
    events = EVENTS[1:]  # busy [1, 4], [6, 9], [10, 15] without the loop
    spans = [Span("chipbench.window", 0.0, 15.0),
             Span("chipbench.wait", 3.9, 6.1),
             Span("repro.loop.fetch", 3.5, 6.5),
             Span("repro.loop.dispatch", 9.0, 9.5)]
    gaps = scopes.idle_gaps_program(events, spans, 0.0, 15.0)
    # the harness's own spans name no gap here
    assert sorted(gaps) == sorted([
        ("repro.loop.fetch", pytest.approx(2.0)),
        ("repro.loop.dispatch", pytest.approx(1.0)),
        (scopes.NO_LOOP_SPAN, pytest.approx(1.0)),
    ])
