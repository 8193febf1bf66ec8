"""The split of traced device time by the program's named scopes, on
hand-built ops with known answers."""
import pytest

import scope_split as ss

PATH = "jit(step_impl)/jit(main)/while/body/{}/dot_general"


def test_the_innermost_scope_on_the_op_name_path():
    assert ss.scope_of([PATH.format("mla")]) == "mla"
    assert ss.scope_of([PATH.format("mla/moe.route")]) == "moe.route"
    assert ss.scope_of(['%fusion.3 = f32[4] fusion(), metadata={op_name="'
                        + PATH.format("moe.experts") + '"}']) == "moe.experts"
    # a scope's name inside another word is no scope
    assert ss.scope_of([PATH.format("xmla"), "moe.routes/x"]) == ss.UNSCOPED
    assert ss.scope_of([]) == ss.UNSCOPED


def test_self_time_leaves_out_nested_ops_and_the_window_clips():
    ns = 1e9
    ops = [(0, 10 * ns, "while.1", ss.UNSCOPED),
           (1 * ns, 4 * ns, "fusion.1", "mla"),
           (2 * ns, 3 * ns, "inner.1", "moe.route"),     # inside fusion.1
           (5 * ns, 9 * ns, "fusion.2", "moe.experts"),
           (12 * ns, 14 * ns, "copy.1", ss.UNSCOPED)]
    got = {n: t for n, _, t in ss.self_times(ops, 0, 13 * ns)}
    assert got == pytest.approx({"while.1": 3.0, "fusion.1": 2.0,
                                 "inner.1": 1.0, "fusion.2": 4.0,
                                 "copy.1": 1.0})
    out = ss.split(ops, 0, 13 * ns)
    assert out["seconds"] == pytest.approx(
        {"mla": 2.0, "moe.route": 1.0, "moe.experts": 4.0,
         ss.UNSCOPED: 4.0})
    # the union of the ops inside the window: every second of [0, 10) and
    # [12, 13)
    assert sum(out["seconds"].values()) == pytest.approx(11.0)
    assert out["top_ops"][ss.UNSCOPED][0] == ["while.1", pytest.approx(3.0)]


def test_a_compiled_modules_instructions_give_shape_and_path():
    text = ('  %fusion.3 = bf16[4,8]{1,0} fusion(%p), kind=kLoop, '
            'metadata={op_name="' + PATH.format("mla") + '" '
            'stack_frame_id=2}\n'
            '  ROOT %copy.1 = f32[2]{0} copy(%x)\n')
    assert ss.instructions(text) == {
        "fusion.3": ("bf16[4,8]{1,0}", PATH.format("mla")),
        "copy.1": ("f32[2]{0}", "")}
