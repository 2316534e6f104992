"""obs/attrib/hlo.py on hand-made HLO text: what a compiled program's loop
does to a buffer of one shape (the chip's own text: tests/test_tpu_compile.py)."""

import pytest

from tenzing_tpu.obs.attrib.hlo import computations, loop_ops_of_shape

HLO = """HloModule jit_stepped

%fused_add (p0: f32[3,8,8,8], p1: f32[]) -> (f32[3,8,8,8], f32[3,8,8,8]) {
  %p0 = f32[3,8,8,8]{3,2,1,0:T(8,128)} parameter(0)
  %p1 = f32[]{:T(128)} parameter(1)
  %b = f32[3,8,8,8]{3,2,1,0:T(8,128)} broadcast(%p1), dimensions={}
  %a = f32[3,8,8,8]{3,2,1,0:T(8,128)} add(%p0, %b)
  ROOT %t = (f32[3,8,8,8]{3,2,1,0:T(8,128)}, f32[3,8,8,8]{3,2,1,0:T(8,128)}) tuple(%a, %a)
}

%body (param: (s32[], f32[3,8,8,8], f32[3,3,8,8])) -> (s32[], f32[3,8,8,8], f32[3,3,8,8]) {
  %param = (s32[]{:T(128)}, f32[3,8,8,8]{3,2,1,0:T(8,128)}, f32[3,3,8,8]{3,2,1,0:T(8,128)}) parameter(0)
  %g = f32[3,8,8,8]{3,2,1,0:T(8,128)} get-tuple-element(%param), index=1
  %tok = f32[]{:T(128)} constant(0)
  %broadcast_add_fusion.1 = (f32[3,8,8,8]{3,2,1,0:T(8,128)}, f32[3,8,8,8]{3,2,1,0:T(8,128)}) fusion(%g, %tok), kind=kLoop, calls=%fused_add
  %copy.7 = f32[3,8,8,8]{3,1,2,0:T(8,128)} copy(%g)
  %call = f32[3,3,8,8]{3,2,1,0:T(8,128)} custom-call(%g), custom_call_target="tpu_custom_call", backend_config={"payload":{
"mesh_axes":"[]"
}}, metadata={op_name="pallas_call"}
  %dynamic_update_slice.3 = f32[3,8,8,8]{3,2,1,0:T(8,128)} dynamic-update-slice(%g, %call, %c0, %c0, %c0, %c0)
  ROOT %out = (s32[]{:T(128)}, f32[3,8,8,8]{3,2,1,0:T(8,128)}, f32[3,3,8,8]{3,2,1,0:T(8,128)}) tuple(%i, %dynamic_update_slice.3, %call)
}

%cond (param.1: (s32[], f32[3,8,8,8], f32[3,3,8,8])) -> pred[] {
  %param.1 = (s32[]{:T(128)}, f32[3,8,8,8]{3,2,1,0:T(8,128)}, f32[3,3,8,8]{3,2,1,0:T(8,128)}) parameter(0)
  ROOT %lt = pred[]{:T(512)} constant(true)
}

ENTRY %main (p: f32[3,8,8,8], q: f32[3,3,8,8]) -> f32[] {
  %p = f32[3,8,8,8]{3,2,1,0:T(8,128)} parameter(0)
  %entry_copy = f32[3,8,8,8]{3,2,1,0:T(8,128)} copy(%p)
  %while.1 = (s32[]{:T(128)}, f32[3,8,8,8]{3,2,1,0:T(8,128)}, f32[3,3,8,8]{3,2,1,0:T(8,128)}) while(%init), condition=%cond, body=%body
  ROOT %r = f32[]{:T(128)} constant(0)
}
"""


def test_computations_survive_a_payload_that_spans_lines():
    comps = computations(HLO)
    assert set(comps) == {"fused_add", "body", "cond", "main"}
    assert any("dynamic-update-slice(" in l for l in comps["body"])


@pytest.mark.parametrize("name,opcode,fused", [
    ("broadcast_add_fusion.1", "fusion", ("add", "broadcast")),
    ("copy.7", "copy", ()),
    ("dynamic_update_slice.3", "dynamic-update-slice", ()),
])
def test_loop_ops_of_the_grids_shape(name, opcode, fused):
    ops = {o.name: o for o in loop_ops_of_shape(HLO, "f32[3,8,8,8]")}
    # the entry's copy runs once a dispatch, the tuples and parameters move
    # nothing, the custom call's result is a face
    assert set(ops) == {"broadcast_add_fusion.1", "copy.7",
                        "dynamic_update_slice.3"}
    assert (ops[name].opcode, ops[name].fused) == (opcode, fused)


def test_a_program_without_a_loop_has_no_loop_ops():
    text = HLO.replace(" while(", " call(")
    assert loop_ops_of_shape(text, "f32[3,8,8,8]") == []
