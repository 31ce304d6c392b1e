"""``tools/product_operands.py`` on a hand-written compiled text: the
parse, which operands FEED a product (as against the arithmetic fused
around it), where each comes from, the product's operations and the
split by weight operand. The text is cut from a v5e compile of the
train cell's step: a forward product that converts its float32 weight
inside the fusion, one whose weight was prefetched, one fed by a bf16
leaf cast before, and a weight-gradient product that shares its fusion
with Adam's update of the same float32 weight."""

import io
import math

import pytest
from conftest import load_tool

tool = load_tool("product_operands")

L = "{1,0:T(8,128)}"
L3 = "{2,1,0:T(8,128)(2,1)}"
TEXT = f"""HloModule jit__step, is_scheduled=true

%fused_computation.510 (param_0.1: f32[256,512]) -> bf16[256,512,1] {{
  %param_0.1 = f32[256,512]{L} parameter(0)
  %convert.1 = bf16[256,512]{L} convert(%param_0.1), metadata={{op_name="jit(_step)/jvp(mlp)/convert_element_type"}}
  ROOT %bitcast.1 = bf16[256,512,1]{{1,0,2:T(8,128)(2,1)}} bitcast(%convert.1)
}}

%fused_computation.509 (param_0.2: f32[256,512], param_1.2: bf16[8,64,256]) -> bf16[8,64,512] {{
  %param_1.2 = bf16[8,64,256]{L3} parameter(1)
  %param_0.2 = f32[256,512]{L} parameter(0)
  %fusion.286 = bf16[256,512,1]{{1,0,2:T(8,128)(2,1)}} fusion(%param_0.2), kind=kLoop, calls=%fused_computation.510, metadata={{op_name="jit(_step)/jvp(mlp)/convert_element_type"}}
  ROOT %convolution.214 = bf16[8,64,512]{L3} convolution(%param_1.2, %fusion.286), window={{size=1}}, dim_labels=0bf_io0->0bf, metadata={{op_name="jit(_step)/jvp(mlp)/dot_general"}}
}}

%fused_computation.600 (param_0.3: bf16[256,512,1], param_1.3: bf16[8,64,256]) -> bf16[8,64,512] {{
  %param_1.3 = bf16[8,64,256]{L3} parameter(1)
  %param_0.3 = bf16[256,512,1]{{1,0,2:T(8,128)(2,1)S(1)}} parameter(0)
  ROOT %convolution.300 = bf16[8,64,512]{L3} convolution(%param_1.3, %param_0.3), window={{size=1}}, dim_labels=0bf_io0->0bf, metadata={{op_name="jit(_step)/checkpoint/rematted_computation/mlp/dot_general"}}
}}

%fused_computation.700 (param_0.4: f32[256,512], param_1.4: f32[256,512], param_2.4: bf16[8,64,256], param_3.4: bf16[8,64,512]) -> (f32[256,512], f32[256,512]) {{
  %param_2.4 = bf16[8,64,256]{L3} parameter(2)
  %param_3.4 = bf16[8,64,512]{L3} parameter(3)
  %convolution.400 = bf16[256,512,1]{{1,0,2:T(8,128)(2,1)}} convolution(%param_2.4, %param_3.4), window={{size=8}}, dim_labels=0fb_0io->bf0, metadata={{op_name="jit(_step)/transpose(jvp(mlp))/dot_general"}}
  %convert.4 = f32[256,512,1]{{1,0,2:T(8,128)}} convert(%convolution.400)
  %bitcast.4 = f32[256,512]{L} bitcast(%convert.4)
  %param_1.4 = f32[256,512]{L} parameter(1)
  %add.4 = f32[256,512]{L} add(%param_1.4, %bitcast.4)
  %param_0.4 = f32[256,512]{L} parameter(0)
  %subtract.4 = f32[256,512]{L} subtract(%param_0.4, %add.4)
  ROOT %tuple.4 = (f32[256,512]{L}, f32[256,512]{L}) tuple(%subtract.4, %add.4)
}}

ENTRY %main.1 (params__up__.1: f32[256,512], params__gate__.1: f32[256,512], m.1: f32[256,512], x.1: bf16[8,64,256]) -> bf16[8,64,512] {{
  %params__up__.1 = f32[256,512]{L} parameter(0), metadata={{op_name="params[\\'ffn.up.weight\\']"}}
  %params__gate__.1 = f32[256,512]{L} parameter(1), metadata={{op_name="params[\\'ffn.gate.weight\\']"}}
  %m.1 = f32[256,512]{L} parameter(2), metadata={{op_name="opt_state[\\'leaf\\'][0][\\'m\\']"}}
  %x.1 = bf16[8,64,256]{L3} parameter(3), metadata={{op_name="batch"}}
  %fusion.285 = bf16[8,64,512]{L3} fusion(%params__up__.1, %x.1), kind=kOutput, calls=%fused_computation.509, metadata={{op_name="jit(_step)/jvp(mlp)/dot_general"}}, backend_config={{"window_config":{{"estimated_cycles":"3000000"}}}}
  %copy-start.1 = (f32[256,512]{{1,0:T(8,128)S(1)}}, f32[256,512]{L}, u32[]) copy-start(%params__gate__.1)
  %copy-done.1 = f32[256,512]{{1,0:T(8,128)S(1)}} copy-done(%copy-start.1)
  %fusion.290 = bf16[8,64,512]{L3} fusion(%copy-done.1, %x.1), kind=kOutput, calls=%fused_computation.509, metadata={{op_name="jit(_step)/jvp(mlp)/dot_general"}}, backend_config={{"window_config":{{"estimated_cycles":"1500000"}}}}
  %convert.9 = bf16[256,512,1]{{1,0,2:T(8,128)(2,1)S(1)}} convert(%params__up__.1), metadata={{op_name="jit(_step)/jvp(weight_cast)/convert_element_type"}}
  %fusion.300 = bf16[8,64,512]{L3} fusion(%convert.9, %x.1), kind=kOutput, calls=%fused_computation.600, metadata={{op_name="jit(_step)/checkpoint/rematted_computation/mlp/dot_general"}}, backend_config={{"window_config":{{"estimated_cycles":"1500000"}}}}
  %divide_subtract_fusion.2 = (f32[256,512]{L}, f32[256,512]{L}) fusion(%params__up__.1, %m.1, %x.1, %fusion.300), kind=kOutput, calls=%fused_computation.700, metadata={{op_name="jit(_step)/transpose(jvp(mlp))/dot_general"}}
  ROOT %out.1 = bf16[8,64,512]{L3} add(%fusion.285, %fusion.290)
}}
"""


@pytest.fixture(scope="module")
def found():
    return {p.fusion.name: p for p in tool.products(TEXT)}


def test_the_product_fusions_are_the_outer_ones(found):
    assert sorted(found) == ["divide_subtract_fusion.2", "fusion.285",
                             "fusion.290", "fusion.300"]


def test_operands_origins_and_which_feed_the_product(found):
    ops = {name: [(shape, what, fed) for shape, what, _, fed in p.operands]
           for name, p in found.items()}
    assert ops["fusion.285"] == [
        ("f32[256,512]", "parameter params['ffn.up.weight']", True),
        ("bf16[8,64,256]", "parameter batch", True)]
    assert ops["fusion.290"][0] == (
        "f32[256,512] S(1)",
        "prefetched parameter params['ffn.gate.weight']", True)
    assert ops["fusion.300"][0] == (
        "bf16[256,512,1] S(1)", "convert", True)
    # Adam's operands stand beside the weight-gradient product
    assert [(w, fed) for _, w, fed in ops["divide_subtract_fusion.2"]] == [
        ("parameter params['ffn.up.weight']", False),
        ("parameter opt_state['leaf'][0]['m']", False),
        ("parameter batch", True),
        ("fusion dot_general", True)]


def test_which_products_read_a_float32_master_weight(found):
    wide = {name: tool.wide_parameters(p) for name, p in found.items()}
    assert wide == {"fusion.285": ["ffn.up.weight"],
                    "fusion.290": ["ffn.gate.weight"],
                    "fusion.300": [], "divide_subtract_fusion.2": []}
    assert tool.wide_parameters(found["fusion.285"], {"other"}) == []
    assert {name: tool.weight_class(p) for name, p in found.items()} == {
        "fusion.285": "float32 parameter read from HBM in the fusion",
        "fusion.290": "float32 parameter prefetched to fast memory",
        "fusion.300": "no matrix parameter feeds the product",
        "divide_subtract_fusion.2": "no matrix parameter feeds the product"}


def test_operations_estimates_scopes_and_passes(found):
    flops = 2 * 8 * 64 * 512 * 256
    for p in found.values():
        assert p.peak_ms == pytest.approx(flops / tool.PEAK_FLOPS * 1e3)
    assert found["fusion.285"].estimate_ms == pytest.approx(2.0)
    assert found["fusion.290"].estimate_ms == pytest.approx(1.0)
    # the compiler gave none: not counted
    assert math.isnan(found["divide_subtract_fusion.2"].estimate_ms)
    assert {n: (p.scope, p.which) for n, p in found.items()} == {
        "fusion.285": ("mlp", "forward"), "fusion.290": ("mlp", "forward"),
        "fusion.300": ("mlp", "recompute"),
        "divide_subtract_fusion.2": ("mlp", "backward")}


def test_the_table_prints_and_sums(found):
    out = io.StringIO()
    tool.show("text", list(found.values()), lines=True, file=out)
    text = out.getvalue()
    assert "4 product fusions (3 with the compiler's estimated_cycles)" \
        in text
    assert "(beside) f32[256,512] <- parameter params['ffn.up.weight']" \
        in text
    assert "mlp forward:   2      3.00" in text
    assert "(+1 without an estimate)" in text
