"""Bitrot guard for the StableHLO precision-audit classifier
(tools/hbm_census.py · classify_contractions): the dtype regexes must
keep parsing the StableHLO text format, and the classification must
distinguish the correct MXU configuration (bf16 inputs, f32
accumulator) from genuine f32-input contractions."""

import importlib.util
import os

SNIPPET = """\
  %0 = stablehlo.dot_general %a, %b, contracting_dims = [1] x [0] : (tensor<8x16xbf16>, tensor<16x4xbf16>) -> tensor<8x4xbf16>
  %1 = stablehlo.dot_general %c, %d, contracting_dims = [1] x [0] : (tensor<8x16xbf16>, tensor<16x4xbf16>) -> tensor<8x4xf32>
  %2 = stablehlo.dot_general %e, %f, contracting_dims = [1] x [0] : (tensor<8x16xf32>, tensor<16x4xf32>) -> tensor<8x4xf32>
  %3 = stablehlo.add %0, %0 : tensor<8x4xbf16>
  %4 = stablehlo.convolution(%x, %w) {foo} : (tensor<1x8x8x3xbf16>, tensor<3x3x3x4xbf16>) -> tensor<1x8x8x4xbf16>
"""


def _load():
    spec = importlib.util.spec_from_file_location(
        "hbm_census_audit", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "hbm_census.py"))
    # keep it isolated under its own name
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_classify_contractions_by_input_and_result_dtype():
    mod = _load()
    dots = mod.classify_contractions(SNIPPET, "dot_general")
    assert dots == {"bf16->bf16": 1, "bf16->f32": 1, "f32->f32": 1}
    convs = mod.classify_contractions(SNIPPET, "convolution")
    assert convs == {"bf16->bf16": 1}
