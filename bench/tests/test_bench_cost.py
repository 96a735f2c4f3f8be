"""Operations and bytes from shapes, against hand-worked numbers."""
import pytest

import tiny  # noqa: F401
from harness import cost, hlo
from harness.peaks import peaks
from harness.spec import load_model

QWEN3 = load_model({"model_type": "qwen3"})

FFN = ("%swiglu_ffn.4 = bf16[4,2560]{1,0:T(4,128)(2,1)S(1)} custom-call("
       "bf16[4,2560]{1,0:T(4,128)(2,1)S(1)} %fusion.90, "
       "bf16[2560,9728]{1,0:T(8,128)(2,1)S(1)} %g, "
       "bf16[2560,9728]{1,0:T(8,128)(2,1)S(1)} %u, "
       "bf16[9728,2560]{1,0:T(8,128)(2,1)} %d), "
       "custom_call_target=\"tpu_custom_call\"")
DEC = ("%decode_attention.4 = bf16[4,8,4,128]{3,2,1,0:T(4,128)(2,1)S(1)} "
       "custom-call(s32[4]{0:T(128)S(1)} %p, "
       "bf16[4,8,4,128]{3,2,1,0:T(4,128)(2,1)S(1)} %q, "
       "bf16[4,4096,8,128]{3,2,1,0:T(8,128)(2,1)} %k, "
       "bf16[4,4096,8,128]{3,2,1,0:T(8,128)(2,1)} %v, "
       "s32[4,1,4096]{2,1,0:T(1,128)} %kp)")


def test_fused_ffn_counts():
    w = cost.fused_ffn(hlo.parse(FFN))
    assert w.flops == 6 * 4 * 2560 * 9728
    assert w.hbm_bytes == 9728 * 2560 * 2               # the down weights
    assert w.vmem_bytes == 2 * 2560 * 9728 * 2 + 2 * 4 * 2560 * 2


def test_decode_attention_counts_live_context_only():
    op = hlo.parse(DEC)
    w = cost.decode_attention_live(op, [100, 300])
    live = 400
    assert w.flops == 4 * 8 * 4 * 128 * live
    # K and V rows of 8 heads x 128 in bf16, plus an int32 position each
    assert w.hbm_bytes == live * (2 * 8 * 128 * 2 + 4)
    # q in, out written: one row per served slot
    assert w.vmem_bytes == 2 * 2 * (8 * 4 * 128 * 2)
    assert cost.decode_attention_live(op, [4096] * 4).hbm_bytes > \
        w.hbm_bytes * 10


def test_min_seconds_takes_the_binding_resource():
    p = peaks("TPU v5 lite")
    assert cost.Work(197e12, 0, 0).min_seconds(p) == pytest.approx(1.0)
    assert cost.Work(1, 819e9, 0).min_seconds(p) == pytest.approx(1.0)
    assert cost.Work(1, 0, 18432e9).min_seconds(p) == pytest.approx(1.0)


def test_model_flops_by_hand():
    m = QWEN3.ModelCost(layers=2, d_model=64, heads=4, kv_heads=2,
                       head_dim=16, d_ff=128, vocab=256)
    per_layer = 64 * 64 + 2 * 64 * 32 + 64 * 64 + 3 * 64 * 128
    assert m.layer_params == per_layer
    assert m.decode_flops(10) == (2 * 2 * per_layer + 4 * 2 * 4 * 16 * 10
                                  + 2 * 64 * 256)
    assert m.prefill_flops(3) == (2 * 2 * per_layer * 3
                                  + 2 * 2 * 4 * 16 * 3 * 4 + 2 * 64 * 256)


def test_shapes_and_names_parse():
    op = hlo.parse(FFN)
    assert op.name == "swiglu_ffn" and op.opcode == "custom-call"
    assert [b.dims for b in op.operands] == [(4, 2560), (2560, 9728),
                                             (2560, 9728), (9728, 2560)]
    assert op.outputs[0].vmem and not op.operands[3].vmem
    assert hlo.base_name("jit_decode(1443)") == "jit_decode"
    tup = hlo.parse("%while.1 = (s32[]{:T(128)}, bf16[4,1,8]{2,1,0}) "
                    "while((s32[], bf16[4,1,8]) %t), body=%b")
    assert tup.opcode == "while" and len(tup.outputs) == 2
