"""Operation and byte counts against hand-computed values."""

import json

import numpy as np
import pytest
import work
from conftest import REPO

TINY = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 2,
        "head_dim": 4, "d_ff": 16, "vocab_size": 32, "use_bias": True,
        "norm": "layernorm"}

#: one Mamba layer with a dense feed-forward, then one attention layer with
#: an MoE feed-forward; RMSNorm
HYBRID = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 1,
          "head_dim": 4, "d_ff": 16, "vocab_size": 32, "norm": "rmsnorm",
          "layer_pattern": ["mamba", "attn"], "moe_period": 2,
          "moe_offset": 1, "n_experts": 4, "n_experts_per_tok": 2,
          "moe_d_ff": 12, "ssm_state": 2, "ssm_conv": 3, "ssm_expand": 2,
          "ssm_dt_rank": 1}


def test_one_operator():
    x = np.zeros((1000, 4), np.float32)
    flops, bytes_ = work.ds_task_work("sql_transform", {}, [x], x.copy())
    assert bytes_ == 2 * 1000 * 4 * 4  # read once, write once
    assert flops == 3 * 1000 * 4      # scale, shift, clip


def test_passed_through_input_is_not_counted():
    x = np.zeros((100, 3), np.float32)
    cent = np.zeros((4, 3), np.float32)
    assign = np.zeros(100, np.int32)
    out = {"x": x, "fit": (cent, assign, np.float32(0))}
    flops, bytes_ = work.ds_task_work("kmeans", {"k": 4, "iters": 10},
                                      [{"x": x}], out)
    assert bytes_ == 100 * 3 * 4 + 4 * 3 * 4 + 100 * 4 + 4
    assert flops == 11 * 3 * 100 * 3 * 4 + 10 * 100 * 3


def test_decode_step():
    # per layer: q,k,v,o 4*8*8 + ffn 3*8*16 = 640; head 8*32 = 256
    counts = work.lm_weight_counts(TINY)
    assert counts["matmul"] == 2 * 640 + 256
    # norms 4*8 and biases q,k,v,o 8+8+8+8 per layer; final norm 2*8
    assert counts["small"] == 2 * (32 + 32) + 16
    flops, bytes_ = work.decode_step_work(TINY, [3, 5])
    assert flops == 2 * 1536 * 2 + 4 * 2 * 4 * 2 * (3 + 5)
    # bf16 weights, the 2 looked-up embedding rows, K/V of 8 positions
    # (2 heads x 4 dims x k,v x 2 layers x 2 bytes each)
    assert bytes_ == (1536 + 144 + 2 * 8) * 2 + 8 * (2 * 2 * 4 * 2 * 2)


def test_decode_step_by_layer_kind():
    assert work.layer_kinds(HYBRID) == [("mamba", "dense", 16),
                                        ("attn", "moe", 16)]
    counts = work.lm_weight_counts(HYBRID)
    # Mamba (d_inner 16, state 2, dt rank 1, conv 3): in 8*32, conv 3*16,
    # x 16*(1+2*2), dt 1*16, out 16*8 = 528; dense FF 3*8*16 = 384;
    # attention q,o 2*8*8 and k,v 2*8*4 = 192; router 8*4; head 8*32
    assert counts["matmul"] == 528 + 384 + 192 + 32 + 256
    # A 16*2, D, dt bias, conv bias 3*16; two RMSNorms a layer, a final one
    assert counts["small"] == 16 * 5 + 2 * 2 * 8 + 8
    assert counts["expert"] == 3 * 8 * 12
    # two slots attending to 3 and 5 positions; 3 held experts touched by
    # the 2 tokens' 4 choices
    flops, bytes_ = work.decode_step_work(HYBRID, [3, 5], moe=(3, 4))
    assert flops == (2 * 1392 * 2            # weights, per token
                     + 4 * 2 * 4 * (3 + 5)  # one attention layer
                     + 6 * 16 * 2 * 2       # one Mamba layer's scan
                     + 2 * 288 * 4)         # routed token-choices
    assert bytes_ == ((1392 + 120 + 2 * 8) * 2   # weights, embedding rows
                      + 8 * (2 * 1 * 4 * 2)      # K/V of 8 positions
                      + 2 * 2 * (16 * 2 * 4 + 2 * 16 * 2)  # h f32, conv ring
                      + 3 * 288 * 2)             # touched experts


def test_decode_step_needs_the_programs_expert_counts():
    with pytest.raises(ValueError, match="moe"):
        work.decode_step_work(HYBRID, [3, 5])
    with pytest.raises(ValueError, match="mixer"):
        work.decode_step_work(dict(TINY, layer_pattern=["local"]), [3])
    with pytest.raises(ValueError, match="n_shared_experts"):
        work.decode_step_work(dict(HYBRID, n_shared_experts=1), [3],
                              moe=(1, 1))


#: ``decode_step_work`` on the chat cell's model: what the count for dense
#: decoders alone read, which the count by layer kind keeps to the last bit
STABLELM_STEPS = [
    ([1], (2877489152.0, 2878287872.0)),
    ([257, 513, 1025, 1537], (12164268032.0, 3533201408.0)),
    ([2048] * 7 + [300], (25895895040.0, 5755674624.0)),
]


@pytest.mark.parametrize("kv_lens,expected", STABLELM_STEPS)
def test_decode_step_of_the_chat_model_is_unchanged(kv_lens, expected):
    m = json.loads((REPO / "bench" / "configs" / "stablelm-1.6b.json")
                   .read_text())["model"]
    assert work.decode_step_work(m, kv_lens) == expected


def test_min_time_is_the_larger_bound():
    peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.min_time(1000.0, 5.0, peaks) == pytest.approx(10.0)
    assert work.min_time(10.0, 50.0, peaks) == pytest.approx(5.0)
