"""Operation and byte counts against hand-computed values."""

import numpy as np
import pytest
import work

TINY = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 2,
        "head_dim": 4, "d_ff": 16, "vocab_size": 32, "use_bias": True}


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


def test_min_time_is_the_larger_bound():
    peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.min_time(1000.0, 5.0, peaks) == pytest.approx(10.0)
    assert work.min_time(10.0, 50.0, peaks) == pytest.approx(5.0)
