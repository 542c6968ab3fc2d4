"""Operation and byte counts against hand counts at qwen3-4b widths."""
import json
import os

from bench import workcount

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "..", "configs", "qwen3-4b.json")) as f:
    QWEN = json.load(f)
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_linear_census():
    # per layer: q 2560x4096, k and v 2560x1024, o 4096x2560,
    # gate and up 2560x9728, down 9728x2560
    per_layer = (2560 * 4096 + 2 * 2560 * 1024 + 4096 * 2560
                 + 3 * 2560 * 9728)
    assert per_layer == 100_925_440
    assert workcount.linear_flops_per_token(QWEN) == 2 * 36 * per_layer


def test_step_flops():
    lin = 2 * 36 * 100_925_440
    att = 4 * 36 * 32 * 128           # per position attended
    unemb = 2 * 2560 * 151936
    # one decode at context 100, and a 3-token prefill from position 5
    # (contexts 6, 7, 8) that finishes the prompt
    step = {"prefill": [(5, 3)], "decode_ctx": [100], "firsts": 1}
    want = (lin + att * 100) + (3 * lin + att * (6 + 7 + 8)) + 2 * unemb
    assert workcount.step_flops(QWEN, step) == want


def test_fp8_linear_call_and_bound():
    ops, nbytes = workcount.fp8_linear_call(32, 2560, 9728)
    assert ops == 2 * 32 * 2560 * 9728
    assert nbytes == 2560 * 9728 + 4 * 9728 + 2 * 32 * 2560 + 2 * 32 * 9728
    t, bound = workcount.roofline_seconds(ops, nbytes, PEAK)
    assert bound == "memory" and t == nbytes / 819e9
    ops, nbytes = workcount.fp8_linear_call(240, 2560, 9728)
    t, bound = workcount.roofline_seconds(ops, nbytes, PEAK)
    assert bound == "compute" and t == ops / 197e12


def test_paged_decode_layer():
    ops, nbytes = workcount.paged_decode_layer(QWEN, [100, 300])
    assert ops == 4 * 32 * 128 * 400
    # K and V codes (1 byte) + f32 scales per row and head, q and out bf16
    assert nbytes == 400 * 8 * (2 * 128 + 2 * 4) + 2 * (2 * 32 * 128 * 2)
