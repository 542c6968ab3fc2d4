"""Operation and byte counts against hand counts at qwen3-4b widths: the
dense decoder family's census and the per-call kernel rules."""
import json
import os
import types

import pytest

from bench import spec, workcount

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "..", "configs", "qwen3-4b.json")) as f:
    QWEN = json.load(f)
with open(os.path.join(HERE, "..", "configs", "mistral-nemo-12b.json")) as f:
    NEMO = json.load(f)
DENSE = spec.load_family(QWEN)
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_linear_census():
    # per layer: q 2560x4096, k and v 2560x1024, o 4096x2560,
    # gate and up 2560x9728, down 9728x2560
    per_layer = (2560 * 4096 + 2 * 2560 * 1024 + 4096 * 2560
                 + 3 * 2560 * 9728)
    assert per_layer == 100_925_440
    assert DENSE.linear_flops_per_token(QWEN) == 2 * 36 * per_layer


def test_step_flops():
    lin = 2 * 36 * 100_925_440
    att = 4 * 36 * 32 * 128           # per position attended
    unemb = 2 * 2560 * 151936
    # one decode at context 100, and a 3-token prefill from position 5
    # (contexts 6, 7, 8) that finishes the prompt
    step = {"prefill": [(5, 3)], "decode_ctx": [100], "firsts": 1}
    want = (lin + att * 100) + (3 * lin + att * (6 + 7 + 8)) + 2 * unemb
    assert DENSE.step_flops(QWEN, step) == want


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


def test_dense_kernel_census():
    """Every layer's seven linears on the fused kernel, every layer on
    the paged decode kernel: 36 layers in qwen3-4b, the 10 of one
    mistral-nemo-12b pipeline stage (q width 4096 below d 5120)."""
    assert DENSE.fused_linears(QWEN) == [
        (2560, 4096, 36), (2560, 1024, 36), (2560, 1024, 36),
        (4096, 2560, 36), (2560, 9728, 36), (2560, 9728, 36),
        (9728, 2560, 36)]
    assert DENSE.paged_layers(QWEN) == 36
    assert DENSE.fused_linears(NEMO) == [
        (5120, 4096, 10), (5120, 1024, 10), (5120, 1024, 10),
        (4096, 5120, 10), (5120, 14336, 10), (5120, 14336, 10),
        (14336, 5120, 10)]
    assert DENSE.paged_layers(NEMO) == 10


# Every per-layer reader's value on run records kept from traced runs on
# a TPU v5e, one a cell (`bench/tests/data/record.<cell>.json`: what the
# readers read, tracks cut to the queue-wait reader's three fields), as
# the readers before the family seam computed them, when the census was
# `workcount`'s and each reader counted layers itself; the same values
# the runs printed.  Taking the census from the family changes none.
PINNED = {
    "mistral-nemo-12b.chat": {
        "scheduler.queue_wait_p50_s": 0.6923619962134921,
        "model.prefill_chunk_ms": 221.18111633846155,
        "model.decode_step_ms": 204.92554349431813,
        "step.mfu": 0.8627034618906518,
        "kernel.fp8_linear_roofline": 4.067880365626075,
        "kernel.paged_decode_roofline": 0.26192310530164414,
        "device.idle_share": 1.165768186681071,
    },
    "qwen3-4b.chat": {
        "scheduler.queue_wait_p50_s": 1.2126635358981197,
        "model.prefill_chunk_ms": 301.6169331935484,
        "model.decode_step_ms": 515.039988625,
        "step.mfu": 0.5454027834804206,
        "kernel.fp8_linear_roofline": 4.08136327805606,
        "kernel.paged_decode_roofline": 0.2650100293846399,
        "device.idle_share": 3.2956654700735166,
    },
    "qwen3-4b.long-prompt": {
        "scheduler.queue_wait_p50_s": 0.9384953547860633,
        "model.prefill_chunk_ms": 301.6146720781249,
        "model.decode_step_ms": 515.0340450468751,
        "step.mfu": 1.0854121955600047,
        "kernel.fp8_linear_roofline": 3.7931848552514116,
        "kernel.paged_decode_roofline": 0.19859701149775744,
        "device.idle_share": 0.49107966135500947,
    },
}


@pytest.mark.parametrize("cell", sorted(PINNED))
def test_readers_on_a_chip_record(cell):
    with open(os.path.join(HERE, "data", f"record.{cell}.json")) as f:
        rec = json.load(f)
    rec["root"] = spec.ROOT
    rec["tracks"] = [types.SimpleNamespace(**t) for t in rec["tracks"]]
    for name, want in PINNED[cell].items():
        assert spec.load_reader(name)(rec) == want, name
