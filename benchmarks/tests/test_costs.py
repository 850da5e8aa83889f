"""costs.py against counts worked by hand at Mistral-7B-v0.3 widths."""

import pytest

from benchmarks.harness import cells, costs, family_llama

M7B = family_llama.llama_fields(cells.load_config("mistral-7b-v0.3"))
NEMO = family_llama.llama_fields(cells.load_config("mistral-nemo-12b"))


def test_parameter_counts():
    # wq 4096x4096, wk and wv 4096x1024, wo 4096x4096, three of 4096x14336
    per_layer = 16777216 + 2 * 4194304 + 16777216 + 3 * 58720256
    assert per_layer == 218103808
    assert costs.layer_matmul_params(M7B) == per_layer
    assert costs.head_params(M7B) == 4096 * 32768 == 134217728
    assert costs.matmul_params(M7B) == 16 * per_layer + 134217728
    # + embedding, two norms a layer, the final norm
    assert costs.frozen_params(M7B) == 16 * (per_layer + 8192) \
        + 2 * 134217728 + 4096 == 3758231552
    # Nemo: q width 4096 != hidden 5120
    nemo_layer = 5120 * 4096 * 2 + 2 * 5120 * 1024 + 3 * 5120 * 14336
    assert costs.layer_matmul_params(NEMO) == nemo_layer == 272629760
    assert costs.frozen_params(NEMO) == 10 * (nemo_layer + 10240) \
        + 2 * 5120 * 131072 + 5120


def test_serve_request_flops_by_hand():
    # 3 prompt tokens, 2 output tokens: 4 positions are fed (the last
    # output token is never fed back), the head runs twice, and position p
    # attends p + 1 keys: 1 + 2 + 3 + 4 = 10 key visits
    layers = 2 * 16 * 218103808
    head = 2 * 134217728
    attn = 4 * 16 * 32 * 128 * 10
    assert costs.serve_request_flops(M7B, 3, 2) == 4 * layers + 2 * head \
        + attn


def test_lora_train_flops_per_token():
    got = costs.lora_train_flops_per_token(NEMO, 2048)
    dense = 4 * (10 * 272629760 + 5120 * 131072)
    attn = 3 * 4 * 10 * 32 * 128 * (2049 / 2)
    assert got == pytest.approx(dense + attn)
    # the trainer's own 6N count charges LoRA for weight gradients
    assert got < 6 * (10 * 272629760 + 5120 * 131072)


def test_kernel_costs_and_the_roofline():
    call = costs.paged_decode_call(M7B, rows=32, context=300)
    assert call["flops"] == 4 * 32 * 128 * 32 * 300
    assert call["bytes"] == 2 * 8 * 128 * 2 * 32 * 300 + 2 * 32 * 32 * 128 * 2
    peak = costs.peaks("TPU v5 lite")
    seconds, bound = costs.roofline_seconds(call, peak)
    assert bound == "memory"
    assert seconds == pytest.approx(call["bytes"] / 819e9)
    fwd = costs.flash_call(NEMO, 4, 2048, products=2, tensors=4)
    bwd = costs.flash_call(NEMO, 4, 2048, products=4, tensors=7)
    assert fwd["flops"] == 2 * 128 * 4 * 32 * 2048 * 2049 / 2 * 2
    assert bwd["flops"] == 2 * fwd["flops"]
    assert fwd["bytes"] == 4 * 4 * 32 * 2048 * 128 * 2
    assert costs.roofline_seconds(fwd, peak)[1] == "compute"
    with pytest.raises(KeyError):
        costs.peaks("TPU v9 imaginary")
