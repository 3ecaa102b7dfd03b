"""The operation counts against hand arithmetic at Mistral-7B's widths."""

import json
import os

from opcount import model_step

from conftest import BENCH

CFG = json.load(open(os.path.join(
    BENCH, "configs", "mistral-7b-v0.3-serve.json")))


def test_layer_parameters_by_hand():
    # wq 4096x4096, wk and wv 4096x1024, wo 4096x4096, three 4096x14336
    by_hand = (4096 * 4096 + 2 * 4096 * 1024 + 4096 * 4096
               + 3 * 4096 * 14336)
    assert by_hand == 218_103_808
    assert model_step.layer_matmul_params(CFG) == by_hand
    # depth 8 and the 4096 x 32768 head: 1.879 B matmul parameters
    assert model_step.matmul_params(CFG) == 8 * by_hand + 4096 * 32768


def test_decode_token_by_hand():
    # one token that sees 500 keys: 2 ops per parameter, plus QK^T and PV
    # (2 x 2 x 32 heads x 128) per key per layer, plus the head
    by_hand = (8 * (2 * 218_103_808 + 4 * 32 * 128 * 500)
               + 2 * 4096 * 32768)
    assert model_step.token_flops(CFG, 500) == by_hand


def test_prefill_counts_half_the_square():
    n = 330
    attn = 4 * 32 * 128 * n * (n + 1) / 2
    by_hand = 8 * (2 * 218_103_808 * n + attn) + 2 * 4096 * 32768
    assert model_step.prefill_flops(CFG, n) == by_hand


def test_training_row_is_three_forwards():
    s = 2048
    fwd = (8 * (2 * 218_103_808 * s + 4 * 32 * 128 * s * (s + 1) / 2)
           + 2 * 4096 * 32768 * (s - 1))
    assert model_step.train_flops_per_row(CFG, s) == 3 * fwd
    # ~11.7 GFLOP a token, the issue's figure
    assert 11.0e9 < model_step.train_flops_per_row(CFG, s) / s < 12.5e9
