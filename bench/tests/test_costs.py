import json
import os

from bench import costs, harness

from conftest import ROOT

OLMO = json.load(open(os.path.join(ROOT, "bench", "configs", "olmo-1b.json")))
DENSE = harness.arch(OLMO)      # the dense transformer's least FLOPs and bytes


def test_one_olmo_layer_by_hand():
    # q, k, v, o: 4 x 2048 x (16 x 128); SwiGLU gate, up, down: 3 x 2048 x 8192
    assert DENSE.layer_params(OLMO) == 4 * 2048 * 2048 + 3 * 2048 * 8192 == 67_108_864
    assert DENSE.body_params(OLMO) == 16 * 67_108_864
    assert DENSE.head_params(OLMO) == 2048 * 50304
    # one token at context 100: 2 FLOPs per weight, 4 * d_head * heads per key
    one_layer = dict(OLMO, n_layers=1)
    f = DENSE.forward_flops(one_layer, 1, 100, 0)
    assert f == 2 * 67_108_864 + 4 * 16 * 128 * 100


def test_causal_sum():
    assert costs.causal_sum(0, 3) == 1 + 2 + 3
    assert costs.causal_sum(10, 2) == 11 + 12
    # a prompt of 4 and its head at the last position, by hand
    f = DENSE.forward_flops(OLMO, 4, costs.causal_sum(0, 4), 1)
    assert f == 2 * 16 * 67_108_864 * 4 + 4 * 16 * 16 * 128 * 10 + 2 * 2048 * 50304


def test_decode_least_bytes():
    need = DENSE.decode_least(OLMO, [10, 20])
    kv = 2 * 16 * 16 * 128 * 2               # k and v, 16 layers, 16 heads x 128, bf16
    assert DENSE.kv_bytes_per_token(OLMO) == kv
    assert need["bytes"] == 4 * (DENSE.body_params(OLMO) + DENSE.head_params(OLMO)) + kv * 30
    assert need["flops"] == 2 * 2 * (DENSE.body_params(OLMO) + DENSE.head_params(OLMO)) \
        + 4 * 16 * 16 * 128 * 30
