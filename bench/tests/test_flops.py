"""Operation and byte counts against hand-worked numbers for the granite
configuration and for a decoder whose query is narrower than its model
width, as mistral-nemo-12b's (multiply-add = 2 operations, float32 = 4
bytes)."""
from pathlib import Path

import pytest

from bench import flops
from bench.harness import load_json

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


@pytest.fixture(scope="module")
def granite():
    return load_json(CONFIGS / "granite-3-8b-l8.json")


@pytest.fixture(scope="module")
def nemo():
    """mistral-nemo-12b's widths at 4 layers."""
    return {"hidden_size": 5120, "intermediate_size": 14336,
            "num_attention_heads": 32, "num_key_value_heads": 8,
            "head_dim": 128, "num_hidden_layers": 4, "vocab_size": 131072,
            "tie_word_embeddings": False}


def test_granite_weights(granite):
    # per layer: q 4096x4096, k and v 4096x1024, o 4096x4096, MLP 3x4096x12800
    layer = 16_777_216 + 2 * 4_194_304 + 16_777_216 + 157_286_400
    assert layer == 199_229_440
    assert flops.matmul_params(granite, head=False) == 8 * layer
    assert flops.matmul_params(granite) == 8 * layer + 4096 * 49155
    assert flops.kv_bytes_per_token(granite) == 8 * 2 * 1024 * 4


def test_nemo_weights_have_a_narrower_query(nemo):
    # q width 32 x 128 = 4096 under d_model 5120
    layer = 5120 * 4096 + 2 * 5120 * 1024 + 4096 * 5120 + 3 * 5120 * 14336
    assert layer == 272_629_760
    assert flops.matmul_params(nemo) == 4 * layer + 5120 * 131072
    assert flops.weight_bytes(nemo) == 7_046_430_720


def test_granite_prefill_of_512_tokens(granite):
    body = 2 * 1_593_835_520 * 512
    attn = 2 * 32 * 128 * 512 * 513 * 8          # causal: n(n+1)/2 keys, x2 ops, QK and PV
    head = 2 * 4096 * 49155                       # last token only
    assert flops.prefill_flops(granite, [512]) == body + attn + head
    assert body + attn + head == 1_649_703_673_856


def test_decode_and_paged_attention_need(granite, nemo):
    assert flops.decode_flops(granite, [100]) == \
        2 * 1_795_174_400 + 4 * 32 * 128 * 8 * 100
    need = flops.paged_attention_need(nemo, [10, 30])
    assert need["flops"] == 4 * 32 * 128 * 4 * 40
    # live keys and values, then query and output, per layer
    assert need["bytes"] == 4 * (2 * 1024 * 4 * 40 + 2 * 2 * 4096 * 4)


def test_least_seconds_takes_the_larger_bound():
    peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.least_seconds(200.0, 10.0, peaks) == 2.0
    assert flops.least_seconds(100.0, 50.0, peaks) == 5.0
