"""FLOP and byte counts at both configurations' published shapes,
against values worked out by hand."""
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import counts  # noqa: E402


def _model(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())["model"]


def test_mamba2_train_flops():
    m = _model("mamba2-130m")
    # per layer 768*(2*1536 + 2*128 + 24) + 1536*768 = 3,753,984; x24
    # plus the tied head 768 * 50277
    assert counts.ssm_matmul_params(m) == 90_095_616 + 38_612_736
    # state map 4*24*64*128 + conv 2*4*(1536 + 256) per layer, x24, x3
    assert counts.ssm_train_flops_per_token(m) == \
        6 * 128_708_352 + 3 * 19_218_432


def test_int8_ring_codec_bytes():
    # k = 2 rows of the 129,057,216 parameters, chunk m = 64,528,608:
    # 2 (encode + decode-add) + 2 encodes + 2 decodes
    enc, dec_add, dec = 322_644_072, 580_758_496, 322_644_064
    assert counts.int8_ring_codec_bytes(129_057_216, 2) == \
        2 * (enc + dec_add) + 2 * enc + 2 * dec == 3_097_381_408
    # one more row: 3 x 2 hops of each, 3 encodes
    n, k = 3_000, 3
    m = 1_000
    e, da, d = 5 * m + 1032, 9 * m + 1024, 5 * m + 1024
    assert counts.int8_ring_codec_bytes(n, k) == 6 * (e + da) + 3 * e \
        + 6 * d


def test_internlm2_serving_counts():
    m = _model("internlm2-1.8b")
    # attention 2048*128*(16 + 2*8) + 16*128*2048, MLP 3*2048*8192, x24,
    # plus the head 2048 * 92544
    assert counts.dense_matmul_params(m) == 1_509_949_440 + 189_530_112
    assert counts.dense_prefill_flops(m, 1000) == \
        2 * 1_699_479_552 * 1000 + 4 * 24 * 16 * 128 * 500_500
    assert counts.dense_decode_flops(m, 1000) == \
        2 * 1_699_479_552 + 4 * 24 * 16 * 128 * 1000
    # K and V of 1000 positions of 8 heads of 128 in bf16, q and out
    assert counts.flash_decode_bytes(m, 1000) == \
        24 * (2 * 1000 * 8 * 128 * 2 + 2 * 16 * 128 * 2) == 98_500_608
