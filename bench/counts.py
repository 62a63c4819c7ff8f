"""Operations and bytes of the work, computed from the configuration's
shapes. The same work gives the same count whatever implements it:
model FLOPs count the forward and backward passes once (no recompute),
and a kernel's bytes are what its operation must read and write once.

Configuration dicts are the files under ``bench/configs``.
"""
from __future__ import annotations

# -- Mamba-2 (training) --------------------------------------------------------


def ssm_dims(cfg: dict) -> dict:
    d = cfg["d_model"]
    di = cfg["expand"] * d
    gn = cfg["ngroups"] * cfg["d_state"]
    return {"d": d, "di": di, "gn": gn, "h": di // cfg["headdim"],
            "p": cfg["headdim"], "n": cfg["d_state"], "k": cfg["d_conv"]}


def ssm_matmul_params(cfg: dict) -> int:
    """Weights that multiply each token: the layers' projections and
    the (tied) LM head over the vocabulary."""
    s = ssm_dims(cfg)
    per_layer = s["d"] * (2 * s["di"] + 2 * s["gn"] + s["h"]) \
        + s["di"] * s["d"]
    return cfg["n_layer"] * per_layer + s["d"] * cfg["vocab_size"]


def ssm_train_flops_per_token(cfg: dict) -> float:
    """Forward + backward FLOPs per trained token: 6 per matmul weight,
    plus three times the forward of the causal convolutions and of the
    state-space map in its recurrent form (update and read-out of the
    H x P x N state: 4 H P N per token)."""
    s = ssm_dims(cfg)
    fwd_other = cfg["n_layer"] * (4 * s["h"] * s["p"] * s["n"]
                                  + 2 * s["k"] * (s["di"] + 2 * s["gn"]))
    return 6.0 * ssm_matmul_params(cfg) + 3.0 * fwd_other


def int8_ring_codec_bytes(n: int, k: int) -> int:
    """Bytes the int8 codec must move in one ring all-reduce of ``k``
    rows of ``n`` float32 elements, chunk ``m = n / k``: an encode reads
    the float32 chunk and writes its int8 codes and the 256-entry
    codebook with the range; a decode reads the codes and codebook and
    writes float32 (a decode-add also reads the float32 accumulator).
    Reduce-scatter: (k-1) hops x k rows of encode + decode-add;
    all-gather: k encodes of the reduced chunks, then (k-1) hops x k
    rows of decode."""
    m = -(-n // k)
    book = 256 * 4
    enc = 4 * m + m + book + 8
    dec_add = m + book + 4 * m + 4 * m
    dec = m + book + 4 * m
    return (k - 1) * k * (enc + dec_add) + k * enc + (k - 1) * k * dec


# -- dense transformer (serving) ----------------------------------------------


def dense_dims(cfg: dict) -> dict:
    d, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"d": d, "nh": nh, "nkv": cfg["num_key_value_heads"],
            "hd": d // nh, "ff": cfg["intermediate_size"],
            "L": cfg["num_hidden_layers"], "v": cfg["vocab_size"]}


def dense_matmul_params(cfg: dict) -> int:
    s = dense_dims(cfg)
    attn = s["d"] * s["hd"] * (s["nh"] + 2 * s["nkv"]) \
        + s["nh"] * s["hd"] * s["d"]
    return s["L"] * (attn + 3 * s["d"] * s["ff"]) + s["d"] * s["v"]


def dense_prefill_flops(cfg: dict, prompt_len: int) -> float:
    """Forward FLOPs of a prompt: 2 per weight per token, and causal
    attention (QK and PV, 4 head_dim FLOPs per query-key pair per
    head)."""
    s = dense_dims(cfg)
    pairs = prompt_len * (prompt_len + 1) / 2
    return 2.0 * dense_matmul_params(cfg) * prompt_len \
        + 4.0 * s["L"] * s["nh"] * s["hd"] * pairs


def dense_decode_flops(cfg: dict, context: int) -> float:
    """Forward FLOPs of one decoded token attending ``context``
    positions."""
    s = dense_dims(cfg)
    return 2.0 * dense_matmul_params(cfg) \
        + 4.0 * s["L"] * s["nh"] * s["hd"] * context


def flash_decode_bytes(cfg: dict, context: int, kv_bytes: int = 2) -> int:
    """Bytes one decoded token's attention must move, over all layers:
    K and V of the ``context`` live positions, the query and the
    output."""
    s = dense_dims(cfg)
    kv = 2 * context * s["nkv"] * s["hd"] * kv_bytes
    qo = 2 * s["nh"] * s["hd"] * kv_bytes
    return s["L"] * (kv + qo)
