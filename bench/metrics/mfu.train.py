"""Model FLOP/s utilization of training: forward + backward FLOPs per
token (bench/counts.py) times the traced run's trained tokens per
second, over the chips' bf16 peak."""


def read(rec):
    tr, pk = rec.get("train"), rec.get("peaks")
    if not tr or not pk:
        return None
    return 100.0 * tr["flops_per_token"] * tr["tokens_per_s"] / (
        rec["chips"] * pk["bf16_flops_per_s"])
