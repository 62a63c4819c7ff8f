"""Model FLOP/s utilization of serving: the forward FLOPs of the
prefills and decoded tokens of the window (bench/counts.py) over the
window, over the chips' bf16 peak."""


def read(rec):
    sv, pk = rec.get("serve"), rec.get("peaks")
    if not sv or not pk or sv["window_s"] <= 0:
        return None
    return 100.0 * sv["model_flops"] / sv["window_s"] / (
        rec["chips"] * pk["bf16_flops_per_s"])
