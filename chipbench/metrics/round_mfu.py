"""The whole round's share of the chip's bf16 peak: model FLOPs of a
round's local training (``flops.round_model_flops``), over the traced
run's time per round, over the peak."""


def read(ctx):
    if not ctx["rounds"] or not ctx["window_s"]:
        return None
    per_round_s = ctx["window_s"] / ctx["rounds"]
    return 100.0 * ctx["round_model_flops"] / per_round_s \
        / ctx["peaks"]["bf16_flops_per_s"]
