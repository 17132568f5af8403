"""The whole step's share of the chip's peak: matmul FLOPs the forward and
backward of a micro-step require (``counts.step_flops``, recompute not
counted) times micro-steps per second of the window, over the published bf16
peak times chips."""

from benchmarks import counts


def read(ctx):
    w = ctx["window"]
    rate = w["micro_steps"] / w["wall_s"]
    return 100.0 * counts.step_flops(ctx["shapes"]) * rate / (
        ctx["peaks"]["flops_bf16"] * ctx["chips"])
