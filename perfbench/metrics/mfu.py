"""Model FLOPs utilization of the traced window: model FLOPs per token
(`yardstick.flops`, no recomputation) x tokens/s over chips x peak."""
from yardstick import flops


def read(t):
    peak = flops.peak_flops(t.device_kind)
    return 100.0 * t.flops_per_token * t.tokens_per_s / (t.chips * peak)
