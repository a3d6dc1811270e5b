"""The whole round's share of the chips' bf16 peak: counted FLOPs of the
rounds the untraced window completed (bench/flops.py) over its seconds on
the host clock, the chips and the peak of their ``device_kind``."""


def read(ctx):
    if not ctx["rounds"]:
        return None
    return (100.0 * ctx["rounds"] * ctx["flops_per_round"]
            / (ctx["window_s"] * ctx["chips"] * ctx["peak"]["bf16_flops"]))
