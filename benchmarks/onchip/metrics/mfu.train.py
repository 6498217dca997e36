"""Model FLOP utilisation of the train step: 6 N FLOPs per trained token
(``counts.train_flops_per_token``) times the window's trained tokens per
second, over the chip's bf16 peak, in %."""
from counts import train_flops_per_token
from peaks import peaks


def read(ctx):
    rate = ctx.tokens / ctx.window_s
    flops = train_flops_per_token(ctx.cell.config["sizes"]) * rate
    return 100.0 * flops / peaks(ctx.device_kind)["flops_bf16"]
