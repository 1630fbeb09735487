"""mfu (model step): the FLOPs the live rows needed in the traced window
(each row's own denoising steps and commit forward over its block, and
each admitted row's own prefill, counted by ``bench/counts.py``) over
the window times the chip's bf16 peak."""


def read(ctx):
    if ctx.window_s <= 0 or ctx.needed <= 0:
        return None
    return 100.0 * ctx.needed / (ctx.window_s * ctx.pk["bf16_flops"])
