"""paged_attn_roofline (kernels): the time the paged block-attention
kernel's calls need at the roofline (the larger of their FLOPs over the
bf16 peak and their bytes over HBM bandwidth, per call, from
``bench/counts.py``) over the kernel's device time in the trace."""


def read(ctx):
    return ctx.roofline("paged_block_attention")
