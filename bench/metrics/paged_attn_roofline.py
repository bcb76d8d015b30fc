"""The paged decode kernel's share of its roofline: the least time the
chip could take for the attention of every live lane at every decode
step of the traced window (the larger of its FLOPs over the bf16 peak
and its K/V bytes, at the pool's dtype, over HBM bandwidth), over the
kernel's summed device time inside the decode-loop program."""

from bench import roofline

PROGRAM = "decode_loop"
KERNEL = "paged_attention"


def read(x):
    w = x.window
    t = x.summary.op_seconds(KERNEL, PROGRAM)
    if t <= 0 or w.lane_steps == 0:
        return None
    flops = roofline.attention_flops(x.model, 1) * w.decode_ctx
    nbytes = roofline.paged_decode_bytes(x.model, x.pool_dtype, w.decode_ctx)
    least = max(flops / x.peaks.bf16_flops, nbytes / x.peaks.hbm_bytes_s)
    return 100.0 * least / t
