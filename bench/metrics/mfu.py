"""The whole step's share of the chip's bf16 peak: useful model FLOPs of
the prompt tokens ingested and the tokens generated in the traced
window, over (window x peak).  Per token: 2 per matmul parameter, LM
head included, plus causal attention at the token's context; counted
from the configuration and the lengths the harness sent, so padding
and dead lanes never count."""

from bench import roofline


def read(x):
    w = x.window
    tokens = w.prefill_tokens + w.lane_steps
    if tokens == 0:
        return None
    flops = (2 * roofline.matmul_params(x.model) * tokens
             + roofline.attention_flops(x.model, 1)
             * (w.prefill_ctx + w.decode_ctx))
    return 100.0 * flops / (x.summary.window_s * x.peaks.bf16_flops)
