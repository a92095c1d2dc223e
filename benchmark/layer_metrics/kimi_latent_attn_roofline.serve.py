"""``kimi_latent_attn_roofline.serve``: one call of
``paged_latent_decode_attention`` (six a step, one a layer) at its
least time, against the kernel's device time a call in the trace. The
least time is the larger of the live latent rows' UNPADDED bytes (the
traced steps' ``window_rows``, a step's mean, times ``flops_kimi.
latent_row_bytes``: 1,152 B in bfloat16) at the HBM peak and their
FLOPs (every head's score over 576 lanes and sum of 512: ``flops_kimi.
kernel_flops_per_row``) at the bf16 peak. The pool keeps a row at 640
lanes, so a walk that reads every byte it copies at the peak reads 90%
here."""

import flops_kimi as F
import trace_reduce
from layer_metrics import decode_loop, kimi_cell

KERNEL = r"paged_latent_decode_attention"


def read(reduced, counters, ctx):
    if reduced is None or ctx.peak is None:
        return None
    m = kimi_cell.model(ctx)
    steps = kimi_cell.traced_steps(ctx, counters)
    seconds, calls = trace_reduce.op_seconds(reduced, KERNEL)
    if m is None or not steps or not calls:
        return None
    rows = decode_loop.mean(p["window_rows"] for p in steps)
    return 100.0 * F.kernel_least_s(m, rows, ctx.peak) / (seconds / calls)
