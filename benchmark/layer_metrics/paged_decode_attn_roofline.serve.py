"""``paged_decode_attn_roofline.serve``: the K/V bytes a decode step had
to read (the live lengths the traced slices' tokens attended over, a
step's mean, all layers, float32: ``flops.kv_bytes``) over the HBM peak,
against the device time of one step's ``paged_decode_attention`` kernel
calls in the trace. Memory-bound by construction (one query row a
slot)."""

import flops
import trace_reduce

KERNEL = r"paged_decode_attention"


def read(reduced, counters, ctx):
    if reduced is None or ctx.peak is None:
        return None
    seconds, calls = trace_reduce.op_seconds(reduced, KERNEL)
    if not calls or not counters.get("traced_steps"):
        return None
    rows_a_step = counters["traced_positions"] / counters["traced_steps"]
    steps_seen = calls / ctx.model.n_layers
    least = (steps_seen * flops.kv_bytes(ctx.model, rows_a_step)
             / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
