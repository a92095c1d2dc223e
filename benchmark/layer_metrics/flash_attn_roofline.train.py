"""``flash_attn_roofline.train``: the least time the chip could take for
the traced steps' causal attention FORWARD (the larger of FLOPs over the
bf16 peak and bytes over the HBM peak, from shapes), over the device
time of the ``flash_attention`` forward kernel (``_flash_call``) in the
trace. The backward is no kernel today (``bwd_impl="xla"``: einsums over
recomputed probabilities); a Pallas backward (``_flash_bwd_call``) would
be a metric of its own. A trace that holds no such kernel reads
nothing."""

import sys

import flops
import trace_reduce

KERNELS = r"_flash_call"


def read(reduced, counters, ctx):
    if reduced is None or ctx.peak is None:
        return None
    seconds, calls = trace_reduce.op_seconds(reduced, KERNELS)
    steps = counters.get("traced_steps")
    if not calls or not steps:
        return None
    need = flops.flash_attention_fwd(
        ctx.model, int(ctx.traffic["batch"]), int(ctx.traffic["seq"]))
    least = flops.roofline(need["flops"], need["bytes"], ctx.peak)
    print(f"flash_attn_roofline.train: bound by {least['bound']}; "
          f"{calls} kernel calls, {seconds:.6f}s in {steps} steps",
          file=sys.stderr)
    return 100.0 * steps * ctx.model.n_layers * least["seconds"] / seconds
