"""``mfu.serve``: the model's FLOPs for every prompt prefilled and every
token generated whose token reached a client inside the window
(``flops.prefill_flops``, ``flops.decode_flops``: from the lengths, not
from the padded buckets), over the window times the bf16 peak."""


def read(reduced, counters, ctx):
    if ctx.peak is None or not counters.get("window_model_flops"):
        return None
    return (100.0 * counters["window_model_flops"]
            / (counters["window_s"] * ctx.peak["bf16_flops_per_s"]))
