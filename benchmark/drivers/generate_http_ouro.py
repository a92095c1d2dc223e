"""Driver ``generate_http_ouro``: ``drivers/generate_http.py`` for a
looped softmax stack (``total_ut_steps`` passes over one set of layers,
a K/V row a (pass, layer, position)). ``ServingServer`` +
``DecodeScheduler`` over the decoder ``serving.decode.decoder_for``
builds from ``TransformerConfig.from_hf`` of the configuration file
(the softmax block's own ``TransformerDecoder``: what differs is what
the program builders read from the configuration), driven over ``POST
/generate?stream=1`` by the same closed-loop clients, over the same
window, with the same reductions (all taken by import). What is this
driver's own: the system under test, the FLOP and byte counts
(``flops_ouro``), and the comparison that decides ``correct``.

``correct``: over ``check_requests`` finished requests drawn from the
seed, the longest among them, the plain reference (``reference_ouro``:
float32, ``highest``, weights from the seed a layer at a time and again
in every pass, no cache) runs ONE full forward over each prompt with
its served tokens, and at every served position the gap by which the
served token's reference logit lies below the reference's best is
read; the limits' file names which of the widest gap, the mean gap and
the mean of its square are held to a limit. Prefill by bucket and
decoding through the (pass, layer) cache on one side; one forward on
the other. The program's state is freed before the reference runs.

``FAULTS``: ``token_altered`` breaks the timed path (every eighth
step's tokens replaced by their neighbours); ``loop_dropped`` and
``loop_cache_shared`` are the faults of the mechanism, planted in the
REFERENCE (one pass fewer; every pass attending the first pass's K and
V rows: a cache keyed by layer alone): a sound program then has to read
as not correct, which shows that the comparison sees every pass and the
rows each pass keeps. ``--control`` reads the reference one precision
below the configuration's (``control``: ``int8``), the gaps of the
tokens it puts first.

Configuration keys used: the source's sizes and ``init``
(``reference_ouro.Model.from_config``; the program reads the same keys
through ``TransformerConfig.from_hf``) and ``serve`` (``dtype``,
``n_slots``, ``max_len``, ``page_size``, ``attn_impl``,
``prompt_buckets``, ``prefix_cache``, ``frame_model``). Traffic keys:
as ``generate_http``.
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, List, Optional

import numpy as np

import flops_ouro as F
import reference_ouro as RO
import trace_reduce
import traffic as traffic_mod
from drivers import generate_http as G
# the memory readings and the traced slices' bounds are any paged
# decoder's: the Granite driver's, by import
from drivers.generate_http_granite import device_bytes, traced_counters

FAULTS = ("token_altered",) + RO.FAULTS
STEP_PROGRAM = r"^jit_looped_step$"


class Served(G.Served):
    """The server and everything it holds, for this configuration."""

    def __init__(self, ctx, m: RO.Model):
        import jax
        import jax.numpy as jnp
        from mmlspark_tpu.models.nn import NNModel
        from mmlspark_tpu.models.transformer import TransformerConfig
        from mmlspark_tpu.models.zoo import ModelDownloader
        from mmlspark_tpu.serving import DecodeScheduler, ServingServer
        from mmlspark_tpu.serving.decode import decoder_for

        ctx.mark("import_model")
        sv = ctx.config["serve"]
        # a program without the looped recipe fails here, at once
        cfg = TransformerConfig.from_hf(ctx.config, dtype=sv["dtype"])
        # the weights as an input: the seed's, rounded to the
        # configuration's dtype a layer at a time
        self.params = jax.block_until_ready(
            RO.make_params(m, ctx.seed, jnp.dtype(sv["dtype"])))
        ctx.mark("weights")
        #: device memory as each phase of set-up left it
        self.memory = {"weights": device_bytes()}
        self.decoder = decoder_for(
            self.params, cfg, n_slots=int(sv["n_slots"]),
            max_len=int(sv["max_len"]), page_size=int(sv["page_size"]),
            attn_impl=sv["attn_impl"], prefix_cache=sv["prefix_cache"],
            prompt_buckets=sv["prompt_buckets"])
        self.warm_programs = self.decoder.warmup()
        ctx.mark("warmup")
        self.memory["warmup"] = device_bytes()
        if ctx.fault == "token_altered":
            self._alter_tokens(m.vocab)
        dl = ModelDownloader(os.path.join(ctx.root, ".zoo_cache"),
                             repo=os.path.join(ctx.root, "zoo"))
        meta = dl.list_models()[sv["frame_model"]]
        frame = NNModel(model=dl.load(sv["frame_model"]),
                        input_col="image", output_col="scores",
                        input_dtype=meta.input_dtype)
        self.sched = DecodeScheduler(self.decoder)
        self.server = ServingServer(frame, port=0, decoder=self.sched)
        self.server.start()
        self.host, self.port = self.server.host, self.server.port
        ctx.mark("server")

    def close(self) -> None:
        """Stop the server and free everything the program holds on the
        device, the weights too: the reference makes its own."""
        self.decoder.params = self.params = None
        super().close()


# ---------------------------------------------------------------------------
# counts


def window_model_flops(m: RO.Model, win: Dict[str, Any]) -> float:
    """The model's FLOPs, every pass, for every token that reached a
    client inside the window: token 0 of a request is its prefill's,
    token ``i`` a step's, whose query reads ``P + i`` positions."""
    t0, t1 = win["t0"], win["t1"]
    total = 0.0
    for r in win["requests"]:
        p_len = len(r["prompt"])
        for i, t in enumerate(r["t_tokens"]):
            if t0 <= t < t1:
                total += (F.prefill_flops(m, p_len) if i == 0
                          else F.decode_flops(m, 1, p_len + i))
    return total


# ---------------------------------------------------------------------------
# correct


def gap_readings(m: RO.Model, seed: int, sample: List[Dict[str, Any]],
                 precision: str = "highest", own_argmax: bool = False,
                 fault: Optional[str] = None) -> Dict[str, Any]:
    """The reference once over each prompt with its served tokens (all
    the sample through each layer before the next layer's weights are
    made), and at every served position the gap by which the served
    token's logit lies below the reference's best. ``own_argmax`` is
    the control's reading: the gaps of the tokens ``precision`` puts
    first, under the float32 reference's logits. ``fault`` plants a
    fault of the mechanism in the reference."""
    seqs, rows = [], []
    for r in sample:
        served = np.asarray(r["streamed"], np.int32)
        seqs.append(np.concatenate([np.asarray(r["prompt"], np.int32),
                                    served[:-1]]))
        p_len = len(r["prompt"])
        rows.append(slice(p_len - 1, p_len - 1 + len(served)))
    ref = RO.served_logits(m, seed, seqs, rows, "highest", fault)
    low = (RO.served_logits(m, seed, seqs, rows, precision)
           if own_argmax else None)
    gaps = []
    for k, r in enumerate(sample):
        tokens = (low[k].argmax(axis=-1) if own_argmax
                  else np.asarray(r["streamed"], np.int32))
        gaps.append(G.R.served_gaps(ref[k], tokens))
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    return {"served_logit_gap_widest": float(g.max()) if len(g) else 0.0,
            "served_logit_gap_mean": float(g.mean()) if len(g) else 0.0,
            "served_logit_gap_mean_sq":
                float(np.square(g).mean()) if len(g) else 0.0,
            "tokens": int(len(g)), "tokens_below_best": int((g > 0).sum()),
            "requests": len(sample),
            "longest": int(max((len(s) + 1 for s in seqs), default=0))}


def run(ctx) -> Dict[str, Any]:
    m = RO.Model.from_config(ctx.config)
    tf, sv = ctx.traffic, ctx.config["serve"]
    timed_fault = ctx.fault if ctx.fault == "token_altered" else None
    served = Served(ctx, m)
    facts: Dict[str, Any] = {"driver": "generate_http_ouro",
                             "attn_impl": served.decoder.attn_impl,
                             "warm_programs": served.warm_programs,
                             "n_params": RO.n_params(m)}
    if ctx.on_chip and served.decoder.attn_impl != "pallas":
        raise RuntimeError(f"attn_impl 'auto' resolved to "
                           f"{served.decoder.attn_impl!r} on the chip")
    per_block = int(tf["sizes_per_block"])
    n_blocks = int(np.ceil((ctx.seconds + float(tf["ramp_s"]) + 10.0)
                           * float(tf.get("max_requests_per_s", 40.0))
                           / per_block))
    plan = traffic_mod.requests(tf, m.vocab, ctx.seed, n_blocks)
    path = tf["path"]
    for r in plan:
        r["wire"] = G.wire(path, served.host, r)
    # one request through the whole path before the clock starts (one
    # that is in no plan)
    first = traffic_mod.warm_request(tf, m.vocab, ctx.seed)
    first["wire"] = G.wire(path, served.host, first)
    warm = G.ClosedLoop(served.host, served.port, [first], 1)
    warm.start()
    warm.join(timeout=300.0)
    if warm.error is not None or len(warm.sent) != 1 \
            or not G.ok(warm.sent[0]):
        raise RuntimeError(f"the warm-up request failed: {warm.error!r} "
                           f"{[r.get('final') for r in warm.sent]}")

    ctx.mark("warm_request")
    win = G.measure(ctx, served, plan)
    setup_s = win["t0"] - ctx.t_start
    facts["setup_phases_s"] = dict(ctx.phases, ramp=setup_s)
    e2e = G.reduce_window(win, ctx.seconds, ctx.model)
    s0, s1, s2 = win["stats0"], win["stats1"], win["stats2"]
    tails = {k: e2e[k] for k in sorted(e2e) if k.endswith("_ms")}
    counters = {**traced_counters(win), **tails, "window_s": ctx.seconds,
                "window_model_flops": window_model_flops(m, win),
                "window_steps": s1["n_steps"] - s0["n_steps"],
                "window_prefills": s1["n_prefills"] - s0["n_prefills"],
                "window_server_tokens": s1["n_tokens"] - s0["n_tokens"],
                "window_prompt_tokens":
                    s1["n_prompt_tokens"] - s0["n_prompt_tokens"],
                "n_slots": int(sv["n_slots"])}
    served.memory["window"] = device_bytes()
    memory_peak = served.memory["window"]["peak"]
    facts.update(
        n_step_faults=s2["n_step_faults"], n_compiles=s2["n_compiles"],
        compiles_before=s0["n_compiles"], releases=s2["releases"],
        requests_sent=len(win["requests"]),
        attempted=e2e["attempted"], n_gaps=e2e["n_gaps"],
        window_steps=counters["window_steps"],
        window_prefills=counters["window_prefills"],
        window_prompt_tokens=counters["window_prompt_tokens"],
        n_loops=s2["n_loops"],
        kv_bytes_per_position=s2["kv_bytes_per_position"],
        pool_bytes=s2["pages"]["pool_bytes"],
        slots_at_opening=s0["slots_in_use"],
        slots_high_water=s2["slots_high_water"],
        page_high_water=s2["pages"]["high_water"],
        n_page_preempts=s2["pages"]["n_preempts"],
        memory_phases=served.memory, tails_ms=tails)
    if ctx.fault:
        facts["fault_planted_in"] = ("timed path" if timed_fault
                                     else "reference")
    if timed_fault:
        facts["altered_steps"] = served.altered_steps
    sound = (s2["n_step_faults"] == 0
             and s2["n_compiles"] == s0["n_compiles"] == served.warm_programs
             and all(G.ok(r) for r in win["requests"])
             and set(s2["releases"]) <= {"length"})
    served.close()
    del served

    reduced = None
    if ctx.trace:
        parts = [trace_reduce.read_and_remove(
            os.path.join(ctx.trace_dir, str(k)), ctx.on_chip,
            tf.get("trace_hole_s")) for k in range(len(win["slices"]))]
        if all(p is not None for p in parts) and parts:
            reduced = trace_reduce.combine(parts)
            seen = trace_reduce.module_seconds(reduced, STEP_PROGRAM)[1]
            facts.update(trace_holes=reduced["holes"],
                         trace_steps_seen=seen,
                         trace_steps_counted=counters["traced_steps"])

    t_ref = time.perf_counter()
    sample = G.sample_requests(win["requests"], ctx.seed,
                               int(tf["check_requests"]))
    got = gap_readings(m, ctx.seed, sample,
                       fault=ctx.fault if ctx.fault in RO.FAULTS else None)
    compared = G.compare(got, ctx.limits)
    out: Dict[str, Any] = {}
    if ctx.control:
        ctl = gap_readings(m, ctx.seed, sample, ctx.config["control"],
                           own_argmax=True)
        c_cmp = G.compare(ctl, ctx.limits)
        out["control"] = {"correct": G.is_correct(c_cmp), "compared": c_cmp,
                          "readings": ctl}
    facts.update(reference_s=time.perf_counter() - t_ref,
                 readings=got, sound=sound)
    out.update({
        "correct": bool(sound and sample and G.is_correct(compared)),
        "attempted": e2e["attempted"], "failed": e2e["failed"],
        "end_to_end": {k: v for k, v in e2e.items()
                       if k.endswith(("_ms", "_per_s"))}
        | {"setup_s": setup_s},
        "counters": counters, "reduced": reduced,
        "memory_peak_bytes": memory_peak, "compared": compared,
        "facts": facts})
    return out
