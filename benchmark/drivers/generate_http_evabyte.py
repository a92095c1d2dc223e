"""Driver ``generate_http_evabyte``: ``drivers/generate_http.py`` for
the EVA block kind. ``ServingServer`` + ``DecodeScheduler`` over the
decoder ``serving.decode.decoder_for`` builds from an
``EvaByteConfig`` (``mmlspark_tpu/models/evabyte.py``), driven over
``POST /generate?stream=1`` by the same closed-loop clients, over the
same window, with the same reductions (all taken by import). What is
this driver's own: the system under test, the FLOP and row counts
(``flops_evabyte``), and the comparison that decides ``correct``.

``correct``: over ``check_requests`` finished requests drawn from the
seed, the longest among them, the plain reference
(``reference_evabyte``: float32, ``highest``, weights from the seed a
layer at a time) runs ONE full forward over each prompt with its served
bytes, and at every served position the gap by which the served byte's
reference logit (first prediction head) lies below the reference's
best is read; the limits' file names which of the widest gap, the mean
gap and the mean of its square are held to a limit. Prefill window by
window, compaction and decoding through the cache on one side; one
forward with the mask written out on the other.

``FAULTS``: ``token_altered`` breaks the timed path (every eighth
step's bytes replaced by their neighbours); ``summaries_dropped`` is
the fault of the mechanism, planted in the REFERENCE (its summary
terms left out of the softmax): a sound program then has to read as
not correct, which shows that the comparison sees the summaries.
``--control`` reads the reference one precision below the
configuration's (``control``: ``int8``), the gaps of the bytes it puts
first.

Configuration keys used: the source's sizes (``reference_evabyte.
Model.from_config``) and ``serve`` (``dtype``, ``n_slots``,
``max_len``, ``page_size``, ``n_pages``, ``attn_impl``,
``frame_model``). Traffic keys: as ``generate_http``.
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, List

import numpy as np

import flops_evabyte as F
import reference_evabyte as RE
import trace_reduce
import traffic as traffic_mod
from drivers import generate_http as G

FAULTS = ("token_altered", "summaries_dropped")
STEP_PROGRAM = r"^jit_eva_step$"


class Served(G.Served):
    """The server and everything it holds, for this block kind."""

    def __init__(self, ctx, m: RE.Model):
        import jax.numpy as jnp
        from mmlspark_tpu.models.evabyte import EvaByteConfig
        from mmlspark_tpu.models.nn import NNModel
        from mmlspark_tpu.models.zoo import ModelDownloader
        from mmlspark_tpu.serving import DecodeScheduler, ServingServer
        from mmlspark_tpu.serving.decode import decoder_for

        ctx.mark("import_model")
        sv = ctx.config["serve"]
        cfg = EvaByteConfig(
            vocab=m.vocab, d_model=m.d_model, n_heads=m.n_heads,
            d_head=m.d_head, d_ff=m.d_ff, n_layers=m.n_layers,
            window=m.window, chunk=m.chunk, n_pred_heads=m.n_pred_heads,
            rope_theta=m.rope_theta, norm_eps=m.norm_eps,
            init_std=m.init_std, dtype=sv["dtype"])
        # the weights as an input: the seed's, rounded to the
        # configuration's dtype a layer at a time
        self.params = RE.make_params(m, ctx.seed, jnp.dtype(sv["dtype"]))
        ctx.mark("weights")
        self.decoder = decoder_for(
            self.params, cfg, n_slots=int(sv["n_slots"]),
            max_len=int(sv["max_len"]), page_size=int(sv["page_size"]),
            n_pages=sv.get("n_pages"), attn_impl=sv["attn_impl"])
        self.warm_programs = self.decoder.warmup()
        ctx.mark("warmup")
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


def token_rows(m: RE.Model, r: Dict[str, Any], i: int):
    """``(summary_rows, window_rows)`` the step that produced streamed
    byte ``i >= 1`` of request ``r`` read: its query sits at position
    ``P + i - 1``."""
    return F.rows_at(m, len(r["prompt"]) + i - 1)


def window_model_flops(m: RE.Model, win: Dict[str, Any]) -> float:
    """The model's FLOPs for every byte that reached a client inside
    the window: byte 0 of a request is its prefill's, byte ``i`` a
    step's."""
    t0, t1 = win["t0"], win["t1"]
    total = 0.0
    for r in win["requests"]:
        for i, t in enumerate(r["t_tokens"]):
            if t0 <= t < t1:
                total += (F.prefill_flops(m, len(r["prompt"])) if i == 0
                          else F.decode_flops(m, 1, sum(token_rows(m, r, i))))
    return total


def traced_counters(m: RE.Model, win: Dict[str, Any]) -> Dict[str, Any]:
    """What the readers need about the traced slices: their bounds on
    the driver's clock, the counters' deltas, and from the clients'
    records the rows of each kind that the steps whose bytes arrived in
    them read."""
    if not win["slices"]:
        return {}
    sum_rows = win_rows = 0
    for r in win["requests"]:
        for i, t in enumerate(r["t_tokens"]):
            if i and any(sl["t0"] <= t < sl["t1"] for sl in win["slices"]):
                n_sum, n_win = token_rows(m, r, i)
                sum_rows += n_sum
                win_rows += n_win

    def delta(key):
        return sum(sl["stats1"].get(key, 0) - sl["stats0"].get(key, 0)
                   for sl in win["slices"])

    return {"traced_s": sum(sl["t1"] - sl["t0"] for sl in win["slices"]),
            "traced_slices": [[sl["t0"], sl["t1"]] for sl in win["slices"]],
            "traced_summary_rows": sum_rows, "traced_window_rows": win_rows,
            "traced_steps": delta("n_steps"),
            "traced_compactions": delta("n_compactions")}


def loop_stalls(ctx, floor_ms: float = 50.0) -> Dict[str, Any]:
    """The window's passes that ran a step and lasted over ``floor_ms``
    without a prefill to explain it (a step is under 20 ms): how many,
    their seconds in all, and the five longest with the phase that held
    the time. From the program's ``decode.pass`` spans; nothing where
    it records none."""
    from layer_metrics import decode_loop
    slow = [p for p in decode_loop.step_passes(ctx)
            if p["ms"] - p["prefill_ms"] > floor_ms]
    slow.sort(key=lambda p: -(p["ms"] - p["prefill_ms"]))
    return {"n": len(slow),
            "seconds": sum(p["ms"] - p["prefill_ms"] for p in slow) * 1e-3,
            "longest": [[round(p["ms"] - p["prefill_ms"], 1),
                         max(p["phases_ms"], key=p["phases_ms"].get)]
                        for p in slow[:5]]}


# ---------------------------------------------------------------------------
# correct


def gap_readings(m: RE.Model, seed: int, sample: List[Dict[str, Any]],
                 precision: str = "highest", own_argmax: bool = False,
                 drop_summaries: bool = False) -> Dict[str, Any]:
    """The reference once over each prompt with its served bytes (all
    the sample through each layer before the next layer's weights are
    made), and at every served position the gap by which the served
    byte's logit lies below the reference's best. ``own_argmax`` is the
    control's reading: the gaps of the bytes ``precision`` puts first,
    under the float32 reference's logits. ``drop_summaries`` plants the
    mechanism's fault in the reference."""
    seqs, rows = [], []
    for r in sample:
        served = np.asarray(r["streamed"], np.int32)
        seqs.append(np.concatenate([np.asarray(r["prompt"], np.int32),
                                    served[:-1]]))
        p_len = len(r["prompt"])
        rows.append(slice(p_len - 1, p_len - 1 + len(served)))
    ref = RE.served_logits(m, seed, seqs, rows, "highest", drop_summaries)
    low = (RE.served_logits(m, seed, seqs, rows, precision)
           if own_argmax else None)
    gaps = []
    for k, r in enumerate(sample):
        tokens = (low[k][:, :m.vocab].argmax(axis=-1) if own_argmax
                  else np.asarray(r["streamed"], np.int32))
        gaps.append(G.R.served_gaps(ref[k][:, :m.vocab], tokens))
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    longest = max((len(s) + 1 for s in seqs), default=0)
    return {"served_logit_gap_widest": float(g.max()) if len(g) else 0.0,
            "served_logit_gap_mean": float(g.mean()) if len(g) else 0.0,
            "served_logit_gap_mean_sq":
                float(np.square(g).mean()) if len(g) else 0.0,
            "tokens": int(len(g)), "tokens_below_best": int((g > 0).sum()),
            "requests": len(sample), "longest": int(longest),
            "boundaries_crossed": int(max(longest - 1, 0) // m.window)}


def run(ctx) -> Dict[str, Any]:
    import jax
    m = RE.Model.from_config(ctx.config)
    tf, sv = ctx.traffic, ctx.config["serve"]
    timed_fault = ctx.fault if ctx.fault == "token_altered" else None
    served = Served(ctx, m)
    facts: Dict[str, Any] = {"driver": "generate_http_evabyte",
                             "attn_impl": served.decoder.attn_impl,
                             "warm_programs": served.warm_programs,
                             "n_params": RE.n_params(m)}
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
    facts["largest_body_bytes"] = max(len(r["wire"]) for r in plan)
    # two requests through the whole path before the clock starts: the
    # block's median prompt (past its first window: the prefill walks
    # and compacts)
    first = traffic_mod.warm_request(tf, m.vocab, ctx.seed)
    # and the block's longest prompt with its own ids, so that the
    # largest body the window will send has been through the edge
    longest = max(len(r["prompt"]) for r in plan)
    big = {"prompt": np.random.default_rng([int(ctx.seed), 2]).integers(
               0, m.vocab, size=longest).astype(np.int32),
           "max_new_tokens": first["max_new_tokens"]}
    for r in (first, big):
        r["wire"] = G.wire(path, served.host, r)
    warm = G.ClosedLoop(served.host, served.port, [first, big], 1)
    warm.start()
    warm.join(timeout=300.0)
    if warm.error is not None or not all(G.ok(r) for r in warm.sent) \
            or len(warm.sent) != 2:
        raise RuntimeError(f"a warm-up request failed: {warm.error!r} "
                           f"{[r.get('final') for r in warm.sent]}")

    ctx.mark("warm_request")
    win = G.measure(ctx, served, plan)
    setup_s = win["t0"] - ctx.t_start
    facts["setup_phases_s"] = dict(ctx.phases, ramp=setup_s)
    e2e = G.reduce_window(win, ctx.seconds, ctx.model)
    s0, s1, s2 = win["stats0"], win["stats1"], win["stats2"]
    tails = {k: e2e[k] for k in sorted(e2e) if k.endswith("_ms")}
    counters = {**traced_counters(m, win), **tails, "window_s": ctx.seconds,
                "window_model_flops": window_model_flops(m, win),
                "window_steps": s1["n_steps"] - s0["n_steps"],
                "window_prefills": s1["n_prefills"] - s0["n_prefills"],
                "window_server_tokens": s1["n_tokens"] - s0["n_tokens"],
                "window_compactions":
                    s1["n_compactions"] - s0["n_compactions"],
                "n_slots": int(sv["n_slots"])}
    memory_peak = max(int((d.memory_stats() or {})
                          .get("peak_bytes_in_use", 0))
                      for d in jax.devices())
    facts.update(
        n_step_faults=s2["n_step_faults"], n_compiles=s2["n_compiles"],
        compiles_before=s0["n_compiles"], releases=s2["releases"],
        requests_sent=len(win["requests"]),
        attempted=e2e["attempted"], n_gaps=e2e["n_gaps"],
        window_steps=counters["window_steps"],
        window_prefills=counters["window_prefills"],
        window_compactions=counters["window_compactions"],
        slots_at_opening=s0["slots_in_use"],
        slots_high_water=s2["slots_high_water"],
        page_high_water=s2["pages"]["high_water"],
        n_page_preempts=s2["pages"]["n_preempts"],
        tails_ms=tails)
    facts["loop_stalls"] = loop_stalls(ctx)
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
                       drop_summaries=ctx.fault == "summaries_dropped")
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
