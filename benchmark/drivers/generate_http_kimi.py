"""Driver ``generate_http_kimi``: ``drivers/generate_http.py`` for Kimi-K2's
language model, a configuration of the hybrid block whose every layer is
multi-head latent attention over a paged latent cache. ``ServingServer``
+ ``DecodeScheduler`` over the decoder ``serving.decode.decoder_for``
builds from the ``GraniteHybridConfig`` the program's own ``from_hf``
reads off the configuration file (``kimi_k2``), driven over ``POST
/generate?stream=1`` by the same closed-loop clients, with the same
reductions (all taken by import). What is this driver's own: the weights
(``reference_kimi.make_params``), the FLOP and byte counts
(``flops_kimi``), the window's wait for the requests in flight at its
end (``drain_s``: an answer of 6,144 tokens outlasts the shared
driver's two minutes at a step of 20 ms), and the comparison that
decides ``correct``.

``correct``: over ``check_requests`` finished requests drawn from the
seed, the longest among them, the plain reference (``reference_kimi``:
float32, ``highest``, weights from the seed a layer and an expert at a
time, the attention expanded) runs ONE full forward over each prompt
with its served tokens, and at every served position the gap by which
the served token's reference logit lies below the reference's best is
read; the limits' file names which of the widest gap, the mean gap and
the mean of its square are held to a limit.

``FAULTS``: ``token_altered`` breaks the timed path (every eighth
step's tokens replaced by their neighbours); ``latent_rope_dropped``
(the decode step's latent kernel ignores the rotary key: the query's
rotary lanes zeroed on the way in) and ``yarn_mscale_dropped`` (the
softmax scale without YaRN's ``mscale ** 2``) are planted in the
PROGRAM; ``expert_bias_ignored`` (the experts chosen by the sigmoid
score alone) and ``shared_expert_dropped`` in the REFERENCE. A sound
comparison reads each as not correct: it sees the rotary part of the
absorbed scores, the scale, the bias's selections and the shared
expert. ``--control`` reads the reference one precision below the
configuration's (``control``: ``int8``), the gaps of the tokens it puts
first, and each fault the reference carries over the same served
tokens (under ``control``'s ``reference_faults``).

The plan: every seed sends the same requests in the same order, the
traffic's block of sizes in the order its ``sizes_seed`` draws; the
seed draws the token ids (and the weights). A window of this cell
holds only some 32 sends (answers of thousands of tokens keep the
clients), and a walk of tiles sets a first token's time in steps of a
tile: under an order of the seed's own, which sizes the window's
``ttft_p50_ms`` was read over was the seed's draw, and on one v5e the
middle half of six seeds' readings spread by some 17% of their median.

On a program without the latent layer (the parent commit) the
configuration is refused before any weight is made: the run exits
non-zero at once.

Configuration keys used: the source's (``GraniteHybridConfig.from_hf``
over ``reference_kimi.program_hf``, ``reference_kimi.Model.from_config``)
and ``serve`` (``dtype``, ``n_slots``, ``max_len``, ``page_size``,
``n_pages``, ``attn_impl``, ``prefill_tile``, ``frame_model``). Traffic
keys: as ``generate_http``, and ``drain_s``.
"""

from __future__ import annotations

import dataclasses
import os
import shutil
import time
from typing import Any, Dict, List, Optional

import numpy as np

import flops_kimi as F
import reference_kimi as RK
import trace_reduce
import traffic as traffic_mod
from drivers import generate_http as G
from drivers import generate_http_granite as GG

PROGRAM_FAULTS = ("latent_rope_dropped", "yarn_mscale_dropped")
FAULTS = ("token_altered",) + PROGRAM_FAULTS + RK.FAULTS
STEP_PROGRAM = GG.STEP_PROGRAM


def program_config(config: Dict[str, Any], dtype: str,
                   fault: Optional[str] = None):
    """The program's configuration, read by its own ``from_hf``;
    ``yarn_mscale_dropped`` takes YaRN's ``mscale ** 2`` out of the
    softmax scale."""
    from mmlspark_tpu.models.granite_hybrid import GraniteHybridConfig
    cfg = GraniteHybridConfig.from_hf(
        RK.program_hf(config), experts_held=range(*config["experts_held"]),
        dtype=dtype)
    if fault == "yarn_mscale_dropped":
        cfg = dataclasses.replace(
            cfg, attention_multiplier=(cfg.d_nope + cfg.d_rope) ** -0.5)
    return cfg


class _RopeDropped:
    """The fault ``latent_rope_dropped``, in place while it is held: the
    decode step's latent attention (the kernel and its dense twin) gets
    its queries with the rotary lanes zeroed, so the rotary key is never
    read. Taken out again by :meth:`restore`."""

    def __init__(self, d_lat: int, d_rope: int):
        from mmlspark_tpu.parallel import pallas_mla
        self.mod = pallas_mla
        self.saved = {name: getattr(pallas_mla, name)
                      for name in ("paged_latent_decode_attention",
                                   "latent_decode_attention_dense")}
        for name, inner in self.saved.items():
            setattr(pallas_mla, name, self._wrap(inner, d_lat, d_rope))

    @staticmethod
    def _wrap(inner, d_lat, d_rope):
        def call(q, *args, **kw):
            return inner(q.at[..., d_lat:d_lat + d_rope].set(0), *args, **kw)
        return call

    def restore(self) -> None:
        for name, fn in self.saved.items():
            setattr(self.mod, name, fn)


class Served(G.Served):
    """The server and everything it holds, for this configuration."""

    def __init__(self, ctx, m: RK.Model, cfg):
        import jax
        import jax.numpy as jnp
        from mmlspark_tpu.models.nn import NNModel
        from mmlspark_tpu.models.zoo import ModelDownloader
        from mmlspark_tpu.serving import DecodeScheduler, ServingServer
        from mmlspark_tpu.serving.decode import decoder_for

        ctx.mark("import_model")
        sv = ctx.config["serve"]
        # the weights as an input: the seed's, rounded to the
        # configuration's dtype a layer at a time
        self.params = jax.block_until_ready(
            RK.make_params(m, ctx.seed, jnp.dtype(sv["dtype"])))
        ctx.mark("weights")
        #: device memory as each phase of set-up left it
        self.memory = {"weights": GG.device_bytes()}
        self.planted = (_RopeDropped(m.kv_lora, m.d_rope)
                        if ctx.fault == "latent_rope_dropped" else None)
        self.decoder = decoder_for(
            self.params, cfg, n_slots=int(sv["n_slots"]),
            max_len=int(sv["max_len"]), page_size=int(sv["page_size"]),
            n_pages=sv.get("n_pages"), attn_impl=sv["attn_impl"],
            prefill_tile=int(sv["prefill_tile"]))
        self.warm_programs = self.decoder.warmup()
        ctx.mark("warmup")
        self.memory["warmup"] = GG.device_bytes()
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
        """Stop the server, take a planted fault out, and free
        everything the program holds on the device, the weights too: the
        reference makes its own."""
        if self.planted is not None:
            self.planted.restore()
        self.decoder.params = self.params = None
        super().close()


def plan_of(tf: Dict[str, Any], vocab: int, seed: int, n_blocks: int
            ) -> List[Dict[str, Any]]:
    """``traffic.requests``, its sizes in ONE order for every seed (the
    order ``sizes_seed`` draws), its token ids drawn from ``seed``."""
    plan = traffic_mod.requests(tf, vocab, int(tf.get("sizes_seed", 0)),
                                n_blocks)
    rng = np.random.default_rng([int(seed), 3])
    shared = rng.integers(0, vocab, size=int(tf.get("shared_prefix", 0)))
    for r in plan:
        prompt = rng.integers(0, vocab, size=len(r["prompt"]))
        k = min(len(shared), len(prompt) - 1)
        prompt[:k] = shared[:k]
        r["prompt"] = prompt.astype(np.int32)
    return plan


# ---------------------------------------------------------------------------
# the window


def measure(ctx, served: Served, plan: List[Dict[str, Any]]
            ) -> Dict[str, Any]:
    """``generate_http.measure``, waiting ``drain_s`` for the requests
    in flight when the window closes."""
    import jax
    tf = ctx.traffic
    clients = G.ClosedLoop(served.host, served.port, plan,
                           int(tf["clients"]))
    clients.start()
    time.sleep(float(tf["ramp_s"]))
    stats0 = served.stats()
    t0 = time.perf_counter()
    out: Dict[str, Any] = {"t0": t0, "t1": t0 + ctx.seconds, "slices": []}
    if ctx.trace:
        n = int(tf["trace_slices"])
        t_len = float(tf["trace_seconds"])
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        for k in range(n):
            due = t0 + ctx.seconds * (k + 1) / (n + 2)
            time.sleep(max(due - time.perf_counter(), 0))
            if time.perf_counter() + t_len + 3.0 > out["t1"]:
                break
            jax.profiler.start_trace(
                os.path.join(ctx.trace_dir, str(k)),
                profiler_options=trace_reduce.profile_options())
            sl = {"stats0": served.stats(), "t0": time.perf_counter()}
            time.sleep(t_len)
            sl["t1"] = time.perf_counter()
            sl["stats1"] = served.stats()
            jax.profiler.stop_trace()
            out["slices"].append(sl)
    time.sleep(max(out["t1"] - time.perf_counter(), 0))
    out["stats1"] = served.stats()
    clients.closing.set()
    clients.join(timeout=float(tf["drain_s"]))
    if clients.is_alive() or clients.error is not None:
        raise RuntimeError(f"the clients did not finish: {clients.error!r}")
    if clients.exhausted:
        raise RuntimeError("the request plan ran out inside the window")
    out["stats0"], out["stats2"] = stats0, served.stats()
    out["requests"] = clients.sent
    out["drain_s"] = time.perf_counter() - out["t1"]
    return out


# ---------------------------------------------------------------------------
# counts


def held_per_token_layer(m: RK.Model, s0: Dict[str, Any],
                         s1: Dict[str, Any]) -> float:
    """Held experts a token was routed to in one MoE layer, the mean
    over the steps between two ``/decode/stats`` readings (the program
    counts its steps' routings: ``expert_routings``); the uniform
    router's where no step ran."""
    routed = sum(s1.get("expert_routings") or ()) - sum(
        s0.get("expert_routings") or ())
    stepped = ((s1["n_tokens"] - s1["n_prefills"])
               - (s0["n_tokens"] - s0["n_prefills"]))
    if routed <= 0 or stepped <= 0:
        return m.top_k_held
    return routed / float(stepped * m.n_moe)


def window_model_flops(m: RK.Model, win: Dict[str, Any], held: float
                       ) -> float:
    """The model's FLOPs on this chip for every token that reached a
    client inside the window: token 0 of a request is its prefill's,
    token ``i`` a step's, whose query reads ``P + i`` latent rows."""
    t0, t1 = win["t0"], win["t1"]
    total = 0.0
    for r in win["requests"]:
        p_len = len(r["prompt"])
        for i, t in enumerate(r["t_tokens"]):
            if t0 <= t < t1:
                total += (F.prefill_flops(m, p_len, held) if i == 0
                          else F.decode_flops(m, 1, p_len + i, held))
    return total


# ---------------------------------------------------------------------------
# correct


def _inputs(sample: List[Dict[str, Any]]):
    """Each request's prompt with its served tokens but the last (the
    reference's input), and the rows whose logits chose a served
    token."""
    seqs, rows = [], []
    for r in sample:
        served = np.asarray(r["streamed"], np.int32)
        seqs.append(np.concatenate([np.asarray(r["prompt"], np.int32),
                                    served[:-1]]))
        p_len = len(r["prompt"])
        rows.append(slice(p_len - 1, p_len - 1 + len(served)))
    return seqs, rows


def _readings(ref: List[np.ndarray], tokens: List[np.ndarray],
              seqs: List[np.ndarray]) -> Dict[str, Any]:
    """At every position the gap by which ``tokens``' logit lies below
    the best of the reference's logits ``ref``."""
    gaps = [G.R.served_gaps(r, t) for r, t in zip(ref, tokens)]
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    return {"served_logit_gap_widest": float(g.max()) if len(g) else 0.0,
            "served_logit_gap_mean": float(g.mean()) if len(g) else 0.0,
            "served_logit_gap_mean_sq":
                float(np.square(g).mean()) if len(g) else 0.0,
            "tokens": int(len(g)), "tokens_below_best": int((g > 0).sum()),
            "requests": len(seqs),
            "longest": int(max((len(s) + 1 for s in seqs), default=0))}


def gap_readings(m: RK.Model, seed: int, sample: List[Dict[str, Any]],
                 fault: Optional[str] = None, control: Optional[str] = None
                 ) -> Dict[str, Any]:
    """The reference once over each prompt with its served tokens, and
    at every served position the gap by which the served token's logit
    lies below the reference's best (``fault`` plants a mechanism's
    fault in the reference). With ``control``, also the control's
    reading (the gaps of the tokens the reference at precision
    ``control`` puts first, under the float32 reference's logits) and
    each fault of :data:`reference_kimi.FAULTS` read over the same
    served tokens: ``{"served": .., "control": .., "faults": {..}}``."""
    seqs, rows = _inputs(sample)
    served = [np.asarray(r["streamed"], np.int32) for r in sample]
    ref = RK.served_logits(m, seed, seqs, rows, "highest", fault)
    out: Dict[str, Any] = {"served": _readings(ref, served, seqs)}
    if control:
        low = RK.served_logits(m, seed, seqs, rows, control)
        out["control"] = _readings(ref, [x.argmax(axis=-1) for x in low],
                                   seqs)
        del low
        out["faults"] = {
            f: _readings(RK.served_logits(m, seed, seqs, rows, "highest", f),
                         served, seqs)
            for f in RK.FAULTS if f != fault}
    return out


def run(ctx) -> Dict[str, Any]:
    tf, sv = ctx.traffic, ctx.config["serve"]
    # first: a program without the latent layer refuses the
    # configuration here, before a weight is made
    cfg = program_config(ctx.config, sv["dtype"], ctx.fault)
    m = RK.Model.from_config(ctx.config)
    timed_fault = ctx.fault if ctx.fault not in RK.FAULTS else None
    served = Served(ctx, m, cfg)
    facts: Dict[str, Any] = {"driver": "generate_http_kimi",
                             "attn_impl": served.decoder.attn_impl,
                             "warm_programs": served.warm_programs,
                             "n_params": RK.n_params(m),
                             "latent_lanes":
                                 int(served.decoder.cache["lat"][0].shape[-1])}
    if ctx.on_chip and served.decoder.attn_impl != "pallas":
        raise RuntimeError(f"attn_impl 'auto' resolved to "
                           f"{served.decoder.attn_impl!r} on the chip")
    per_block = int(tf["sizes_per_block"])
    n_blocks = int(np.ceil((ctx.seconds + float(tf["ramp_s"]) + 10.0)
                           * float(tf.get("max_requests_per_s", 40.0))
                           / per_block))
    plan = plan_of(tf, m.vocab, ctx.seed, n_blocks)
    path = tf["path"]
    for r in plan:
        r["wire"] = G.wire(path, served.host, r)
    facts["largest_body_bytes"] = max(len(r["wire"]) for r in plan)
    # two requests through the whole path before the clock starts: the
    # block's median prompt, and the block's longest prompt with ids of
    # its own, so that the largest body the window will send has been
    # through the edge and the longest walk of tiles has run
    first = traffic_mod.warm_request(tf, m.vocab, ctx.seed)
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
    win = measure(ctx, served, plan)
    setup_s = win["t0"] - ctx.t_start
    facts["setup_phases_s"] = dict(ctx.phases, ramp=setup_s)
    e2e = G.reduce_window(win, ctx.seconds, ctx.model)
    s0, s1, s2 = win["stats0"], win["stats1"], win["stats2"]
    tails = {k: e2e[k] for k in sorted(e2e) if k.endswith("_ms")}
    held = held_per_token_layer(m, s0, s1)
    counters = {**GG.traced_counters(win), **tails, "window_s": ctx.seconds,
                "window_model_flops": window_model_flops(m, win, held),
                "window_steps": s1["n_steps"] - s0["n_steps"],
                "window_prefills": s1["n_prefills"] - s0["n_prefills"],
                "window_server_tokens": s1["n_tokens"] - s0["n_tokens"],
                "window_prompt_tokens":
                    s1["n_prompt_tokens"] - s0["n_prompt_tokens"],
                "held_per_token_layer": held,
                "n_slots": int(sv["n_slots"])}
    served.memory["window"] = GG.device_bytes()
    memory_peak = served.memory["window"]["peak"]
    facts.update(
        n_step_faults=s2["n_step_faults"], n_compiles=s2["n_compiles"],
        compiles_before=s0["n_compiles"], releases=s2["releases"],
        requests_sent=len(win["requests"]), drain_s=win["drain_s"],
        attempted=e2e["attempted"], n_gaps=e2e["n_gaps"],
        window_steps=counters["window_steps"],
        window_prefills=counters["window_prefills"],
        window_prompt_tokens=counters["window_prompt_tokens"],
        held_per_token_layer=held,
        expert_routings=s2["expert_routings"],
        slots_at_opening=s0["slots_in_use"],
        slots_high_water=s2["slots_high_water"],
        page_high_water=s2["pages"]["high_water"],
        n_page_preempts=s2["pages"]["n_preempts"],
        memory_phases=served.memory, tails_ms=tails)
    if ctx.fault:
        facts["fault_planted_in"] = ("timed path" if timed_fault
                                     else "reference")
    if ctx.fault == "token_altered":
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
    read = gap_readings(
        m, ctx.seed, sample,
        fault=ctx.fault if ctx.fault in RK.FAULTS else None,
        control=ctx.config["control"] if ctx.control else None)
    got = read["served"]
    compared = G.compare(got, ctx.limits)
    out: Dict[str, Any] = {}
    if ctx.control:
        c_cmp = G.compare(read["control"], ctx.limits)
        out["control"] = {
            "correct": G.is_correct(c_cmp), "compared": c_cmp,
            "readings": read["control"],
            # the faults the reference carries, over the same tokens
            "reference_faults": {
                f: {"correct": G.is_correct(G.compare(r, ctx.limits)),
                    "compared": G.compare(r, ctx.limits), "readings": r}
                for f, r in read["faults"].items()}}
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
