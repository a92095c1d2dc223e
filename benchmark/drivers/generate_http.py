"""Driver ``generate_http``: ``ServingServer`` + ``DecodeScheduler`` +
``TransformerDecoder`` in this process, driven over HTTP
``POST /generate?stream=1`` by closed-loop clients.

Set-up: weights on the device from the seed, the decoder and its
``warmup()`` (every program the traffic can reach), the server, and a
ramp of ``ramp_s`` seconds in which the clients already run, so that
the window opens on a full batch. The window is ``--seconds`` long on
the clients' clock; requests SENT in it are the sample for the tails
and are all waited for. One thread (``selectors``) is all the clients.
After the window: counters, peak memory, the server stopped and its
state freed, then the plain reference over a sample of the finished
requests, drawn from the seed, with the longest in it.

Configuration keys used: the model's sizes and ``serve`` (``n_slots``,
``max_len``, ``page_size``, ``attn_impl``, ``frame_model``). Traffic
keys: ``clients``, ``path``, the length distributions, ``ramp_s``,
``check_requests``, ``trace_slices`` x ``trace_seconds``,
``trace_hole_s``.
"""

from __future__ import annotations

import gc
import json
import os
import re
import selectors
import shutil
import socket
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

import flops
import reference as R
import trace_reduce
import traffic as traffic_mod

FAULTS = ("token_altered",)
STEP_PROGRAM = r"^jit_step$"
EVENT = re.compile(rb"data: (\{[^\n]*\})\n\n")
# the tails a BENCHMARK.json entry may name: ttft_p<q>_ms, gap_p<q>_ms
PERCENTILES = (50, 80, 90, 95, 99)


# ---------------------------------------------------------------------------
# the clients: one thread, one socket per request in flight


class _Flight:
    """One request on the wire."""

    __slots__ = ("req", "sock", "buf", "head_done", "seen")

    def __init__(self, req, sock):
        self.req, self.sock = req, sock
        self.buf = b""
        self.head_done = False
        self.seen = 0                  # bytes of buf already scanned


class ClosedLoop(threading.Thread):
    """``clients`` closed-loop clients: each sends its next request when
    the last one completed. ``plan`` is the requests in sending order;
    client ``c`` takes ``plan[c], plan[c + clients], ...``. Every request
    record gets ``t_send``, ``status``, ``t_tokens`` (the client's clock
    at each streamed token), ``streamed`` and the final event."""

    def __init__(self, host: str, port: int,
                 plan: List[Dict[str, Any]], clients: int):
        super().__init__(name="bench-clients", daemon=True)
        self.addr = (host, port)
        self.queues = [plan[c::clients] for c in range(clients)]
        self.closing = threading.Event()
        self.sent: List[Dict[str, Any]] = []
        self.error: Optional[BaseException] = None
        self.exhausted = False

    def _send(self, sel, c: int) -> None:
        if self.closing.is_set():
            return
        if not self.queues[c]:
            self.exhausted = True
            return
        req = self.queues[c].pop(0)
        req.update(client=c, t_send=time.perf_counter(), status=None,
                   t_tokens=[], streamed=[], final=None)
        sock = socket.create_connection(self.addr, timeout=120.0)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        sock.sendall(req.pop("wire"))
        sel.register(sock, selectors.EVENT_READ, _Flight(req, sock))
        self.sent.append(req)

    def _on_data(self, sel, fl: _Flight) -> None:
        data = fl.sock.recv(1 << 16)
        now = time.perf_counter()
        req = fl.req
        done = not data
        fl.buf += data
        if not fl.head_done:
            end = fl.buf.find(b"\r\n\r\n")
            if end < 0 and not done:
                return
            req["status"] = int(fl.buf.split(b" ", 2)[1]) if end > 0 else 0
            fl.head_done = True
            fl.seen = max(end, 0)
        if req["status"] == 200:
            last = fl.seen
            for mt in EVENT.finditer(fl.buf, fl.seen):
                ev = json.loads(mt.group(1))
                last = mt.end()
                if ev.get("done"):
                    req["final"] = ev
                    done = True
                else:
                    req["streamed"].append(ev["token"])
                    req["t_tokens"].append(now)
            fl.seen = last
        elif b"\r\n\r\n" in fl.buf:
            req["final"] = {"error": fl.buf[-300:].decode("latin1")}
            done = True
        if done:
            req["t_done"] = now
            sel.unregister(fl.sock)
            fl.sock.close()
            self._send(sel, req["client"])

    def run(self) -> None:
        sel = selectors.DefaultSelector()
        try:
            for c in range(len(self.queues)):
                self._send(sel, c)
            while sel.get_map():
                for key, _ in sel.select(timeout=1.0):
                    self._on_data(sel, key.data)
        except BaseException as e:  # noqa: BLE001 — reported by the driver
            self.error = e
        finally:
            for key in list(sel.get_map().values()):
                key.fileobj.close()
            sel.close()


def wire(path: str, host: str, req: Dict[str, Any]) -> bytes:
    body = json.dumps({"prompt": [int(t) for t in req["prompt"]],
                       "max_new_tokens": int(req["max_new_tokens"])}
                      ).encode()
    return (f"POST {path} HTTP/1.1\r\nHost: {host}\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\nConnection: close\r\n\r\n"
            ).encode() + body


def get_json(host: str, port: int, path: str) -> Dict[str, Any]:
    import http.client
    conn = http.client.HTTPConnection(host, port, timeout=60.0)
    try:
        conn.request("GET", path)
        r = conn.getresponse()
        body = r.read()
        if r.status != 200:
            raise RuntimeError(f"GET {path} -> {r.status}: {body[:200]!r}")
        return json.loads(body)
    finally:
        conn.close()


# ---------------------------------------------------------------------------
# the system under test


class Served:
    """The server and everything it holds."""

    def __init__(self, ctx):
        from mmlspark_tpu.models import transformer as T
        from mmlspark_tpu.models.nn import NNModel
        from mmlspark_tpu.models.zoo import ModelDownloader
        from mmlspark_tpu.serving import (
            DecodeScheduler, ServingServer, TransformerDecoder)

        ctx.mark("import_model")
        m, sv = ctx.model, ctx.config["serve"]
        cfg = T.TransformerConfig(
            vocab=m.vocab, d_model=m.d_model, n_heads=m.n_heads,
            d_head=m.d_head, d_ff=m.d_ff, n_stages=1,
            layers_per_stage=m.n_layers, dtype=sv["dtype"])
        self.params = R.make_params(m, ctx.seed)
        ctx.mark("weights")
        self.decoder = TransformerDecoder(
            self.params, cfg, n_slots=int(sv["n_slots"]),
            max_len=int(sv["max_len"]), page_size=int(sv["page_size"]),
            attn_impl=sv["attn_impl"])
        self.warm_programs = self.decoder.warmup()
        ctx.mark("warmup")
        if ctx.fault == "token_altered":
            self._alter_tokens(m.vocab)
        # the frame model: the smallest the zoo verifies; it gets no
        # traffic
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

    def _alter_tokens(self, vocab: int) -> None:
        """The planted fault: where the step produces its tokens, every
        eighth step's are replaced by their neighbours in the
        vocabulary (every slot's, so that whichever requests the check
        samples hold some)."""
        inner = self.decoder.step_logits
        self.altered_steps = 0
        calls = [0]

        def step_logits(tokens, pos, page_tables=None):
            out, logits = inner(tokens, pos, page_tables)
            calls[0] += 1
            if calls[0] % 8 == 0:
                out = (np.asarray(out) + 1) % vocab
                self.altered_steps += 1
            return out, logits

        self.decoder.step_logits = step_logits

    def stats(self) -> Dict[str, Any]:
        return get_json(self.host, self.port, "/decode/stats")

    def close(self) -> None:
        """Stop the server and free what the program holds on the
        device (the benchmark's own weights stay for the reference)."""
        self.server.stop()
        self.decoder.cache = None
        self.decoder = self.sched = self.server = None
        gc.collect()


# ---------------------------------------------------------------------------
# the window


def percentile(values: List[float], q: float) -> float:
    """The ``q``-th percentile, by the nearest rank above."""
    v = sorted(values)
    return v[min(len(v) - 1, int(np.ceil(q / 100.0 * len(v))) - 1)]


def measure(ctx, served: Served, plan: List[Dict[str, Any]]
            ) -> Dict[str, Any]:
    import jax
    tf = ctx.traffic
    clients = ClosedLoop(served.host, served.port, plan,
                         int(tf["clients"]))
    clients.start()
    time.sleep(float(tf["ramp_s"]))
    stats0 = served.stats()
    t0 = time.perf_counter()
    out: Dict[str, Any] = {"t0": t0, "t1": t0 + ctx.seconds}
    out["slices"] = []
    if ctx.trace:
        # the device's trace buffer holds about a second of this cell's
        # events: several short slices, spread over the window
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
    clients.join(timeout=120.0)
    if clients.is_alive() or clients.error is not None:
        raise RuntimeError(f"the clients did not finish: {clients.error!r}")
    if clients.exhausted:
        raise RuntimeError("the request plan ran out inside the window")
    out["stats0"], out["stats2"] = stats0, served.stats()
    out["requests"] = clients.sent
    return out


def reduce_window(win: Dict[str, Any], seconds: float, m
                  ) -> Dict[str, Any]:
    """The end-to-end metrics, over all the work of the window."""
    t0, t1 = win["t0"], win["t1"]
    reqs = win["requests"]
    inside = [r for r in reqs if t0 <= r["t_send"] < t1]
    tokens, gaps, ttft, model_flops = 0, [], [], 0.0
    for r in reqs:
        ts = r["t_tokens"]
        p_len = len(r["prompt"])
        for i, t in enumerate(ts):
            if t0 <= t < t1:
                tokens += 1
                model_flops += (flops.prefill_flops(m, p_len) if i == 0
                                else flops.decode_flops(m, 1, p_len + i))
        gaps += [(b - a) * 1e3 for a, b in zip(ts, ts[1:]) if t0 <= b < t1]
    for r in inside:
        if r["t_tokens"]:
            ttft.append((r["t_tokens"][0] - r["t_send"]) * 1e3)
    failed = [r for r in inside if not ok(r)]
    # a failed request has no first token: it counts as missing the
    # tail (the worst rank)
    ttft += [float("inf")] * (len(inside) - len(ttft))
    out = {"gen_tokens_per_s": tokens / seconds,
           "n_gaps": len(gaps), "attempted": len(inside),
           "failed": len(failed), "tokens_in_window": tokens,
           "model_flops": model_flops}
    for q in PERCENTILES:
        out[f"ttft_p{q}_ms"] = percentile(ttft, float(q))
        out[f"gap_p{q}_ms"] = percentile(gaps, float(q))
    return out


def ok(r: Dict[str, Any]) -> bool:
    f = r.get("final") or {}
    return (r["status"] == 200 and f.get("finish_reason") == "length"
            and f.get("n_tokens") == r["max_new_tokens"]
            and f.get("tokens") == r["streamed"])


def traced_counters(win: Dict[str, Any]) -> Dict[str, Any]:
    """What the readers need about the traced slices of the window: the
    counters' deltas, and from the clients' records the tokens that
    arrived in them with the cached rows each one's step read."""
    if not win["slices"]:
        return {}
    positions = 0
    for r in win["requests"]:
        p_len = len(r["prompt"])
        for i, t in enumerate(r["t_tokens"]):
            # token 0 comes from the prefill, token i from a step that
            # reads P + i cached rows
            if i and any(sl["t0"] <= t < sl["t1"] for sl in win["slices"]):
                positions += p_len + i

    return {"traced_s": sum(sl["t1"] - sl["t0"] for sl in win["slices"]),
            "traced_positions": positions,
            "traced_steps": sum(sl["stats1"]["n_steps"]
                                - sl["stats0"]["n_steps"]
                                for sl in win["slices"])}


# ---------------------------------------------------------------------------
# correct


def sample_requests(reqs: List[Dict[str, Any]], seed: int, n: int
                    ) -> List[Dict[str, Any]]:
    """``n`` finished requests drawn from the seed, the longest among
    them."""
    done = [r for r in reqs if ok(r)]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r["prompt"]) + len(r["streamed"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(int(seed) + 1)
    pick = rng.permutation(len(rest))[:max(n - 1, 0)]
    return [longest] + [rest[i] for i in pick]


def gap_readings(params, m, sample: List[Dict[str, Any]], max_len: int,
                 precision: str, own_argmax: bool = False
                 ) -> Dict[str, Any]:
    """The reference once over each prompt with its served tokens, and
    at every served position the gap by which the served token's logit
    lies below the reference's best: the widest gap, and over all the
    positions the mean gap and the mean of the gap's square.
    ``own_argmax`` is the control's reading: the gaps of the tokens
    that ``precision`` puts first, under the float32 reference's
    logits."""
    gaps = []
    for r in sample:
        served = np.asarray(r["streamed"], np.int32)
        p_len = len(r["prompt"])
        seq = np.zeros(max_len, np.int32)
        seq[:p_len] = r["prompt"]
        seq[p_len:p_len + len(served) - 1] = served[:-1]
        rows = slice(p_len - 1, p_len - 1 + len(served))
        ref = np.asarray(R.logits(params, seq[None], m, "highest")[0, rows])
        if own_argmax:
            low = R.logits(params, seq[None], m, precision)[0, rows]
            tokens = np.asarray(low.argmax(axis=-1))
        else:
            tokens = served
        gaps.append(R.served_gaps(ref, tokens))
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    return {"served_logit_gap_widest": float(g.max()) if len(g) else 0.0,
            "served_logit_gap_mean": float(g.mean()) if len(g) else 0.0,
            "served_logit_gap_mean_sq":
                float(np.square(g).mean()) if len(g) else 0.0,
            "tokens": int(len(g)), "tokens_below_best": int((g > 0).sum()),
            "requests": len(sample)}


def compare(got: Dict[str, Any], limits: Dict[str, float]
            ) -> Dict[str, Any]:
    """Each number the limits' file names, beside its limit."""
    return {k: [got[k], limit] for k, limit in limits.items()}


def is_correct(compared: Dict[str, Any]) -> bool:
    return all(np.isfinite(v[0]) and v[0] <= v[1]
               for v in compared.values())


def run(ctx) -> Dict[str, Any]:
    import jax
    m, tf, sv = ctx.model, ctx.traffic, ctx.config["serve"]
    served = Served(ctx)
    facts: Dict[str, Any] = {"driver": "generate_http",
                             "attn_impl": served.decoder.attn_impl,
                             "warm_programs": served.warm_programs}
    if ctx.on_chip and served.decoder.attn_impl != "pallas":
        raise RuntimeError(f"attn_impl 'auto' resolved to "
                           f"{served.decoder.attn_impl!r} on the chip")
    # enough blocks for the ramp, the window and the drain at several
    # times the rate predicted
    per_block = int(tf["sizes_per_block"])
    n_blocks = int(np.ceil((ctx.seconds + float(tf["ramp_s"]) + 10.0)
                           * float(tf.get("max_requests_per_s", 40.0))
                           / per_block))
    plan = traffic_mod.requests(tf, m.vocab, ctx.seed, n_blocks)
    path = tf["path"]
    for r in plan:
        r["wire"] = wire(path, served.host, r)
    # one request through the whole path before the clock starts
    # (one that is in no plan, so that the prefix cache holds nothing
    # of the window's)
    first = traffic_mod.warm_request(tf, m.vocab, ctx.seed)
    first["wire"] = wire(path, served.host, first)
    warm = ClosedLoop(served.host, served.port, [first], 1)
    warm.start()
    warm.join(timeout=120.0)
    if warm.error is not None or not ok(warm.sent[0]):
        raise RuntimeError(f"the warm-up request failed: "
                           f"{warm.error!r} {warm.sent[0].get('final')}")

    ctx.mark("warm_request")
    win = measure(ctx, served, plan)
    setup_s = win["t0"] - ctx.t_start
    facts["setup_phases_s"] = dict(ctx.phases, ramp=setup_s)
    e2e = reduce_window(win, ctx.seconds, m)
    s0, s1, s2 = win["stats0"], win["stats1"], win["stats2"]
    tails = {k: e2e[k] for k in sorted(e2e) if k.endswith("_ms")}
    counters = {**traced_counters(win), **tails, "window_s": ctx.seconds,
                "window_model_flops": e2e["model_flops"],
                "window_steps": s1["n_steps"] - s0["n_steps"],
                "window_prefills": s1["n_prefills"] - s0["n_prefills"],
                "window_server_tokens": s1["n_tokens"] - s0["n_tokens"],
                "n_slots": int(sv["n_slots"])}
    # the peak on the fullest chip
    memory_peak = max(int((d.memory_stats() or {})
                          .get("peak_bytes_in_use", 0))
                      for d in jax.devices())
    prefix_hits = (s2.get("prefix_cache") or {}).get("hits", 0)
    facts.update(
        n_step_faults=s2["n_step_faults"], n_compiles=s2["n_compiles"],
        compiles_before=s0["n_compiles"], releases=s2["releases"],
        prefix_hits=prefix_hits, requests_sent=len(win["requests"]),
        attempted=e2e["attempted"], n_gaps=e2e["n_gaps"],
        window_steps=counters["window_steps"],
        window_prefills=counters["window_prefills"],
        slots_high_water=s2["slots_high_water"],
        tails_ms=tails)
    if ctx.fault:
        facts["altered_steps"] = served.altered_steps
    sound = (s2["n_step_faults"] == 0
             and s2["n_compiles"] == s0["n_compiles"] == served.warm_programs
             and all(ok(r) for r in win["requests"])
             and set(s2["releases"]) <= {"length"})
    params, max_len = served.params, int(sv["max_len"])
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
    sample = sample_requests(win["requests"], ctx.seed,
                             int(tf["check_requests"]))
    got = gap_readings(params, m, sample, max_len, "highest")
    compared = compare(got, ctx.limits)
    out: Dict[str, Any] = {}
    if ctx.control:
        # --control: the reference in the program's place, one
        # precision below what the configuration states, judged by the
        # same comparison
        ctl = gap_readings(params, m, sample, max_len,
                           ctx.config["control"], own_argmax=True)
        c_cmp = compare(ctl, ctx.limits)
        out["control"] = {"correct": is_correct(c_cmp), "compared": c_cmp,
                          "readings": ctl}
    facts.update(reference_s=time.perf_counter() - t_ref,
                 readings=got, sound=sound)
    out.update({
        "correct": bool(sound and sample and is_correct(compared)),
        "attempted": e2e["attempted"], "failed": e2e["failed"],
        "end_to_end": {k: v for k, v in e2e.items()
                       if k.endswith(("_ms", "_per_s"))}
        | {"setup_s": setup_s},
        "counters": counters, "reduced": reduced,
        "memory_peak_bytes": memory_peak, "compared": compared,
        "facts": facts})
    return out
