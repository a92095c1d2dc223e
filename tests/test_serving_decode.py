"""Continuous batching for autoregressive decode (ISSUE 9).

Four pillars:

* **correctness through the whole serving stack** — greedy tokens from
  the slot-indexed KV-cache plane (HTTP -> admission -> scheduler ->
  jitted prefill/step) match the full-context reference forward
  token-for-token;
* **zero retraces under churn** — requests joining and leaving a
  running decode batch never grow the compiled-shape set past warmup;
* **no slot leaks, ever** — cancel, deadline expiry, and injected
  decode-step faults (the ``testing/faults.py`` sites) all return
  their slot: after any churn schedule, ``n_free == n_slots``;
* **adaptive batching** — the per-bucket policy learns the
  arrival-rate/service-time tradeoff from the dispatch histograms and
  is A/B selectable against the fixed ``max_latency_ms`` knob.
"""

import json
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest
import requests

from mmlspark_tpu.core.resilience import Deadline, ManualClock
from mmlspark_tpu.core.stage import Transformer
from mmlspark_tpu.core.telemetry import MetricsRegistry
from mmlspark_tpu.models import transformer as T
from mmlspark_tpu.serving import (
    AdaptiveBatchPolicy, DecodeScheduler, ServingServer, SlotPool,
    TransformerDecoder,
)
from mmlspark_tpu.serving.decode import DecodeOverloaded
from mmlspark_tpu.testing.faults import FaultPlan

CFG = T.TransformerConfig(vocab=64, d_model=16, n_heads=2, d_head=8,
                          d_ff=32, n_stages=1, layers_per_stage=2)
PARAMS = T.init_params(CFG, seed=0)


def _decoder(n_slots=4, max_len=32, **kw) -> TransformerDecoder:
    return TransformerDecoder(PARAMS, CFG, n_slots=n_slots,
                              max_len=max_len, **kw)


def _greedy_reference(prompt, n_new):
    ctx = [int(t) for t in prompt]
    out = []
    for _ in range(n_new):
        lg = T.reference_logits(
            PARAMS, jnp.asarray(np.asarray(ctx, np.int32))[None], CFG)
        t = int(jnp.argmax(lg[0, -1]))
        out.append(t)
        ctx.append(t)
    return out


def _prompt(rng, n):
    return [int(t) for t in rng.integers(0, CFG.vocab, size=n)]


class _Pending:
    """The slice of _PendingRequest the standalone scheduler touches."""

    def __init__(self, payload, rid, deadline=None):
        self.payload = payload
        self.rid = rid
        self.deadline = deadline
        self.event = threading.Event()
        self.callbacks = []
        self.reply = None
        self.status = 200
        self.span = None
        self.trace = rid


def _pages_idle(sched) -> bool:
    """The refcounted page-leak ledger at idle: every claimable page
    is either free or held EXACTLY once by the prefix index (cached,
    evictable) — no request left a reference behind. With the prefix
    cache off this degrades to the raw-ownership invariant."""
    claimable = sched.pages.n_pages - 1
    if sched.prefix is None:
        return sched.pages.n_free == claimable
    return (sched.pages.n_free + sched.prefix.n_cached == claimable
            and sched.prefix.ledger_clean())


class Identity(Transformer):
    def transform(self, df):
        return df


def _serve(**kw) -> ServingServer:
    sched = DecodeScheduler(_decoder(**kw.pop("decoder_kw", {})),
                            max_new_tokens_default=8)
    return ServingServer(Identity(), port=0, decoder=sched,
                         max_latency_ms=1.0, verify_checkpoints=False,
                         **kw)


class TestSlotPool:

    def test_claim_release_roundtrip(self):
        pool = SlotPool(3)
        slots = [pool.claim() for _ in range(3)]
        assert sorted(slots) == [0, 1, 2]
        assert pool.claim() is None and pool.n_free == 0
        for s in slots:
            pool.release(s)
        assert pool.n_free == 3

    def test_double_release_raises(self):
        pool = SlotPool(2)
        s = pool.claim()
        pool.release(s)
        with pytest.raises(RuntimeError, match="double-released"):
            pool.release(s)

    def test_release_of_never_claimed_raises(self):
        """The claimed-set ledger (O(1), no free-list scan) catches a
        release of a slot that was never handed out."""
        pool = SlotPool(3)
        with pytest.raises(RuntimeError, match="double-released"):
            pool.release(1)


class TestPrefixCacheUnit:
    """The refcounted page pool + radix index without a model: claim/
    ref/release arithmetic, lookup/publish keying, LRU eviction, and
    the idle ledger."""

    def _cache(self, n_pages=17, page_size=4, max_pages=None):
        from mmlspark_tpu.serving import PagePool, PrefixCache
        pool = PagePool(n_pages)
        return pool, PrefixCache(pool, page_size,
                                 max_pages=max_pages)

    def test_refcounts_share_and_release(self):
        from mmlspark_tpu.serving import PagePool
        pool = PagePool(4)
        (p,) = pool.claim(1)
        pool.ref([p])                     # second reader attaches
        assert pool.refcount(p) == 2
        pool.release([p])                 # first reader leaves
        assert pool.refcount(p) == 1 and pool.n_free == 2
        pool.release([p])                 # last reader frees it
        assert pool.refcount(p) == 0 and pool.n_free == 3
        with pytest.raises(RuntimeError, match="double-released"):
            pool.release([p])
        with pytest.raises(RuntimeError, match="unclaimed"):
            pool.ref([p])

    def test_lookup_publish_roundtrip_and_cap(self):
        pool, pc = self._cache()
        prompt = np.arange(10, dtype=np.int32)   # 2 full chunks + 2
        pages = pool.claim(3)
        absorbed = pc.publish(prompt, pages)
        # only the 2 prompt-complete chunks are published; the partial
        # tail page stays the caller's
        assert absorbed == set(pages[:2])
        pool.release([p for p in pages if p not in absorbed])
        hit, got = pc.lookup(prompt)
        assert (hit, got) == (8, pages[:2])
        assert all(pool.refcount(p) == 2 for p in got)
        pool.release(got)
        # an exact-prefix prompt (len == published depth) caps at
        # len - 1: the last position must be computed for its logits
        hit, got = pc.lookup(prompt[:8])
        assert hit == 4 and got == pages[:1]
        pool.release(got)
        # diverging second chunk: longest shared prefix is 1 chunk
        other = prompt.copy()
        other[6] = 63
        hit, got = pc.lookup(other)
        assert hit == 4 and got == pages[:1]
        pool.release(got)
        assert pc.lookup(np.asarray([9, 9, 9, 9, 9], np.int32)) \
            == (0, [])
        assert pc.ledger_clean()

    def test_publish_dedupe_keeps_incumbent(self):
        pool, pc = self._cache()
        prompt = np.arange(8, dtype=np.int32)
        first = pool.claim(2)
        assert pc.publish(prompt, first) == set(first)
        dup = pool.claim(2)
        assert pc.publish(prompt, dup) == set()   # incumbent kept
        pool.release(dup)
        assert pc.n_cached == 2 and pc.ledger_clean()

    def test_lru_eviction_spares_referenced_pages(self):
        pool, pc = self._cache(max_pages=4)
        p_a = pool.claim(2)
        pc.publish(np.arange(8, dtype=np.int32), p_a)
        p_b = pool.claim(2)
        pc.publish(np.arange(8, 16, dtype=np.int32), p_b)
        assert pc.n_cached == 4
        # a reader pins prefix A (older), so pressure must evict B
        hit, got = pc.lookup(np.arange(9, dtype=np.int32))
        assert got == p_a
        assert pc.evict_for(pool.n_free + 2) == 2
        assert pc.n_cached == 2
        assert pc.lookup(np.arange(8, 16, dtype=np.int32))[1] == []
        hit2, got2 = pc.lookup(np.arange(9, dtype=np.int32))
        assert got2 == p_a               # the pinned prefix survived
        pool.release(got + got2)
        assert pc.ledger_clean()

    def test_max_pages_bounds_publication(self):
        pool, pc = self._cache(max_pages=2)
        p_a = pool.claim(2)
        assert len(pc.publish(np.arange(8, dtype=np.int32), p_a)) == 2
        # the bound forces LRU turnover, never growth past max_pages
        p_b = pool.claim(2)
        absorbed = pc.publish(np.arange(8, 16, dtype=np.int32), p_b)
        pool.release([p for p in p_b if p not in absorbed])
        assert pc.n_cached <= 2 and pc.ledger_clean()

    def test_clear_returns_every_cached_page(self):
        pool, pc = self._cache()
        pages = pool.claim(4)
        pc.publish(np.arange(16, dtype=np.int32), pages)
        assert pool.n_free == 16 - 4
        assert pc.clear() == 4
        assert pool.n_free == 16 and pc.n_cached == 0


class TestSchedulerDirect:
    """The scheduler without HTTP: standalone commit path."""

    def _run(self, sched, payloads, rids=None, deadlines=None,
             timeout=30.0):
        pendings = [
            _Pending(p, (rids or {}).get(i, f"r{i}"),
                     (deadlines or {}).get(i))
            for i, p in enumerate(payloads)]
        for p in pendings:
            sched.submit(p)
        for p in pendings:
            assert p.event.wait(timeout), "request stranded"
        return pendings

    @pytest.mark.slow
    def test_greedy_tokens_match_reference(self):
        sched = DecodeScheduler(_decoder()).start()
        try:
            rng = np.random.default_rng(0)
            prompts = [_prompt(rng, n) for n in (3, 5, 7)]
            done = self._run(sched, [
                {"prompt": pr, "max_new_tokens": 6} for pr in prompts])
            for pr, p in zip(prompts, done):
                out = json.loads(p.reply)
                assert out["tokens"] == _greedy_reference(pr, 6)
                assert out["finish_reason"] == "length"
                assert out["prompt_len"] == len(pr)
        finally:
            sched.stop()
        assert sched.pool.n_free == sched.decoder.n_slots

    def test_eos_frees_slot_early(self):
        rng = np.random.default_rng(1)
        prompt = _prompt(rng, 5)
        ref = _greedy_reference(prompt, 8)
        eos = ref[2]                  # stop at the 3rd generated token
        sched = DecodeScheduler(_decoder(eos_id=eos)).start()
        try:
            (p,) = self._run(sched, [{"prompt": prompt,
                                      "max_new_tokens": 8}])
            out = json.loads(p.reply)
            assert out["finish_reason"] == "eos"
            assert out["tokens"] == ref[:3]
        finally:
            sched.stop()
        assert sched.pool.n_free == sched.decoder.n_slots

    def test_more_requests_than_slots_all_complete(self):
        """12 requests over 3 slots: leavers hand their slots to
        waiters and every request matches its own golden — the
        continuous part of continuous batching."""
        sched = DecodeScheduler(_decoder(n_slots=3)).start()
        try:
            warm = sched.decoder.warmup()
            rng = np.random.default_rng(2)
            prompts = [_prompt(rng, 2 + (i % 5)) for i in range(12)]
            done = self._run(sched, [
                {"prompt": pr, "max_new_tokens": 4} for pr in prompts])
            for pr, p in zip(prompts, done):
                assert json.loads(p.reply)["tokens"] == \
                    _greedy_reference(pr, 4)
            # churn never grew the compiled-shape set
            assert sched.decoder.n_compiles() == warm
        finally:
            sched.stop()
        assert sched.pool.n_free == 3

    def test_max_len_bounds_generation(self):
        """A request whose budget exceeds its cache lane ends at the
        lane, finish_reason 'length' (the clamp documented in
        parse())."""
        sched = DecodeScheduler(_decoder(n_slots=2, max_len=16)).start()
        try:
            rng = np.random.default_rng(3)
            prompt = _prompt(rng, 10)
            (p,) = self._run(sched, [{"prompt": prompt,
                                      "max_new_tokens": 1000}])
            out = json.loads(p.reply)
            assert out["finish_reason"] == "length"
            assert out["n_tokens"] == 16 - 10
        finally:
            sched.stop()
        assert sched.pool.n_free == 2

    def test_parse_rejections(self):
        sched = DecodeScheduler(_decoder())
        for bad in ([], {"prompt": []}, {"prompt": "abc"},
                    {"prompt": [1, -2]}, {"prompt": [CFG.vocab]},
                    {"prompt": list(range(32))},          # >= max_len
                    {"prompt": [1], "max_new_tokens": 0},
                    # bool is an int subclass: must 400, not decode
                    # as tokens [1, 0] / budget 1
                    {"prompt": [True, False]},
                    {"prompt": [1], "max_new_tokens": True}):
            with pytest.raises(ValueError):
                sched.parse(bad)

    def test_overload_sheds(self):
        sched = DecodeScheduler(_decoder(), max_waiting=2)  # not started
        sched.submit(_Pending({"prompt": [1]}, "a"))
        sched.submit(_Pending({"prompt": [1]}, "b"))
        assert sched.overloaded()
        with pytest.raises(DecodeOverloaded):
            sched.submit(_Pending({"prompt": [1]}, "c"))


@pytest.mark.chaos
class TestSlotLeaks:
    """The slot-leak chaos pillar: every exit path returns its slot."""

    def test_cancel_mid_decode_frees_slot(self):
        sched = DecodeScheduler(_decoder(n_slots=2)).start()
        try:
            rng = np.random.default_rng(4)
            p = _Pending({"prompt": _prompt(rng, 4),
                          "max_new_tokens": 10_000}, "long")
            sched.submit(p)
            t_end = time.monotonic() + 10
            while not sched.stats()["active"] and \
                    time.monotonic() < t_end:
                time.sleep(0.005)
            assert sched.cancel("long") is True
            assert p.event.wait(10)
            out = json.loads(p.reply)
            assert out["finish_reason"] == "cancelled"
            # partial tokens were emitted incrementally and returned
            assert out["n_tokens"] == len(out["tokens"])
        finally:
            sched.stop()
        assert sched.pool.n_free == 2
        assert sched.cancel("unknown") is False

    def test_deadline_expiry_mid_decode_frees_slot(self):
        clock = ManualClock()
        sched = DecodeScheduler(_decoder(n_slots=2), clock=clock).start()
        try:
            rng = np.random.default_rng(5)
            p = _Pending({"prompt": _prompt(rng, 4),
                          "max_new_tokens": 10_000}, "dl",
                         deadline=Deadline(5.0, clock=clock))
            sched.submit(p)
            t_end = time.monotonic() + 10
            while not sched.stats()["active"] and \
                    time.monotonic() < t_end:
                time.sleep(0.005)
            clock.advance(6.0)        # budget spent mid-decode
            assert p.event.wait(10)
            assert p.status == 504
            assert json.loads(p.reply)["finish_reason"] == "deadline"
        finally:
            sched.stop()
        assert sched.pool.n_free == 2

    def test_dead_waiters_reaped_while_all_slots_busy(self):
        """With every slot pinned by long decodes, cancelled and
        deadline-expired WAITERS must still resolve promptly (and stop
        counting toward overloaded()) — not rot until the frontend's
        request_timeout."""
        clock = ManualClock()
        sched = DecodeScheduler(_decoder(n_slots=1), clock=clock).start()
        rng = np.random.default_rng(11)
        try:
            hog = _Pending({"prompt": _prompt(rng, 3),
                            "max_new_tokens": 10_000}, "hog")
            sched.submit(hog)
            t_end = time.monotonic() + 10
            while not sched.stats()["active"] and \
                    time.monotonic() < t_end:
                time.sleep(0.005)
            dead_c = _Pending({"prompt": _prompt(rng, 3),
                               "max_new_tokens": 4}, "w-cancel")
            dead_d = _Pending({"prompt": _prompt(rng, 3),
                               "max_new_tokens": 4}, "w-deadline",
                              deadline=Deadline(1.0, clock=clock))
            sched.submit(dead_c)
            sched.submit(dead_d)
            sched.cancel("w-cancel")
            clock.advance(2.0)
            # both resolve while the hog still owns the only slot
            assert dead_c.event.wait(10)
            assert dead_d.event.wait(10)
            assert json.loads(dead_c.reply)["finish_reason"] == \
                "cancelled"
            assert dead_d.status == 504
            assert sched.stats()["slots_in_use"] == 1   # hog lives on
            assert sched.stats()["waiting"] == 0
            sched.cancel("hog")
        finally:
            sched.stop()
        assert sched.pool.n_free == 1

    def test_expired_waiter_never_claims_a_slot(self):
        clock = ManualClock()
        sched = DecodeScheduler(_decoder(n_slots=2), clock=clock)
        p = _Pending({"prompt": [1, 2]}, "doa",
                     deadline=Deadline(1.0, clock=clock))
        sched.submit(p)
        clock.advance(2.0)
        sched._admit_waiting()        # the loop's admission pass
        assert p.event.is_set() and p.status == 504
        assert sched.pool.n_free == 2
        assert sched.n_prefills == 0

    def test_injected_step_fault_never_strands_a_slot(self):
        """The ``decode_step`` fault site: a failing step 500s the
        in-slot requests (never journaled — retries re-execute) and
        releases every slot; the loop keeps serving the next wave."""
        plan = FaultPlan(script={"decode_step": ["ok", "fail"]})
        sched = DecodeScheduler(_decoder(n_slots=2),
                                fault_plan=plan).start()
        try:
            rng = np.random.default_rng(6)
            first = [_Pending({"prompt": _prompt(rng, 3),
                               "max_new_tokens": 6}, f"w{i}")
                     for i in range(2)]
            for p in first:
                sched.submit(p)
            for p in first:
                assert p.event.wait(10)
            # the scripted fault hit the SECOND step: both in-slot
            # requests 500 with their partial tokens attached
            assert {p.status for p in first} == {500}
            for p in first:
                out = json.loads(p.reply)
                assert out["finish_reason"] == "error"
                assert out["n_tokens"] >= 1
            assert sched.n_step_faults == 1
            assert sched.pool.n_free == 2
            # the plane recovered: the next request decodes cleanly
            prompt = _prompt(rng, 4)
            after = _Pending({"prompt": prompt, "max_new_tokens": 3},
                             "after")
            sched.submit(after)
            assert after.event.wait(10)
            assert after.status == 200
            assert json.loads(after.reply)["tokens"] == \
                _greedy_reference(prompt, 3)
        finally:
            sched.stop()
        assert sched.pool.n_free == 2

    def test_prefill_fault_releases_claimed_slot(self):
        plan = FaultPlan(script={"decode_prefill": ["fail"]})
        sched = DecodeScheduler(_decoder(n_slots=2),
                                fault_plan=plan).start()
        try:
            p = _Pending({"prompt": [1, 2, 3]}, "pf")
            sched.submit(p)
            assert p.event.wait(10)
            assert p.status == 500
        finally:
            sched.stop()
        assert sched.pool.n_free == 2

    def test_churn_cycles_return_every_slot(self):
        """N churn cycles mixing clean finishes, cancels, deadline
        expiries, and an injected step fault: the free-slot count
        returns to n_slots and the release ledger accounts for every
        request."""
        clock = ManualClock()
        plan = FaultPlan(script={"decode_step": ["ok"] * 7 + ["fail"]})
        sched = DecodeScheduler(_decoder(n_slots=3), clock=clock,
                                fault_plan=plan).start()
        rng = np.random.default_rng(7)
        n_total = 0
        try:
            for cycle in range(4):
                kinds = [
                    _Pending({"prompt": _prompt(rng, 3),
                              "max_new_tokens": 2}, f"c{cycle}-ok"),
                    _Pending({"prompt": _prompt(rng, 3),
                              "max_new_tokens": 10_000},
                             f"c{cycle}-cancel"),
                    _Pending({"prompt": _prompt(rng, 3),
                              "max_new_tokens": 10_000},
                             f"c{cycle}-deadline",
                             deadline=Deadline(1.0, clock=clock)),
                ]
                n_total += len(kinds)
                for p in kinds:
                    sched.submit(p)
                time.sleep(0.05)          # let slots fill / steps run
                sched.cancel(f"c{cycle}-cancel")
                clock.advance(2.0)        # expire this cycle's deadline
                for p in kinds:
                    assert p.event.wait(10), "stranded request"
            assert sched.pool.n_free == 3
            assert sched.stats()["slots_in_use"] == 0
            ledger = sched.stats()["releases"]
            assert sum(ledger.values()) == n_total
        finally:
            sched.stop()
        assert sched.pool.n_free == 3


class TestDecodeOverHttp:
    """The full stack: both frontends, admission semantics, journal
    replay, /decode/stats, decode metrics in /metrics."""

    @pytest.mark.parametrize("frontend", ["eventloop", "threaded"])
    def test_generate_end_to_end(self, frontend):
        with _serve(frontend=frontend) as srv:
            srv.decoder.decoder.warmup()
            warm = srv.decoder.decoder.n_compiles()
            rng = np.random.default_rng(8)
            url = f"http://{srv.host}:{srv.port}/generate"
            prompts = [_prompt(rng, 2 + i) for i in range(6)]
            results = {}

            def hit(i):
                results[i] = requests.post(
                    url, json={"prompt": prompts[i],
                               "max_new_tokens": 4}, timeout=30)

            threads = [threading.Thread(target=hit, args=(i,))
                       for i in range(len(prompts))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for i, pr in enumerate(prompts):
                r = results[i]
                assert r.status_code == 200, r.text
                assert r.json()["tokens"] == _greedy_reference(pr, 4)
            assert srv.decoder.decoder.n_compiles() == warm
            st = requests.get(
                f"http://{srv.host}:{srv.port}/decode/stats",
                timeout=10).json()
            assert st["slots_in_use"] == 0
            assert st["n_requests"] == 6
            assert st["releases"].get("length") == 6
            body = requests.get(
                f"http://{srv.host}:{srv.port}/metrics?scope=server",
                timeout=10).text
            assert "serving_decode_steps_total" in body
            assert "serving_decode_slots_in_use 0" in body
            assert "serving_prefill_latency_ms" in body

    def test_replay_and_join_semantics(self):
        with _serve() as srv:
            url = f"http://{srv.host}:{srv.port}/generate"
            rng = np.random.default_rng(9)
            prompt = _prompt(rng, 4)
            r1 = requests.post(url, json={"prompt": prompt,
                                          "max_new_tokens": 3},
                               headers={"X-Request-Id": "gen-1"},
                               timeout=30)
            r2 = requests.post(url, json={"prompt": prompt,
                                          "max_new_tokens": 3},
                               headers={"X-Request-Id": "gen-1"},
                               timeout=30)
            assert r1.json() == r2.json()
            assert r2.headers.get("X-Replayed") == "1"
            assert srv.n_replayed == 1
            # exactly one inference ran for the logical request
            assert srv.decoder.stats()["releases"]["length"] == 1

    def test_bad_payload_400_and_retryable_rid(self):
        with _serve() as srv:
            url = f"http://{srv.host}:{srv.port}/generate"
            r = requests.post(url, json={"prompt": []},
                              headers={"X-Request-Id": "bad-1"},
                              timeout=10)
            assert r.status_code == 400
            # the reject removed the in-flight entry: the same rid
            # with a FIXED payload re-admits instead of joining a
            # dead pending
            r = requests.post(url, json={"prompt": [1, 2],
                                         "max_new_tokens": 2},
                              headers={"X-Request-Id": "bad-1"},
                              timeout=30)
            assert r.status_code == 200

    def test_decode_shed_429(self):
        with _serve(decoder_kw=dict(n_slots=2)) as srv:
            srv.decoder.max_waiting = 0    # everything sheds
            url = f"http://{srv.host}:{srv.port}/generate"
            r = requests.post(url, json={"prompt": [1]}, timeout=10)
            assert r.status_code == 429
            assert "Retry-After" in r.headers
            assert srv.n_shed >= 1

    def test_decode_stats_404_without_decoder(self):
        with ServingServer(Identity(), port=0,
                           verify_checkpoints=False) as srv:
            r = requests.get(
                f"http://{srv.host}:{srv.port}/decode/stats",
                timeout=10)
            assert r.status_code == 404

    def test_frame_plane_unaffected_by_decoder(self):
        """The two planes coexist: /predict still serves frames while
        /generate decodes."""
        with _serve() as srv:
            r = requests.post(srv.address, json={"x": 1.5}, timeout=10)
            assert r.status_code == 200
            g = requests.post(
                f"http://{srv.host}:{srv.port}/generate",
                json={"prompt": [5, 6], "max_new_tokens": 2},
                timeout=30)
            assert g.status_code == 200
            assert len(g.json()["tokens"]) == 2


class TestAdaptiveBatchPolicy:
    """The per-bucket adaptive batcher (ROADMAP item 1's policy)."""

    @staticmethod
    def _stats(per_bucket):
        """Synthetic per-bucket dispatch histograms: every sample in
        the bucket that contains service_ms."""
        edges = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0)

        def counts(ms, n):
            out = [0] * (len(edges) + 1)
            for i, e in enumerate(edges):
                if ms <= e:
                    out[i] = n
                    return out
            out[-1] = n
            return out

        return lambda: [(b, edges, counts(ms, n))
                        for b, (ms, n) in per_bucket.items()]

    def test_warmup_contract(self):
        """Below min_count (or without an arrival-rate estimate) the
        policy defers to the fixed knob (None)."""
        clock = ManualClock()
        pol = AdaptiveBatchPolicy(self._stats({8: (2.0, 4)}),
                                  [1, 2, 4, 8], min_count=32,
                                  clock=clock)
        pol.refresh()
        assert pol.decide_wait_ms(1) is None       # too few samples
        pol = AdaptiveBatchPolicy(self._stats({8: (2.0, 100)}),
                                  [1, 2, 4, 8], min_count=32,
                                  clock=clock)
        pol.refresh()
        assert pol.decide_wait_ms(1) is None       # no rate estimate

    def test_converges_on_seeded_arrivals(self):
        """Deterministic seeded arrivals at a fixed rate: the decided
        wait stabilizes (successive decisions equal) and lands where
        the throughput model says — fast arrivals fill the big bucket,
        slow arrivals dispatch immediately."""
        clock = ManualClock()
        stats = self._stats({1: (1.5, 40), 2: (1.5, 40),
                             4: (1.5, 40), 8: (1.5, 40)})
        pol = AdaptiveBatchPolicy(stats, [1, 2, 4, 8], ceiling_ms=10.0,
                                  min_count=32, clock=clock)
        pol.refresh()
        rng = np.random.default_rng(0)
        # ~2000 req/s: gaps of ~0.5 ms with seeded jitter
        for _ in range(200):
            clock.advance(float(rng.uniform(0.0004, 0.0006)))
            pol.note_arrival()
        decisions = [pol.decide_wait_ms(1) for _ in range(3)]
        assert decisions[0] == decisions[1] == decisions[2]
        # filling 8 rows at 2000/s costs ~3.5 ms against a 1.5 ms
        # dispatch: the throughput score picks a real positive wait
        assert 1.0 < decisions[0] <= 10.0
        # a batch already holding 8 rows has nothing to wait for
        assert pol.decide_wait_ms(8) == 0.0
        # slow arrivals (~20/s): filling any bigger bucket busts the
        # ceiling -> dispatch now
        for _ in range(100):
            clock.advance(0.05)
            pol.note_arrival()
        assert pol.decide_wait_ms(1) == 0.0

    def test_idle_lull_resets_rate(self):
        clock = ManualClock()
        pol = AdaptiveBatchPolicy(self._stats({8: (2.0, 100)}),
                                  [1, 8], max_gap_s=5.0, clock=clock)
        pol.refresh()
        for _ in range(10):
            clock.advance(0.001)
            pol.note_arrival()
        assert pol.rate_per_s is not None
        clock.advance(60.0)
        pol.note_arrival()                # first post-lull arrival
        assert pol.rate_per_s is None     # estimate reset, not polluted

    def test_ab_selectable_on_live_server(self):
        """batch_policy='adaptive' serves identically (A/B contract)
        and reports its state via /stats; 'fixed' reports no policy
        state; unknown values refuse."""
        with ServingServer(Identity(), port=0, max_latency_ms=5.0,
                           batch_policy="adaptive",
                           verify_checkpoints=False) as srv:
            for i in range(40):
                r = requests.post(srv.address, json={"x": float(i)},
                                  timeout=10)
                assert r.status_code == 200
            st = requests.get(f"http://{srv.host}:{srv.port}/stats",
                              timeout=10).json()
            assert st["batch_policy"] == "adaptive"
            assert st["adaptive_batch"] is not None
            assert st["adaptive_batch"]["ceiling_ms"] == 5.0
        with ServingServer(Identity(), port=0,
                           verify_checkpoints=False) as srv:
            st = requests.get(f"http://{srv.host}:{srv.port}/stats",
                              timeout=10).json()
            assert st["batch_policy"] == "fixed"
            assert st["adaptive_batch"] is None
        with pytest.raises(ValueError, match="batch_policy"):
            ServingServer(Identity(), port=0, batch_policy="nope",
                          verify_checkpoints=False)

    def test_adaptive_learns_service_table_from_live_histograms(self):
        """On a live adaptive server the refresh cadence populates the
        service-time table from the real per-bucket dispatch
        histograms."""
        with ServingServer(Identity(), port=0, max_latency_ms=2.0,
                           max_batch_size=4, batch_policy="adaptive",
                           verify_checkpoints=False) as srv:
            srv.warmup({"x": 0.0})
            for i in range(40):
                requests.post(srv.address, json={"x": float(i)},
                              timeout=10)
            srv.adaptive_batcher.refresh()
            table = srv.adaptive_batcher.service_ms
            assert table, "no buckets learned"
            assert set(table) <= {1, 2, 4}


class TestSampling:
    """Request-selectable temperature / top-k / top-p sampling over the
    full logits the decode step already returns — greedy stays the
    default (and the device-argmax fast path), seeded sampling is
    bit-reproducible per request."""

    def test_sampler_seeded_determinism(self):
        from mmlspark_tpu.serving.decode import Sampler
        logits = np.random.default_rng(0).normal(size=64)
        a = Sampler(0.8, top_k=16, top_p=0.9, seed=42)
        b = Sampler(0.8, top_k=16, top_p=0.9, seed=42)
        seq_a = [a.sample(logits) for _ in range(20)]
        seq_b = [b.sample(logits) for _ in range(20)]
        assert seq_a == seq_b
        c = Sampler(0.8, top_k=16, top_p=0.9, seed=43)
        assert [c.sample(logits) for _ in range(20)] != seq_a

    def test_top_k_and_top_p_restrict_support(self):
        from mmlspark_tpu.serving.decode import Sampler
        logits = np.arange(64, dtype=np.float64)     # strictly increasing
        s = Sampler(1.0, top_k=4, seed=0)
        picks = {s.sample(logits) for _ in range(200)}
        assert picks <= {60, 61, 62, 63}
        # a tiny nucleus at a peaked distribution pins the argmax
        peaked = np.zeros(64); peaked[7] = 50.0
        s2 = Sampler(1.0, top_p=0.5, seed=0)
        assert {s2.sample(peaked) for _ in range(50)} == {7}

    def test_parse_sampling_validation(self):
        sched = DecodeScheduler(_decoder())
        base = {"prompt": [1, 2, 3]}
        assert sched.parse(base)[2] is None                 # greedy default
        assert sched.parse({**base, "temperature": 0})[2] is None
        s = sched.parse({**base, "temperature": 0.7, "top_k": 5,
                         "top_p": 0.9, "seed": 1})[2]
        assert s is not None and s.temperature == 0.7
        # explicit EFFECTIVE top_k without temperature: sampling at
        # T=1, not silently greedy
        assert sched.parse({**base, "top_k": 3})[2] is not None
        assert sched.parse({**base, "top_p": 0.9})[2] is not None
        # explicit NO-OP knobs (both documented as "off") stay greedy:
        # key presence alone must never flip a request to unseeded
        # full-vocab sampling
        assert sched.parse({**base, "top_k": 0})[2] is None
        assert sched.parse({**base, "top_p": 1.0})[2] is None
        # an EXPLICIT temperature: 0 always wins (0 is documented as
        # greedy), even alongside effective knobs — overriding it to
        # T=1 would hand back exactly the nondeterminism the client
        # asked to avoid
        assert sched.parse({**base, "temperature": 0,
                            "top_p": 0.9})[2] is None
        assert sched.parse({**base, "temperature": 0,
                            "top_k": 5})[2] is None
        for bad in ({"temperature": -1}, {"temperature": "hot"},
                    {"top_k": -2}, {"top_p": 0.0}, {"top_p": 1.5},
                    {"seed": "x"}, {"temperature": True}):
            with pytest.raises(ValueError):
                sched.parse({**base, **bad})

    @pytest.mark.parametrize("frontend", ["eventloop", "threaded"])
    def test_http_seeded_sampling_deterministic(self, frontend):
        with _serve(frontend=frontend) as srv:
            srv.decoder.decoder.warmup()
            warm = srv.decoder.decoder.n_compiles()
            url = f"http://{srv.host}:{srv.port}/generate"
            rng = np.random.default_rng(3)
            prompt = _prompt(rng, 4)
            body = {"prompt": prompt, "max_new_tokens": 6,
                    "temperature": 0.9, "top_k": 16, "seed": 1234}
            r1 = requests.post(url, json=body, timeout=30)
            r2 = requests.post(url, json=body, timeout=30)
            assert r1.status_code == r2.status_code == 200
            # same seed -> the same sampled sequence, across requests
            assert r1.json()["tokens"] == r2.json()["tokens"]
            r3 = requests.post(url, json={**body, "seed": 99},
                               timeout=30)
            greedy = requests.post(
                url, json={"prompt": prompt, "max_new_tokens": 6},
                timeout=30)
            assert greedy.json()["tokens"] == _greedy_reference(prompt, 6)
            # different seed virtually always diverges at T=0.9 over 6
            # tokens; equality of all three would mean sampling is off
            assert not (r3.json()["tokens"] == r1.json()["tokens"]
                        == greedy.json()["tokens"])
            # sampling never grows the compiled-shape set (host-side
            # sampling over logits the step already returns)
            assert srv.decoder.decoder.n_compiles() == warm
            r400 = requests.post(
                url, json={"prompt": prompt, "temperature": -2},
                timeout=30)
            assert r400.status_code == 400

    def test_mixed_greedy_and_sampled_slots(self):
        """A sampled request sharing the step batch must not perturb a
        greedy neighbour (slot independence extends to sampling)."""
        with _serve() as srv:
            url = f"http://{srv.host}:{srv.port}/generate"
            rng = np.random.default_rng(5)
            g_prompt, s_prompt = _prompt(rng, 3), _prompt(rng, 5)
            results = {}

            def hit(name, body):
                results[name] = requests.post(url, json=body, timeout=30)

            threads = [
                threading.Thread(target=hit, args=("greedy", {
                    "prompt": g_prompt, "max_new_tokens": 5})),
                threading.Thread(target=hit, args=("sampled", {
                    "prompt": s_prompt, "max_new_tokens": 5,
                    "temperature": 1.2, "seed": 7})),
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert results["greedy"].json()["tokens"] == \
                _greedy_reference(g_prompt, 5)
            assert results["sampled"].status_code == 200
            assert len(results["sampled"].json()["tokens"]) == 5


# ---------------------------------------------------------------------------
# ISSUE 11: paged KV cache, speculative decoding, streamed tokens
# ---------------------------------------------------------------------------


def _read_chunked_sse(sock):
    """Read one chunked HTTP response off ``sock``; returns
    ``(head_bytes, [parsed SSE event dicts])``."""
    buf = b""
    while b"\r\n\r\n" not in buf:
        buf += sock.recv(65536)
    head, _, rest = buf.partition(b"\r\n\r\n")
    data = rest
    while b"0\r\n\r\n" not in data:
        chunk = sock.recv(65536)
        if not chunk:
            break
        data += chunk
    body = b""
    while data:
        line, _, data = data.partition(b"\r\n")
        if not line:
            continue
        n = int(line, 16)
        if n == 0:
            break
        body += data[:n]
        data = data[n + 2:]
    events = [json.loads(e.split(b"data: ", 1)[1])
              for e in body.split(b"\n\n") if e.strip()]
    return head, events


def _post_raw(host, port, path, payload):
    import socket as _socket
    s = _socket.create_connection((host, port), timeout=30)
    body = json.dumps(payload).encode()
    s.sendall(b"POST %s HTTP/1.1\r\nHost: x\r\n"
              b"Content-Type: application/json\r\n"
              b"Content-Length: %d\r\n\r\n%s"
              % (path.encode(), len(body), body))
    return s


class TestPagedScheduler:
    """The paged decode plane end to end: block-table goldens through
    the scheduler, page-leak ledger over every release reason,
    page-exhaustion admission, mid-decode preemption."""

    def test_scheduler_tokens_match_greedy_reference(self):
        """Four prompt lengths through the scheduler (two slots over
        a nine-page pool) produce the full-context greedy
        sequences."""
        rng = np.random.default_rng(21)
        prompts = [_prompt(rng, n) for n in (1, 3, 6, 9)]
        sched = DecodeScheduler(
            _decoder(n_slots=2, page_size=8, n_pages=9)).start()
        try:
            pendings = [_Pending({"prompt": p,
                                  "max_new_tokens": 5}, f"r{i}")
                        for i, p in enumerate(prompts)]
            for p in pendings:
                sched.submit(p)
            for p in pendings:
                assert p.event.wait(30)
            outs = [json.loads(p.reply)["tokens"] for p in pendings]
        finally:
            sched.stop()
        assert sched.pool.n_free == 2
        for pr, toks in zip(prompts, outs):
            assert toks == _greedy_reference(pr, 5)

    def test_page_reclaim_after_every_release_reason(self):
        """EOS, token budget, deadline, cancel, disconnect-shaped
        cancel, and an injected step fault all return their pages:
        the ledger ends at n_free == n_pages - 1 with every reason
        accounted."""
        clock = ManualClock()
        rng = np.random.default_rng(22)
        eos_prompt = _prompt(rng, 3)
        eos = _greedy_reference(eos_prompt, 3)[1]
        # a lane long enough that the requests this test cancels and
        # lets expire are still decoding when it does (2,044 steps to
        # the lane's end)
        sched = DecodeScheduler(
            _decoder(n_slots=3, max_len=2048, page_size=8,
                     eos_id=eos),
            clock=clock).start()
        try:
            waves = [
                [_Pending({"prompt": eos_prompt,
                           "max_new_tokens": 8}, "w-eos"),
                 _Pending({"prompt": _prompt(rng, 4),
                           "max_new_tokens": 2}, "w-len")],
                [_Pending({"prompt": _prompt(rng, 4),
                           "max_new_tokens": 10_000}, "w-cancel"),
                 _Pending({"prompt": _prompt(rng, 4),
                           "max_new_tokens": 10_000}, "w-deadline",
                          deadline=Deadline(1.0, clock=clock))],
                [_Pending({"prompt": _prompt(rng, 4),
                           "max_new_tokens": 10_000}, "w-fault")],
            ]
            for p in waves[0]:
                sched.submit(p)
            for p in waves[0]:
                assert p.event.wait(30)
            # the EOS wave is done: no later request may end on the
            # stop token by accident (of this seed's prompts, the one
            # meant to expire emits it as its FIRST token and the one
            # meant to be cancelled ran to its lane's end unobserved)
            sched.decoder.eos_id = None
            for p in waves[1]:
                sched.submit(p)
            t_end = time.monotonic() + 10
            while sched.stats()["slots_in_use"] < 2 and \
                    time.monotonic() < t_end:
                time.sleep(0.002)
            sched.cancel("w-cancel")
            clock.advance(2.0)
            for p in waves[1]:
                assert p.event.wait(30)
            # arm the fault only now, so the earlier waves' reasons
            # are deterministic however many steps they consumed
            sched.fault_plan = FaultPlan(
                script={"decode_step": ["fail"]})
            for p in waves[2]:
                sched.submit(p)          # rides into the scripted fault
            for p in waves[2]:
                assert p.event.wait(30)
            sched.fault_plan = None
            reasons = {p.rid: json.loads(p.reply)["finish_reason"]
                       for wave in waves for p in wave}
            assert reasons == {"w-eos": "eos", "w-len": "length",
                               "w-cancel": "cancelled",
                               "w-deadline": "deadline",
                               "w-fault": "error"}
        finally:
            sched.stop()
        assert sched.pool.n_free == 3
        assert _pages_idle(sched)
        assert sched.pages.high_water > 0

    def test_page_exhaustion_429_then_readmit(self):
        """A pool-filling decode makes the next submit shed
        DecodeOverloaded (the server's 429 + Retry-After); once pages
        free, the same request admits and completes."""
        # 4 claimable pages of 4 rows; a 13-token prompt claims all 4
        sched = DecodeScheduler(
            _decoder(n_slots=2, max_len=16, page_size=4,
                     n_pages=5)).start()
        rng = np.random.default_rng(23)
        try:
            hog = _Pending({"prompt": _prompt(rng, 13),
                            "max_new_tokens": 2}, "hog")
            sched.submit(hog)
            t_end = time.monotonic() + 10
            while sched.pages.n_free > 0 and time.monotonic() < t_end:
                time.sleep(0.001)
            victim = _Pending({"prompt": _prompt(rng, 4),
                               "max_new_tokens": 2}, "victim")
            with pytest.raises(DecodeOverloaded, match="page pool"):
                sched.submit(victim)
            assert hog.event.wait(30)
            retry = _Pending({"prompt": _prompt(rng, 4),
                              "max_new_tokens": 2}, "victim")
            sched.submit(retry)
            assert retry.event.wait(30)
            assert retry.status == 200
        finally:
            sched.stop()
        assert _pages_idle(sched)

    def test_mid_decode_page_preempt_never_ooms(self):
        """When running slots outgrow the pool, the starved request
        finishes with its partial tokens (finish_reason
        pages_exhausted) — no OOM, no stall, pages accounted."""
        # 3 claimable pages of 4 rows: two 5-token prompts admit at 2
        # pages each? no — 2 pages needed each, only 3 exist, so the
        # second waits; instead one slot grows past its claim
        sched = DecodeScheduler(
            _decoder(n_slots=2, max_len=16, page_size=4,
                     n_pages=4)).start()
        rng = np.random.default_rng(24)
        try:
            a = _Pending({"prompt": _prompt(rng, 6),
                          "max_new_tokens": 12}, "a")   # 2 pages now,
            b = _Pending({"prompt": _prompt(rng, 2),    # grows to 4
                          "max_new_tokens": 2}, "b")    # 1 page
            sched.submit(a)
            sched.submit(b)
            assert a.event.wait(30) and b.event.wait(30)
            out_a = json.loads(a.reply)
            assert b.status == 200
            # a could not reach 12 new tokens on 12 claimable rows
            # alongside b: it preempted with partial output
            assert out_a["finish_reason"] in ("pages_exhausted",
                                              "length")
            if out_a["finish_reason"] == "pages_exhausted":
                assert sched.n_page_preempts >= 1
                assert 0 < out_a["n_tokens"] < 12
        finally:
            sched.stop()
        assert _pages_idle(sched)
        assert sched.pool.n_free == 2

    def test_undersized_pool_raises_without_scheduler_tables(self):
        dec = _decoder(n_slots=2, max_len=16, page_size=4,
                       n_pages=4)
        with pytest.raises(ValueError, match="PagePool"):
            dec.prefill(0, np.asarray([1, 2], np.int32))

    def test_prompt_ladder_derived_not_scanned(self):
        from mmlspark_tpu.parallel.sharding import (
            bucket_ladder, bucket_target,
        )
        dec = _decoder(max_len=32)
        assert dec.prompt_buckets() == bucket_ladder(32) == sorted(
            {bucket_target(n, 32) for n in range(1, 33)})


class TestTwoRowKinds:
    """The cache manager with a decoder that holds TWO kinds of row in
    the one page pool (the EVA block kind, ``serving/eva_decode.py``:
    window 16, chunk 4, pages of 4 rows, so a window is 4 pages and
    leaves 1 summary page): rows of both kinds claimed, compacted and
    released, counted as the decoder says they are, on the release
    path every request shares."""

    E_CFG = None

    @classmethod
    def _decoder(cls, **kw):
        from mmlspark_tpu.models import evabyte as E
        from mmlspark_tpu.serving.decode import decoder_for
        if cls.E_CFG is None:
            cls.E_CFG = E.EvaByteConfig(
                vocab=64, d_model=16, n_heads=2, d_head=8, d_ff=32,
                n_layers=2, window=16, chunk=4, n_pred_heads=2,
                init_std=0.2, dtype="float32")
            cls.E_PARAMS = E.init_params(cls.E_CFG, seed=3)
        kw = dict(dict(n_slots=2, max_len=64, page_size=4,
                       attn_impl="dense"), **kw)
        return decoder_for(cls.E_PARAMS, cls.E_CFG, **kw)

    def test_pages_of_both_kinds_by_position(self):
        """A request at position ``pos`` holds ``pos // 16`` summary
        pages and the pages of ``pos % 16 + 1`` window rows: 40 rows of
        one kind would be 10 pages, here the most is 2 + 4."""
        sched = DecodeScheduler(self._decoder()).start()
        rng = np.random.default_rng(41)
        seen = {}
        inner = sched.decoder.step_logits

        def step_logits(tokens, pos, tables=None):
            req = sched._active.get(0)
            if req is not None:
                seen[int(pos[0])] = (len(req.sum_pages), len(req.pages),
                                     sched.pages.n_claimed)
            return inner(tokens, pos, tables)

        sched.decoder.step_logits = step_logits
        try:
            p = _Pending({"prompt": _prompt(rng, 21),
                          "max_new_tokens": 20}, "two-kinds")
            sched.submit(p)
            assert p.event.wait(30)
            stats = sched.stats()
        finally:
            sched.stop()
        assert json.loads(p.reply)["finish_reason"] == "length"
        for pos, (n_sum, n_win, claimed) in seen.items():
            assert (n_sum, n_win) == (pos // 16, (pos % 16) // 4 + 1), pos
            assert claimed == n_sum + n_win
        assert sorted(seen) == list(range(21, 40))
        # the prompt's first window inside the prefill, the second by
        # the loop after the step at position 31
        assert stats["n_compactions"] == 2
        assert stats["loop"]["compact"]["n"] == 1
        assert stats["window_rows"] == 0 and stats["summary_rows"] == 0
        # the compaction at 32 holds the full window, the first
        # window's summary page and the new one for a moment
        assert sched.pages.high_water == 1 + 4 + 1
        assert _pages_idle(sched)

    @pytest.mark.parametrize("reason", ["length", "eos", "cancelled",
                                        "deadline", "error",
                                        "pages_exhausted"])
    def test_pool_back_to_its_start_after_every_release_reason(
            self, reason):
        """Both kinds of page come back whatever ends the request, and
        a request that ends past a compaction held summary pages."""
        clock = ManualClock()
        rng = np.random.default_rng(42)
        prompt = _prompt(rng, 20)
        kw = {}
        if reason == "pages_exhausted":
            # one window's pages and one summary page, and not the
            # second summary page the compaction at position 32 needs
            kw["n_pages"] = 1 + 4 + 1
        dec = self._decoder(**kw)
        if reason == "eos":
            probe = DecodeScheduler(dec).start()
            p = _Pending({"prompt": prompt, "max_new_tokens": 16}, "probe")
            probe.submit(p)
            assert p.event.wait(30)
            probe.stop()
            dec.eos_id = json.loads(p.reply)["tokens"][14]
        sched = DecodeScheduler(dec, clock=clock).start()
        held = {}
        inner = dec.step_logits

        def step_logits(tokens, pos, tables=None):
            req = sched._active.get(0)
            if req is not None and int(pos[0]) == 34:
                # past the compaction at 32: hold here until released
                held["sum_pages"] = len(req.sum_pages)
                if reason == "cancelled":
                    sched.cancel("r")
                elif reason == "deadline":
                    clock.advance(5.0)
                elif reason == "error":
                    raise RuntimeError("scripted step fault")
            return inner(tokens, pos, tables)

        dec.step_logits = step_logits
        try:
            p = _Pending({"prompt": prompt,
                          "max_new_tokens": 16 if reason == "length"
                          else 40}, "r",
                         deadline=Deadline(1.0, clock=clock)
                         if reason == "deadline" else None)
            sched.submit(p)
            assert p.event.wait(30)
        finally:
            sched.stop()
        out = json.loads(p.reply)
        assert out["finish_reason"] == reason, out
        if reason == "pages_exhausted":
            # it ended AT the compaction it could not hold: the
            # prefill's byte and those of the steps at 20..31, partial
            # output, no fault
            assert out["n_tokens"] == 13 and sched.n_page_preempts == 1
        elif reason != "eos":
            assert held["sum_pages"] == 2
        assert sched.pool.n_free == 2
        assert _pages_idle(sched)
        assert sched.releases == {reason: 1}

    def test_admission_counts_the_prefills_peak(self):
        """A prompt past its first window needs a whole window's pages
        while it is walked: a pool one page short sheds at submit; the
        same pool admits a prompt that fits."""
        # 1 summary page + 4 window pages are the 18-byte prompt's peak
        sched = DecodeScheduler(self._decoder(n_pages=1 + 4)).start()
        rng = np.random.default_rng(43)
        try:
            with pytest.raises(DecodeOverloaded, match="5 pages"):
                sched.submit(_Pending({"prompt": _prompt(rng, 18),
                                       "max_new_tokens": 2}, "big"))
            ok = _Pending({"prompt": _prompt(rng, 12),
                           "max_new_tokens": 3}, "fits")
            sched.submit(ok)
            assert ok.event.wait(30) and ok.status == 200
        finally:
            sched.stop()
        assert _pages_idle(sched)

    def test_prepare_stamps_the_rows_by_kind(self):
        from mmlspark_tpu.core.tracing import Tracer
        from mmlspark_tpu.serving.decode import pass_view
        tracer = Tracer()
        sched = DecodeScheduler(self._decoder(), tracer=tracer).start()
        rng = np.random.default_rng(44)
        try:
            p = _Pending({"prompt": _prompt(rng, 37),
                          "max_new_tokens": 3}, "rows")
            sched.submit(p)
            assert p.event.wait(30)
        finally:
            sched.stop()
        views = [pass_view(sp.attrs["phases"])
                 for sp in tracer.recorder.scan("decode.pass")]
        pre, = [q for v in views for q in v["prefills"]]
        assert pre["windows"] == 3 and pre["summary_rows_written"] == 8
        steps = [v for v in views if "dispatch" in v["phases_ms"]]
        # positions 37 and 38: 8 summary rows, 6 then 7 window rows
        assert [(v["summary_rows"], v["window_rows"]) for v in steps] \
            == [(8, 6), (8, 7)]


class TestPrefixScheduler:
    """The cross-request prefix cache end to end (ISSUE 15): radix
    hits through the scheduler with exact parity, shared-page
    immutability, 429-before-shared-state admission, eviction under
    pressure, and the refcount ledger under chaos."""

    def _shared_prompts(self, seed, head_len=9, n=4, tail=3):
        rng = np.random.default_rng(seed)
        head = _prompt(rng, head_len)
        return head, [head + _prompt(rng, tail) for _ in range(n)]

    def _run(self, sched, payloads, timeout=60):
        ps = [_Pending(p, f"px{i}") for i, p in enumerate(payloads)]
        for p in ps:
            sched.submit(p)
        for p in ps:
            assert p.event.wait(timeout), "stranded"
        return ps

    def test_hits_match_reference_with_flat_compiles(self):
        sched = DecodeScheduler(
            _decoder(n_slots=2, page_size=4)).start()
        try:
            warm = sched.decoder.warmup()
            head, prompts = self._shared_prompts(61)
            prompts.append(head)        # exact-prefix prompt rides too
            done = self._run(sched, [
                {"prompt": pr, "max_new_tokens": 4} for pr in prompts])
            for pr, p in zip(prompts, done):
                assert json.loads(p.reply)["tokens"] == \
                    _greedy_reference(pr, 4)
            pc = sched.stats()["prefix_cache"]
            assert pc["hits"] >= 3 and pc["hit_tokens"] >= 24
            assert sched.decoder.n_compiles() == warm
        finally:
            sched.stop()
        assert _pages_idle(sched)

    def test_sampled_and_cacheoff_parity(self):
        """Seeded sampling through a prefix hit draws the same tokens
        as with the cache disabled — offset prefill is exact."""
        outs = {}
        for on in (False, True):
            sched = DecodeScheduler(
                _decoder(n_slots=2, page_size=4,
                         prefix_cache=on)).start()
            try:
                head, prompts = self._shared_prompts(62)
                done = self._run(sched, [
                    {"prompt": pr, "max_new_tokens": 5,
                     "temperature": 0.8, "top_k": 8, "seed": 99}
                    for pr in prompts])
                outs[on] = [json.loads(p.reply)["tokens"]
                            for p in done]
            finally:
                sched.stop()
        assert outs[True] == outs[False]

    def test_shared_pages_are_immutable(self):
        """The invariant sharing rests on: an attaching request NEVER
        writes a shared prefix page (decode appends only to its
        private tail) — cached page content is bit-stable across a
        full borrow/decode/release cycle."""
        sched = DecodeScheduler(
            _decoder(n_slots=2, page_size=4)).start()
        try:
            head, prompts = self._shared_prompts(63, head_len=9)
            (first,) = self._run(sched, [
                {"prompt": prompts[0], "max_new_tokens": 3}])
            pc = sched.prefix
            with pc._lock:
                cached = [ch.page for ch in
                          pc._root.children.values()]
                assert cached
            # the pool is one array a layer: stacked, [L, pages, ...]
            before = {p: np.stack(
                sched.decoder.cache["k"])[:, p].copy()
                for p in cached}
            done = self._run(sched, [
                {"prompt": pr, "max_new_tokens": 6}
                for pr in prompts[1:]])
            assert all(p.status == 200 for p in done)
            assert sched.stats()["prefix_cache"]["hits"] >= 1
            after = np.stack(sched.decoder.cache["k"])
            for p, snap in before.items():
                assert np.array_equal(snap, after[:, p]), \
                    f"shared page {p} was mutated"
        finally:
            sched.stop()
        assert _pages_idle(sched)

    def test_admission_429_before_touching_shared_state(self):
        """A submit the pool cannot hold (even counting evictable
        cached pages) sheds WITHOUT a lookup, a ref, or an eviction."""
        sched = DecodeScheduler(
            _decoder(n_slots=2, max_len=16, page_size=4, n_pages=5))
        sched.start()
        rng = np.random.default_rng(64)
        try:
            hog = _Pending({"prompt": _prompt(rng, 13),
                            "max_new_tokens": 10_000}, "hog")
            sched.submit(hog)
            t_end = time.monotonic() + 10
            while sched.pages.n_free > 0 and time.monotonic() < t_end:
                time.sleep(0.001)
            lookups_before = sched.prefix.n_lookups
            evicted_before = sched.prefix.n_evicted
            with pytest.raises(DecodeOverloaded, match="page pool"):
                sched.submit(_Pending({"prompt": _prompt(rng, 8),
                                       "max_new_tokens": 2}, "v"))
            assert sched.prefix.n_lookups == lookups_before
            assert sched.prefix.n_evicted == evicted_before
            sched.cancel("hog")
            assert hog.event.wait(30)
        finally:
            sched.stop()
        assert _pages_idle(sched)

    def test_eviction_under_pressure_all_complete(self):
        """Non-overlapping prompts churning a small pool force LRU
        eviction of cached pages; every request still completes and
        the ledger ends clean."""
        sched = DecodeScheduler(
            _decoder(n_slots=2, max_len=32, page_size=4,
                     n_pages=17)).start()
        rng = np.random.default_rng(65)
        try:
            prompts = [_prompt(rng, 9) for _ in range(10)]
            done = self._run(sched, [
                {"prompt": pr, "max_new_tokens": 3}
                for pr in prompts])
            for pr, p in zip(prompts, done):
                assert json.loads(p.reply)["tokens"] == \
                    _greedy_reference(pr, 3)
            assert sched.prefix.n_evicted > 0
        finally:
            sched.stop()
        assert _pages_idle(sched)

    @pytest.mark.chaos
    def test_chaos_on_shared_pages_keeps_refcounts_coherent(self):
        """Mid-decode cancel, deadline expiry, and an injected step
        fault on requests HOLDING shared prefix pages: refcounts end
        coherent, the survivors' cached pages stay valid, and the
        idle invariant holds (the sharing analogue of
        test_page_reclaim_after_every_release_reason)."""
        clock = ManualClock()
        sched = DecodeScheduler(
            _decoder(n_slots=3, max_len=256, page_size=4),
            clock=clock).start()
        try:
            head, prompts = self._shared_prompts(66, n=3)
            # seed the cache (cold publish), then attach three readers
            self._run(sched, [{"prompt": prompts[0],
                               "max_new_tokens": 2}])
            waves = [
                _Pending({"prompt": prompts[0],
                          "max_new_tokens": 10_000}, "c-cancel"),
                _Pending({"prompt": prompts[1],
                          "max_new_tokens": 10_000}, "c-deadline",
                         deadline=Deadline(1.0, clock=clock)),
                _Pending({"prompt": prompts[2],
                          "max_new_tokens": 10_000}, "c-fault"),
            ]
            for p in waves:
                sched.submit(p)
            t_end = time.monotonic() + 10
            while sched.stats()["slots_in_use"] < 3 and \
                    time.monotonic() < t_end:
                time.sleep(0.002)
            # all three happen while sharing the head's pages
            sched.cancel("c-cancel")
            clock.advance(2.0)
            sched.fault_plan = FaultPlan(
                script={"decode_step": ["fail"]})
            for p in waves:
                assert p.event.wait(30)
            sched.fault_plan = None
            reasons = {json.loads(p.reply)["finish_reason"]
                       for p in waves}
            assert {"cancelled"} <= reasons
            # the cache survived the churn: a fresh reader still hits
            # and decodes correctly
            (again,) = self._run(sched, [
                {"prompt": prompts[1], "max_new_tokens": 4}])
            assert json.loads(again.reply)["tokens"] == \
                _greedy_reference(prompts[1], 4)
        finally:
            sched.stop()
        assert sched.pool.n_free == 3
        assert _pages_idle(sched)

    @pytest.mark.chaos
    def test_preempt_while_sharing_keeps_ledger(self):
        """A request that grows into pages_exhausted while HOLDING
        shared pages releases its refs without dropping the cache's —
        and the 'error' publish refusal keeps faulted content out of
        the index."""
        sched = DecodeScheduler(
            _decoder(n_slots=2, max_len=32, page_size=4,
                     n_pages=11)).start()
        rng = np.random.default_rng(67)
        try:
            head = _prompt(rng, 9)
            self._run(sched, [{"prompt": head + _prompt(rng, 2),
                               "max_new_tokens": 2}])
            # two readers attach the cached head and grow until the
            # pool (10 claimable) runs out: at least one preempts
            done = self._run(sched, [
                {"prompt": head + _prompt(rng, 2),
                 "max_new_tokens": 30} for _ in range(2)])
            reasons = {json.loads(p.reply)["finish_reason"]
                       for p in done}
            assert reasons <= {"pages_exhausted", "length"}
        finally:
            sched.stop()
        assert _pages_idle(sched)


class TestStreaming:
    """Token streaming (ISSUE 11): chunked SSE over both frontends,
    incremental events consistent with the terminal reply, keep-alive
    preserved, and a mid-stream disconnect that frees slot AND
    pages."""

    @pytest.mark.parametrize("frontend", ["eventloop", "threaded"])
    def test_streamed_generate(self, frontend):
        with _serve(frontend=frontend) as srv:
            rng = np.random.default_rng(31)
            prompt = _prompt(rng, 3)
            s = _post_raw(srv.host, srv.port, "/generate?stream=1",
                          {"prompt": prompt, "max_new_tokens": 5})
            head, events = _read_chunked_sse(s)
            assert b" 200 " in head.split(b"\r\n")[0]
            assert b"text/event-stream" in head
            assert b"chunked" in head.lower()
            toks = [e["token"] for e in events if "done" not in e]
            final = [e for e in events if e.get("done")][0]
            assert final["tokens"] == _greedy_reference(prompt, 5)
            assert toks == final["tokens"]
            assert [e["i"] for e in events
                    if "done" not in e] == list(range(5))
            assert final["finish_reason"] == "length"
            # keep-alive: a plain decode on the SAME socket
            body = json.dumps({"prompt": prompt,
                               "max_new_tokens": 2}).encode()
            s.sendall(b"POST /generate HTTP/1.1\r\nHost: x\r\n"
                      b"Content-Length: %d\r\n\r\n%s"
                      % (len(body), body))
            buf = b""
            t_end = time.monotonic() + 20
            while b"\r\n\r\n" not in buf or b"tokens" not in buf:
                c = s.recv(65536)
                if not c or time.monotonic() > t_end:
                    break
                buf += c
            assert b" 200 " in buf.split(b"\r\n")[0]
            s.close()
            assert srv.decoder.pool.n_free == \
                srv.decoder.decoder.n_slots

    def test_stream_flag_in_payload(self):
        """`"stream": true` in the body streams too (no query)."""
        with _serve() as srv:
            rng = np.random.default_rng(32)
            prompt = _prompt(rng, 4)
            s = _post_raw(srv.host, srv.port, "/generate",
                          {"prompt": prompt, "max_new_tokens": 3,
                           "stream": True})
            head, events = _read_chunked_sse(s)
            s.close()
            assert [e for e in events if e.get("done")]

    def test_stream_bad_payload_is_plain_400(self):
        """Sync rejects must never send the chunked 200 head."""
        with _serve() as srv:
            s = _post_raw(srv.host, srv.port, "/generate?stream=1",
                          {"prompt": []})
            buf = b""
            while b"\r\n\r\n" not in buf:
                buf += s.recv(65536)
            assert b" 400 " in buf.split(b"\r\n")[0]
            assert b"text/event-stream" not in buf
            s.close()

    @pytest.mark.parametrize("frontend", ["eventloop", "threaded"])
    @pytest.mark.chaos
    def test_mid_stream_disconnect_frees_slot_and_pages(
            self, frontend):
        with _serve(frontend=frontend) as srv:
            sched = srv.decoder
            rng = np.random.default_rng(33)
            s = _post_raw(srv.host, srv.port, "/generate?stream=1",
                          {"prompt": _prompt(rng, 3),
                           "max_new_tokens": 100_000})
            # see the 200 head (stream live), then slam the socket
            assert b" 200 " in s.recv(4096)[:20]
            s.close()
            # poll for the TERMINAL event (the disconnect release),
            # not for a free pool: before the request claims its slot
            # (admission can still be inside the prefill compile) the
            # pool is trivially all-free and sampling the release
            # ledger then is a race, not a check
            t_end = time.monotonic() + 15
            while time.monotonic() < t_end and \
                    not sched.stats()["releases"].get(
                        "disconnected", 0):
                time.sleep(0.02)
            assert sched.stats()["releases"].get(
                "disconnected", 0) >= 1
            assert sched.pool.n_free == sched.decoder.n_slots
            assert _pages_idle(sched)

    def test_stream_stats_surface(self):
        with _serve() as srv:
            rng = np.random.default_rng(34)
            s = _post_raw(srv.host, srv.port, "/generate?stream=1",
                          {"prompt": _prompt(rng, 3),
                           "max_new_tokens": 3})
            _read_chunked_sse(s)
            s.close()
            st = requests.get(
                f"http://{srv.host}:{srv.port}/stats",
                timeout=10).json()
            fr = st["frontend"]
            assert fr["streams_total"] >= 1
            assert fr["stream_events_total"] >= 4   # 3 tokens + done
            body = requests.get(
                f"http://{srv.host}:{srv.port}/metrics?scope=server",
                timeout=10).text
            assert "serving_streams_total" in body
            assert "serving_decode_pages_free" in body


def _spec_setup(n_slots=3, max_len=64, spec_k=4, **kw):
    from mmlspark_tpu.testing.decode_load import make_spec_model_pair
    cfg = T.TransformerConfig(vocab=64, d_model=16, n_heads=2,
                              d_head=8, d_ff=32, n_stages=1,
                              layers_per_stage=4)
    params, draft_params, draft_cfg = make_spec_model_pair(
        cfg, draft_layers=1)
    dec = TransformerDecoder(params, cfg, n_slots=n_slots,
                             max_len=max_len,
                             draft_params=draft_params,
                             draft_cfg=draft_cfg, spec_k=spec_k, **kw)
    return params, cfg, dec


def _spec_greedy_reference(params, cfg, prompt, n_new):
    ctx = [int(t) for t in prompt]
    out = []
    for _ in range(n_new):
        lg = T.reference_logits(
            params, jnp.asarray(np.asarray(ctx, np.int32))[None], cfg)
        t = int(jnp.argmax(lg[0, -1]))
        out.append(t)
        ctx.append(t)
    return out


class TestSpeculativeScheduler:
    """Speculative decoding through the scheduler: exact greedy
    parity, per-slot enable, seeded-sampling determinism, acceptance
    metrics, and the acceptance-gated policy."""

    def _run(self, sched, payloads, timeout=60):
        ps = [_Pending(p, f"s{i}") for i, p in enumerate(payloads)]
        for p in ps:
            sched.submit(p)
        for p in ps:
            assert p.event.wait(timeout), "stranded"
        return ps

    @pytest.mark.slow
    def test_greedy_parity_and_acceptance(self):
        params, cfg, dec = _spec_setup()
        sched = DecodeScheduler(dec).start()
        try:
            warm = dec.warmup()
            rng = np.random.default_rng(41)
            prompts = [[int(t) for t in rng.integers(0, 64, size=n)]
                       for n in (3, 5, 7)]
            done = self._run(sched, [
                {"prompt": pr, "max_new_tokens": 10}
                for pr in prompts])
            for pr, p in zip(prompts, done):
                assert json.loads(p.reply)["tokens"] == \
                    _spec_greedy_reference(params, cfg, pr, 10)
            st = sched.stats()["speculative"]
            assert st["rounds"] > 0 and st["proposed"] > 0
            assert st["acceptance_rate"] is not None
            assert dec.n_compiles() == warm   # spec shapes all warmed
        finally:
            sched.stop()
        assert sched.pool.n_free == 3
        assert _pages_idle(sched)

    def test_per_slot_opt_out(self):
        params, cfg, dec = _spec_setup()
        sched = DecodeScheduler(dec).start()
        try:
            rng = np.random.default_rng(42)
            pr = [int(t) for t in rng.integers(0, 64, size=4)]
            done = self._run(sched, [
                {"prompt": pr, "max_new_tokens": 6,
                 "speculative": False}])
            assert json.loads(done[0].reply)["tokens"] == \
                _spec_greedy_reference(params, cfg, pr, 6)
            assert sched.stats()["speculative"]["rounds"] == 0
        finally:
            sched.stop()

    def test_sampled_spec_seeded_determinism(self):
        """Rejection-sampled speculation is bit-reproducible per seed
        (the request's own PRNG drives draft draws AND accept
        draws)."""
        params, cfg, dec = _spec_setup()
        sched = DecodeScheduler(dec).start()
        try:
            rng = np.random.default_rng(43)
            pr = [int(t) for t in rng.integers(0, 64, size=5)]
            body = {"prompt": pr, "max_new_tokens": 8,
                    "temperature": 0.9, "seed": 77,
                    "speculative": True}
            a = self._run(sched, [dict(body)])
            b = self._run(sched, [dict(body)])
            ta = json.loads(a[0].reply)["tokens"]
            tb = json.loads(b[0].reply)["tokens"]
            assert ta == tb and len(ta) == 8
            assert sched.stats()["speculative"]["rounds"] > 0
        finally:
            sched.stop()

    def test_spec_requires_matching_vocab(self):
        """A draft over another vocabulary is refused, and the unpaged
        target is not an option any more (gone, not ignored)."""
        import dataclasses
        from mmlspark_tpu.testing.decode_load import (
            make_spec_model_pair,
        )
        cfg = T.TransformerConfig(vocab=64, d_model=16, n_heads=2,
                                  d_head=8, d_ff=32, n_stages=1,
                                  layers_per_stage=4)
        params, dp, dcfg = make_spec_model_pair(cfg, draft_layers=1)
        with pytest.raises(ValueError, match="share a vocab"):
            TransformerDecoder(
                params, cfg, n_slots=2, max_len=32, draft_params=dp,
                draft_cfg=dataclasses.replace(dcfg, vocab=32))
        with pytest.raises(TypeError, match="paged"):
            TransformerDecoder(params, cfg, n_slots=2, max_len=32,
                               paged=False)

    def test_speculation_policy_gates_rounds(self):
        from mmlspark_tpu.serving.policy import SpeculationPolicy
        pol = SpeculationPolicy(min_rate=0.5, warmup_rounds=2,
                                reprobe_every=4)
        assert pol.should_speculate()          # warmup always on
        pol.note(8, 8)
        pol.note(8, 8)
        assert pol.should_speculate()          # healthy acceptance
        for _ in range(30):
            pol.note(8, 0)                     # acceptance collapses
        decisions = [pol.should_speculate() for _ in range(8)]
        assert decisions.count(True) == 2      # probes only (every 4)
        assert pol.status()["speculating"] is False
        pol2 = SpeculationPolicy()
        sched = DecodeScheduler(_spec_setup()[2], spec_policy=pol2)
        assert sched.spec_policy is pol2       # injectable


class TestReviewHardening:
    """Regression pins for the PR 11 review findings."""

    def test_page_size_must_be_power_of_two(self):
        """page_size=24 divides max_len=96 but cannot chunk the pow2
        prompt buckets — the constructor must refuse, not crash at
        prefill."""
        with pytest.raises(ValueError, match="power of two"):
            _decoder(max_len=96, page_size=24)
        _decoder(max_len=96, page_size=32)   # fine

    def test_stream_query_parsed_not_substringed(self):
        """?stream=10 / ?upstream=1 must NOT upgrade to SSE."""
        from mmlspark_tpu.serving.server import _stream_requested
        assert _stream_requested("/generate?stream=1", {})
        assert _stream_requested("/generate?a=b&stream=1", {})
        assert not _stream_requested("/generate?stream=10", {})
        assert not _stream_requested("/generate?upstream=1", {})
        assert not _stream_requested("/generate", {"stream": 1})
        assert _stream_requested("/generate", {"stream": True})

    def test_wedged_stream_reaped_by_request_timeout(self):
        """A stream whose producer never emits must not park the
        client forever: the sweep drops it after request_timeout and
        flags the handle closed."""
        import socket as _socket
        from mmlspark_tpu.serving.frontend import EventLoopFrontend
        handles = []

        class App:
            def handle_request(self, method, path, headers, body,
                               reply):
                handles.append(reply.begin_stream())
                return True          # ... and never emit

        fe = EventLoopFrontend(App(), port=0,
                               request_timeout=0.3).start()
        try:
            s = _socket.create_connection((fe.host, fe.port),
                                          timeout=10)
            s.sendall(b"POST /x HTTP/1.1\r\nHost: x\r\n"
                      b"Content-Length: 0\r\n\r\n")
            head = s.recv(4096)
            assert b" 200 " in head[:20]
            s.settimeout(5)
            assert s.recv(4096) == b""       # dropped by the sweep
            t_end = time.monotonic() + 5
            while not handles[0].closed and time.monotonic() < t_end:
                time.sleep(0.02)
            assert handles[0].closed         # producer was flagged
            assert fe.n_request_timeouts >= 1
        finally:
            fe.stop()

    @pytest.mark.slow
    def test_draft_cache_stays_warm_through_suppressed_rounds(self):
        """Policy-suppressed rounds still advance the draft cache, so
        a probe round proposes from real rows and acceptance recovers
        (the 'never sticky-dead' contract actually holds)."""
        from mmlspark_tpu.serving.policy import SpeculationPolicy
        params, cfg, dec = _spec_setup(n_slots=2, max_len=128)
        # impossible min_rate: exactly one leading spec round, then
        # suppression with a probe every 3rd round
        pol = SpeculationPolicy(min_rate=2.0, warmup_rounds=0,
                                reprobe_every=3)
        sched = DecodeScheduler(dec, spec_policy=pol).start()
        try:
            rng = np.random.default_rng(51)
            pr = [int(t) for t in rng.integers(0, 64, size=4)]
            p = _Pending({"prompt": pr, "max_new_tokens": 40}, "long")
            sched.submit(p)
            assert p.event.wait(60)
            assert json.loads(p.reply)["tokens"] == \
                _spec_greedy_reference(params, cfg, pr, 40)
            st = sched.stats()["speculative"]
            # probes ran beyond the first round, and the tempered
            # self-drafting pair kept accepting on them — stale draft
            # rows would have cratered this to ~0
            assert st["rounds"] >= 2
            assert st["accepted"] / st["proposed"] > 0.8
            assert pol.n_suppressed > 0      # suppression really on
        finally:
            sched.stop()
        assert sched.pool.n_free == 2
        assert _pages_idle(sched)


class TestStateASlot:
    """The cache manager with a decoder whose slots hold a recurrent
    state beside their rows (the Granite-hybrid block kind, ``serving/
    hybrid_decode.py``: two Mamba layers around one grouped-query
    attention layer, 2 of 4 experts held, tiles of 8, pages of 4 rows):
    pages count the attention layer's K/V rows only, the state is the
    slot's and is reset by a request's first tile, on the release path
    every request shares."""

    G_CFG = None

    @classmethod
    def _decoder(cls, **kw):
        from mmlspark_tpu.models import granite_hybrid as GH
        from mmlspark_tpu.serving.decode import decoder_for
        if cls.G_CFG is None:
            cls.G_CFG = GH.GraniteHybridConfig(
                vocab=64, d_model=16, n_heads=4, n_kv_heads=2, d_head=8,
                layer_types=("mamba", "attention", "mamba"), n_experts=4,
                top_k=2, experts_held=(0, 1), d_expert=8, d_shared=16,
                ssm_heads=4, ssm_head_dim=8, ssm_state=8, ssm_chunk=4,
                embed_std=0.01, dtype="float32")
            cls.G_PARAMS = GH.init_params(cls.G_CFG, seed=3)
        kw = dict(dict(n_slots=4, max_len=64, page_size=4, prefill_tile=8,
                       attn_impl="dense"), **kw)
        return decoder_for(cls.G_PARAMS, cls.G_CFG, **kw)

    def test_sixteen_requests_through_four_slots(self):
        """Every request resets the slot it lands in, reads as it does
        alone, and leaves nothing behind; two programs whatever the
        lengths."""
        rng = np.random.default_rng(51)
        prompts = [_prompt(rng, n) for n in rng.integers(3, 40, size=16)]
        alone = self._decoder()
        want = []
        for prompt in prompts:
            toks = [alone.prefill(0, np.asarray(prompt, np.int32))]
            for i in range(5):
                t, at = np.zeros(4, np.int32), np.zeros(4, np.int32)
                t[0], at[0] = toks[-1], len(prompt) + i
                toks.append(int(alone.step(t, at)[0]))
            want.append(toks)
        dec = self._decoder()
        warm = dec.warmup()
        sched = DecodeScheduler(dec).start()
        try:
            pend = [_Pending({"prompt": p, "max_new_tokens": 6}, f"s{i}")
                    for i, p in enumerate(prompts)]
            for p in pend:
                sched.submit(p)
            assert all(p.event.wait(60) for p in pend)
            stats = sched.stats()
        finally:
            sched.stop()
        assert [json.loads(p.reply)["tokens"] for p in pend] == want
        assert warm == 2 and stats["n_compiles"] == 2
        assert stats["n_state_resets"] == 16
        assert stats["slots_high_water"] == 4
        assert sum(stats["expert_routings"]) > 0
        assert len(stats["expert_routings"]) == 2
        assert stats["n_compactions"] == 0
        assert _pages_idle(sched) and sched.pool.n_free == 4

    def test_passes_carry_the_state_and_the_routings(self):
        """``decode.prepare`` stamps the slots whose state a step
        advances, ``decode.prefill`` the tiles walked, and the step's
        ``decode.fetch`` what the held experts received."""
        from mmlspark_tpu.core.tracing import Tracer
        from mmlspark_tpu.serving.decode import pass_view
        tracer = Tracer()
        sched = DecodeScheduler(self._decoder(), tracer=tracer).start()
        rng = np.random.default_rng(52)
        try:
            p = _Pending({"prompt": _prompt(rng, 19),
                          "max_new_tokens": 4}, "stamps")
            sched.submit(p)
            assert p.event.wait(30)
        finally:
            sched.stop()
        views = [pass_view(sp.attrs["phases"])
                 for sp in tracer.recorder.scan("decode.pass")]
        (walk,) = [q for v in views for q in v["prefills"]]
        assert walk["tiles"] == 3 and walk["prompt_tokens"] == 19
        steps = [v for v in views if "dispatch" in v["phases_ms"]]
        assert len(steps) == 3
        for v in steps:
            assert v["state_slots"] == 1 and v["window_rows"] > 19
            # one live slot, top 2 of 4 over three layers, 2 held
            assert len(v["expert_routings"]) == 2
            assert 0 <= sum(v["expert_routings"]) <= 6
            assert v["expert_load_max"] == max(v["expert_routings"])
            assert v["experts_touched"] <= 6

    @pytest.mark.parametrize("reason", ["length", "eos", "cancelled",
                                        "deadline", "error",
                                        "pages_exhausted"])
    def test_pool_back_to_its_start_after_every_release_reason(
            self, reason):
        """The K/V pages come back whatever ends the request, and the
        next request in the slot starts from a zero state."""
        clock = ManualClock()
        rng = np.random.default_rng(53)
        prompt = _prompt(rng, 20)
        kw = {}
        if reason == "pages_exhausted":
            # the 21 rows the prefill leaves and two pages more
            kw["n_pages"] = 1 + 6 + 2
        dec = self._decoder(**kw)
        if reason == "eos":
            probe = DecodeScheduler(dec).start()
            p = _Pending({"prompt": prompt, "max_new_tokens": 16}, "probe")
            probe.submit(p)
            assert p.event.wait(30)
            probe.stop()
            dec.eos_id = json.loads(p.reply)["tokens"][14]
        sched = DecodeScheduler(dec, clock=clock).start()
        inner = dec.step_logits

        def step_logits(tokens, pos, tables=None):
            if sched._active.get(0) is not None and int(pos[0]) == 30:
                if reason == "cancelled":
                    sched.cancel("r")
                elif reason == "deadline":
                    clock.advance(5.0)
                elif reason == "error":
                    raise RuntimeError("scripted step fault")
            return inner(tokens, pos, tables)

        dec.step_logits = step_logits
        try:
            p = _Pending({"prompt": prompt,
                          "max_new_tokens": 16 if reason == "length"
                          else 40}, "r",
                         deadline=Deadline(1.0, clock=clock)
                         if reason == "deadline" else None)
            sched.submit(p)
            assert p.event.wait(30)
            out = json.loads(p.reply)
            assert out["finish_reason"] == reason, out
            # the slot's next request reads as in a fresh decoder
            again = _Pending({"prompt": prompt[:9], "max_new_tokens": 3},
                             "again")
            sched.submit(again)
            assert again.event.wait(30)
            stats = sched.stats()
        finally:
            sched.stop()
        fresh = self._decoder()
        first = fresh.prefill(0, np.asarray(prompt[:9], np.int32))
        assert json.loads(again.reply)["tokens"][0] == first
        assert stats["n_state_resets"] >= 2
        assert _pages_idle(sched) and sched.pool.n_free == 4


@pytest.mark.parametrize("make,prompt_len,n_new,want", [
    # one kind of row, pages of 16: positions 14..17 hold 15..18 rows
    (lambda: _decoder(), 14, 5, [1, 1, 2, 2]),
    # two kinds (window 16, 4 summaries a window, pages of 4): positions
    # 37..40 hold 8 summary rows and 6..9 window rows in ONE lane
    (lambda: TestTwoRowKinds._decoder(), 37, 5, [4, 4, 4, 5]),
    # a state a slot beside the rows (pages of 4): positions 19..21
    (lambda: TestStateASlot._decoder(), 19, 4, [5, 6, 6]),
], ids=["one_kind", "two_kinds", "state_a_slot"])
def test_prepare_counts_the_rows_the_kernel_walks(make, prompt_len, n_new,
                                                  want):
    """``decode.prepare`` stamps the rows a step reads by kind, from
    the positions the scheduler holds: ``cdiv(rows, page_size)`` of a
    live slot's table entries name them, which is what
    ``paged_decode_attention`` walks (``parallel/pallas_attention.
    paged_walk``); the rest of a table is never looked at."""
    from mmlspark_tpu.core.tracing import Tracer
    from mmlspark_tpu.parallel.pallas_attention import paged_walk
    from mmlspark_tpu.serving.decode import pass_view
    tracer = Tracer()
    sched = DecodeScheduler(make(), tracer=tracer).start()
    dec = sched.decoder
    rng = np.random.default_rng(prompt_len)
    try:
        p = _Pending({"prompt": _prompt(rng, prompt_len),
                      "max_new_tokens": n_new}, "entries")
        sched.submit(p)
        assert p.event.wait(30)
    finally:
        sched.stop()
    steps = [v for v in (pass_view(sp.attrs["phases"])
                         for sp in tracer.recorder.scan("decode.pass"))
             if "dispatch" in v["phases_ms"]]
    rows = [v["summary_rows"] + v["window_rows"] for v in steps]
    assert [-(-r // dec.page_size) for r in rows] == want
    # the kernel's own count at the same rows: the last row's index
    assert [int(paged_walk(r - 1, dec.page_size)) for r in rows] == want


# ---------------------------------------------------------------------------
# a step in flight (ISSUE 36): with every slot taken and greedy the loop
# queues step N+1 on step N's tokens as they lie on the device, and only
# then fetches step N. The yardstick is the same requests served in
# today's order (``step_logits``: dispatch and fetch in a row) by the
# same decoder.


class _InTurn(DecodeScheduler):
    """Today's order in every pass."""

    def _may_run_ahead(self, flight):
        return "free_slot"


class _Stream:
    """The slice of the frontend's token stream the scheduler touches."""

    closed = False
    t_first = 0.0

    def __init__(self):
        self.tokens, self.final = [], None

    def emit(self, event: bytes) -> None:
        self.tokens.append(json.loads(event[6:])["token"])

    def finish(self, event: bytes) -> None:
        self.final = json.loads(event[6:])


def _tiny_ouro():
    from mmlspark_tpu.serving.decode import decoder_for
    cfg = T.TransformerConfig.from_hf(
        {"model_type": "ouro", "vocab_size": 64, "hidden_size": 16,
         "num_attention_heads": 2, "num_key_value_heads": 2,
         "head_dim": 8, "intermediate_size": 24, "num_hidden_layers": 2,
         "total_ut_steps": 3, "early_exit_threshold": 1,
         "rope_theta": 1000000, "rms_norm_eps": 1e-6,
         "hidden_act": "silu", "rope_scaling": None,
         "sliding_window": None, "tie_word_embeddings": False},
        dtype="float32")
    return decoder_for(T.init_params(cfg, seed=5), cfg, n_slots=2,
                       max_len=64, page_size=4, attn_impl="dense")


KINDS = {
    "tiny": lambda: _decoder(n_slots=2, max_len=64, page_size=4),
    "tiny-evabyte": lambda: TestTwoRowKinds._decoder(),
    "tiny-granite": lambda: TestStateASlot._decoder(n_slots=2),
    "tiny-ouro": _tiny_ouro,
}
_KIND_DECODERS = {}


def _kind(kind: str):
    """One two-slot decoder a block kind, compiled once for the cases
    below (every case brings its own scheduler, pool and tables)."""
    if kind not in _KIND_DECODERS:
        _KIND_DECODERS[kind] = KINDS[kind]()
    return _KIND_DECODERS[kind]


@pytest.fixture(params=list(KINDS))
def kind_decoder(request):
    return _kind(request.param)


def _serve_all(sched, payloads, streams=(), timeout=60):
    """``payloads`` through ``sched`` from start to stop: the requests
    (``streams`` names those that stream) and the final stats."""
    ps = [_Pending(p, f"f{i}") for i, p in enumerate(payloads)]
    for i in streams:
        ps[i].stream = _Stream()
    sched.start()
    try:
        for p in ps:
            sched.submit(p)
        for p in ps:
            assert p.event.wait(timeout), "stranded"
    finally:
        sched.stop()
    return ps, sched.stats()


def _mix(rng, shapes):
    return [{"prompt": _prompt(rng, n), "max_new_tokens": m}
            for n, m in shapes]


def _nothing_left(sched, stats) -> bool:
    return (stats["slots_free"] == stats["n_slots"] and _pages_idle(sched)
            and sched._flight is None)


class TestStepInFlight:

    def test_ahead_serves_the_tokens_of_todays_order(self, kind_decoder):
        """Two slots, five greedy requests whose ends and admissions
        fall among the steps (and, with a window of 16, whose lanes
        cross it while a step is queued): every request's tokens are
        those of the same requests served in today's order."""
        payloads = _mix(np.random.default_rng(7),
                        ((10, 14), (5, 9), (3, 12), (12, 6), (7, 11)))
        want, in_turn = _serve_all(_InTurn(kind_decoder), payloads)
        sched = DecodeScheduler(kind_decoder)
        got, stats = _serve_all(sched, payloads)
        for a, b in zip(got, want):
            assert a.status == b.status == 200
            assert json.loads(a.reply) == json.loads(b.reply)
        assert in_turn["n_steps_ahead"] == 0
        assert 0 < stats["n_steps_ahead"] < stats["n_steps"]
        assert stats["n_tokens_discarded"] == 0
        assert stats["n_tokens"] == in_turn["n_tokens"] == sum(
            p["max_new_tokens"] for p in payloads)
        assert _nothing_left(sched, stats)

    @pytest.mark.parametrize("third", [9, 16])
    def test_an_eos_with_a_step_in_flight_costs_one_lane(
            self, kind_decoder, third):
        """An ``eos_id`` token is seen at the emit, with the next step
        queued: that step's lane for the request is discarded (not
        streamed, not in the final event, not in ``n_tokens``), slot
        and pages come back, and the slot's next request (a state a
        slot: reset by its first tile) reads as in today's order. That
        request is admitted with the queued step not yet fetched and
        takes no part in it: a prompt of exactly one window (``third``
        16) leaves it at a window's edge with nothing to compact."""
        dec = kind_decoder
        # A and B start together; step j's emit hands A its token j.
        # Windows of 16 fill at steps 6 (A) and 10 (B), whose passes
        # only fetch: the EOS is looked for at steps that run ahead
        # (tiny models repeat themselves: prompts are drawn until one
        # of A's tokens there is nobody else's and not A's before)
        for seed in range(11, 27):
            payloads = _mix(np.random.default_rng(seed),
                            ((10, 16), (6, 30), (third, 7)))
            probe, _ = _serve_all(_InTurn(dec), payloads)
            toks = [json.loads(p.reply)["tokens"] for p in probe]
            k = next((k for k in (8, 9, 12, 13, 14) if toks[0][k] not in
                      toks[0][:k] + toks[1] + toks[2]), None)
            if k is not None:
                break
        assert k is not None
        dec.eos_id = toks[0][k]
        try:
            # (the decoder counts its compactions over every scheduler)
            n0 = dec.n_compactions
            want, in_turn = _serve_all(_InTurn(dec), payloads)
            n1 = in_turn["n_compactions"]
            sched = DecodeScheduler(dec)
            got, stats = _serve_all(sched, payloads, streams=(0, 2))
        finally:
            dec.eos_id = None
        first = json.loads(got[0].reply)
        assert first["finish_reason"] == "eos"
        assert first["tokens"] == toks[0][:k + 1]
        for a, b in zip(got, want):
            assert json.loads(a.reply) == json.loads(b.reply)
        for p in (got[0], got[2]):
            out = json.loads(p.reply)
            assert p.stream.tokens == p.stream.final["tokens"] \
                == out["tokens"]
        assert stats["n_tokens_discarded"] == 1
        assert stats["n_tokens"] == sum(
            json.loads(p.reply)["n_tokens"] for p in got)
        assert stats["n_steps_ahead"] > k - 4
        assert stats["n_compactions"] - n1 == n1 - n0
        assert stats["n_step_faults"] == 0
        assert _nothing_left(sched, stats)

    @pytest.mark.parametrize("how", ["cancelled", "disconnected"])
    def test_a_request_that_leaves_with_a_step_in_flight(self, how):
        """A cancel or a closed stream that lands between a step's
        dispatch and the fetch before it: the request retires at that
        emit with the token it was owed, and the step already queued
        carries one lane more, discarded."""
        from mmlspark_tpu.serving.decode import StepInFlight
        dec = _kind("tiny")
        payloads = _mix(np.random.default_rng(13), ((8, 20), (5, 20)))
        want, _ = _serve_all(_InTurn(dec), payloads)
        sched = DecodeScheduler(dec)
        inner, n_ahead = dec.dispatch_step, [0]

        def dispatch_step(tokens, pos, tables=None):
            step = inner(tokens, pos, tables)
            n_ahead[0] += isinstance(tokens, StepInFlight)
            if n_ahead[0] == 5 and isinstance(tokens, StepInFlight):
                if how == "cancelled":
                    sched.cancel("f0")
                else:
                    sched._by_rid["f0"].stream.closed = True
            return step

        dec.dispatch_step = dispatch_step
        try:
            got, stats = _serve_all(sched, payloads, streams=(0, 1))
        finally:
            del dec.dispatch_step
        gone, stays = (json.loads(p.reply) for p in got)
        assert gone["finish_reason"] == how
        assert got[0].status == (200 if how == "cancelled" else 500)
        # the fifth step dispatched ahead is step 6, behind step 5: the
        # prefill's token and one from each of steps 1..5
        assert gone["tokens"] == json.loads(want[0].reply)["tokens"][:6]
        # (a closed stream takes no event: the token of the emit that
        # found it closed is in the reply alone, as in today's order)
        assert got[0].stream.tokens == gone["tokens"][
            :6 if how == "cancelled" else 5]
        assert stays == json.loads(want[1].reply)
        assert got[1].stream.tokens == stays["tokens"]
        assert stats["n_tokens_discarded"] == 1
        assert stats["n_tokens"] == gone["n_tokens"] + stays["n_tokens"]
        assert _nothing_left(sched, stats)

    @pytest.mark.parametrize("kind", ["tiny", "tiny-granite"])
    def test_a_step_that_fails_at_its_fetch_with_another_queued(
            self, kind):
        """Both steps are lost as the one fault they are: the slots'
        requests get 500s, no slot or page is lost, and the loop
        serves the next request as today's order does."""
        dec = _kind(kind)
        payloads = _mix(np.random.default_rng(17),
                        ((8, 20), (5, 20), (6, 9)))
        want, _ = _serve_all(_InTurn(dec), payloads[2:])
        sched = DecodeScheduler(dec)
        inner, queued = dec.fetch_step, []

        def fetch_step(step):
            if dec.n_dispatched > step.seq:
                queued.append(step.seq)
                if len(queued) == 4:
                    raise RuntimeError("scripted fetch fault")
            return inner(step)

        dec.fetch_step = fetch_step
        try:
            got, stats = _serve_all(sched, payloads)
        finally:
            del dec.fetch_step
        for p in got[:2]:
            out = json.loads(p.reply)
            assert p.status == 500 and out["finish_reason"] == "error"
            assert "scripted fetch fault" in out["error"]
            assert out["n_tokens"] == 4      # the prefill, steps 1..3
        assert got[2].status == 200
        assert json.loads(got[2].reply) == json.loads(want[0].reply)
        assert stats["n_step_faults"] == 1
        assert stats["releases"] == {"error": 2, "length": 1}
        assert _nothing_left(sched, stats)

    @pytest.mark.parametrize("why", ["free_slot", "sampler",
                                     "speculative", "last_token"])
    def test_the_rule_keeps_todays_order(self, why):
        """A free slot, a sampler in a slot, a speculative cohort and
        a slot whose token in flight is its last each give passes in
        today's order: no step is dispatched behind another."""
        from mmlspark_tpu.core.tracing import Tracer
        from mmlspark_tpu.serving.decode import pass_view
        rng = np.random.default_rng(19)
        if why == "speculative":
            dec = _spec_setup(n_slots=2)[2]
        else:
            dec = _kind("tiny")
        payloads = _mix(rng, ((6, 8), (4, 8)))
        if why == "free_slot":
            payloads = payloads[:1]
        elif why == "sampler":
            payloads[1].update(temperature=0.8, seed=3)
        elif why == "last_token":
            # the prefill's token and one step's: that step is left in
            # flight by the pass that dispatched it and fetched alone
            payloads = _mix(rng, ((6, 2), (4, 2)))
        tracer = Tracer()
        sched = DecodeScheduler(dec, tracer=tracer)
        got, stats = _serve_all(sched, payloads)
        assert all(p.status == 200 for p in got)
        assert stats["n_steps_ahead"] == 0
        assert stats["n_tokens_discarded"] == 0
        views = [pass_view(sp.attrs["phases"])
                 for sp in tracer.recorder.scan("decode.pass")]
        assert not any(v.get("ahead") for v in views)
        # every stepping pass is held, and by this rule while the
        # slots are as the case set them (a slot freed by the first
        # request to end reads ``free_slot``)
        held = [v["held_by"] for v in views
                if v.get("order") not in (None, "start")]
        assert held[0] == why and set(held) <= {why, "free_slot"}
        assert stats["held_by"][why] == held.count(why)
        assert sum(stats["held_by"].values()) == len(held)
        if why == "speculative":
            assert stats["speculative"]["rounds"] > 0
            assert {v["order"] for v in views if "order" in v} \
                <= {"spec_round", "in_turn"}
        elif why == "last_token":
            assert stats["n_steps"] == 1
            assert [v["order"] for v in views if "order" in v] \
                == ["start", "fetch_only"]
            assert [sorted(v["phases_ms"]) for v in views
                    if "fetch" in v["phases_ms"]] \
                == [["admit", "emit", "fetch", "prepare"]]
        else:
            assert stats["n_steps"] == 7
            assert all(v["fetched"] == v["seq"] for v in views
                       if "dispatch" in v["phases_ms"])
        assert _nothing_left(sched, stats)

    def test_every_pass_says_its_order(self, kind_decoder):
        """Over a run whose ends and admissions fall among the steps:
        every stepping pass carries an ``order`` of the vocabulary,
        ``held_by`` exactly where the rule held it, ``order`` and the
        ``ahead`` on its dispatch agree, the counters in
        ``/decode/stats`` are the passes' sums, and a pass that ran
        ahead left the device nothing to wait for."""
        from collections import Counter
        from mmlspark_tpu.core.tracing import Tracer
        from mmlspark_tpu.serving.decode import (
            HELD_BY, PASS_ORDERS, STARVED_PHASES, pass_view)
        payloads = _mix(np.random.default_rng(7),
                        ((10, 14), (5, 9), (3, 12), (12, 6), (7, 11)))
        tracer = Tracer()
        sched = DecodeScheduler(kind_decoder, tracer=tracer)
        got, stats = _serve_all(sched, payloads)
        assert all(p.status == 200 for p in got)
        spans = tracer.recorder.scan("decode.pass")
        views = [pass_view(sp.attrs["phases"]) for sp in spans]
        stepping = [v for v in views if "prepare" in v["phases_ms"]]
        assert stepping and {v["order"] for v in stepping} \
            <= set(PASS_ORDERS) - {"spec_round"}
        assert not any("order" in v for v in views if v not in stepping)
        for v in stepping:
            assert ("held_by" in v) == (v["order"] in ("in_turn",
                                                       "fetch_only"))
            assert v.get("held_by", "free_slot") in HELD_BY
            if "dispatch" in v["phases_ms"]:
                assert v["ahead"] == (v["order"] == "ahead")
                assert v["order"] != "fetch_only"
            else:
                assert v["order"] in ("fetch_only", "in_turn")
        orders = Counter(v["order"] for v in stepping)
        assert orders["ahead"] == stats["n_steps_ahead"] > 0
        assert orders["start"] > 0 and orders["fetch_only"] > 0
        held = Counter(v["held_by"] for v in stepping if "held_by" in v)
        assert {k: n for k, n in stats["held_by"].items() if n} == held
        assert set(stats["held_by"]) == set(HELD_BY)
        assert held["last_token"] > 0
        if kind_decoder.window:
            assert held["window_fill"] > 0
        # the starved account: nothing in a pass that ran ahead, the
        # whole host's turn in one in today's order, and the stats'
        # sums are the passes'
        total = dict.fromkeys(STARVED_PHASES, 0.0)
        for sp, v in zip(spans, views):
            starved = sp.attrs["starved_ms"]
            assert set(starved) <= set(STARVED_PHASES)
            for k, ms in starved.items():
                assert 0 <= ms <= v["phases_ms"][k] + 1e-9
                total[k] += ms
            if v.get("order") == "ahead":
                assert starved == {}
            elif v.get("order") == "in_turn" and "emit" in v["phases_ms"]:
                assert set(starved) == set(STARVED_PHASES)
                assert starved["dispatch"] == pytest.approx(
                    v["phases_ms"]["dispatch"])
            assert sp.attrs["cpu_ms"] >= 0 and sp.attrs["proc_cpu_ms"] >= 0
        assert stats["loop"]["starved"] == pytest.approx(
            {k: ms * 1e-3 for k, ms in total.items()}, abs=1e-5)
        assert sum(total.values()) > 0
        assert _nothing_left(sched, stats)

    @pytest.mark.parametrize("why", ["cancelled", "stream_closed",
                                     "deadline", "riders_changed",
                                     "window_fill"])
    def test_a_held_pass_names_the_rule(self, why):
        """With a step in flight, a cancel, a closed stream or a
        deadline the host has seen, a request that left at the last
        emit, and a step that fills a window, each hold the next pass
        to a fetch alone under their word."""
        from mmlspark_tpu.core.tracing import Tracer
        from mmlspark_tpu.serving.decode import pass_view
        dec = _kind("tiny-evabyte" if why == "window_fill" else "tiny")
        clock = ManualClock()
        # (a request that left is missed by the rule only once its
        # slot is taken again: a third request waits for it)
        payloads = _mix(np.random.default_rng(29),
                        ((8, 20), (5, 20), (6, 3))[
                            :3 if why == "riders_changed" else 2])
        tracer = Tracer()
        sched = DecodeScheduler(dec, tracer=tracer, clock=clock)
        inner, n_tokens = sched._retire_if_done, [0]

        def retire_if_done(req, tok):
            # f0's fifth token, of a step fetched with another queued:
            # the cancel lands before the emit looks (the request
            # leaves there: ``riders_changed``) or just after it
            n_tokens[0] += req.pending.rid == "f0"
            mine = req.pending.rid == "f0" and n_tokens[0] == 4
            if mine and why == "riders_changed":
                sched.cancel("f0")
            done = inner(req, tok)
            if mine and why == "cancelled":
                sched.cancel("f0")
            elif mine and why == "stream_closed":
                req.stream.closed = True
            elif mine and why == "deadline":
                clock.advance(5.0)
            return done

        sched._retire_if_done = retire_if_done
        ps = [_Pending(p, f"f{i}") for i, p in enumerate(payloads)]
        ps[0].stream = _Stream()
        if why == "deadline":
            ps[0].deadline = Deadline(1.0, clock=clock)
        sched.start()
        try:
            for p in ps:
                sched.submit(p)
            for p in ps:
                assert p.event.wait(60), "stranded"
        finally:
            sched.stop()
        stats = sched.stats()
        views = [pass_view(sp.attrs["phases"])
                 for sp in tracer.recorder.scan("decode.pass")]
        held = [v for v in views if v.get("held_by") == why]
        assert held and stats["held_by"][why] == len(held)
        assert all(v["order"] == "fetch_only" for v in held)
        if why == "window_fill":
            # both lanes cross position 16, three steps apart: one
            # pass each, and the lanes run on behind their compactions
            assert len(held) == 2 and stats["n_compactions"] >= 2
            assert stats["n_tokens_discarded"] == 0
        else:
            # a step was queued as the host saw it: the pass after only
            # fetches, and the request leaves at that emit (or left at
            # the one before, its lane in the queued step discarded)
            assert len(held) == 1
            assert stats["n_tokens_discarded"] == (why == "riders_changed")
            assert json.loads(ps[0].reply)["finish_reason"] == {
                "stream_closed": "disconnected", "deadline": "deadline"
                }.get(why, "cancelled")
        assert _nothing_left(sched, stats)

    def test_a_budget_of_m_tokens_runs_m_less_two_steps_ahead(self):
        """Two slots, two requests of five tokens: the prefill's, then
        four steps, of which the first is left in flight, the next
        three queue behind one, and the last is fetched alone."""
        dec = _kind("tiny")
        sched = DecodeScheduler(dec)
        _, stats = _serve_all(sched, _mix(np.random.default_rng(23),
                                          ((6, 5), (4, 5))))
        assert (stats["n_steps"], stats["n_steps_ahead"]) == (4, 3)
        assert stats["loop"]["dispatch"]["n"] == 4
        assert stats["loop"]["fetch"]["n"] == 4
