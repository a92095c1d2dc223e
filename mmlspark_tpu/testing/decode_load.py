"""Decode-serving load harness: continuous vs static whole-batch A/B.

The simulation ``bench.py decode_continuous_v1`` times. Both modes
drive the SAME
:class:`~mmlspark_tpu.serving.decode.TransformerDecoder` (same jitted
prefill/step, same KV pool) over the same seeded workload of requests
arriving at staggered wall-clock offsets; only the batching discipline
differs:

* **continuous** — the scheduler discipline: arrived requests claim
  free slots between steps, finished requests release them mid-batch,
  the fixed-shape step runs whenever any slot is live;
* **static** — the whole-batch baseline: collect the arrived requests
  into one batch, decode the ENTIRE batch until its longest member
  finishes (early finishers pad the batch, the classic cost), only
  then admit the next group — requests arriving mid-batch wait.

Evidence collected alongside tokens/s: post-warmup compile-count delta
(must be zero), KV-pool buffer-pointer stability across steps (the
donation proof — cache-out reuses cache-in's buffer IN PLACE), and
device live-array count stability over the steady state (zero
allocation growth).
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import numpy as np


@dataclass
class DecodeJob:
    arrival_s: float          # offset from window start
    prompt: np.ndarray
    max_new: int
    # filled by the runs
    t_done: float = 0.0
    n_tokens: int = 0


def make_workload(vocab: int, n_requests: int, seed: int = 0,
                  mean_gap_ms: float = 30.0,
                  prompt_lens=(3, 5, 8, 12),
                  max_new=(8, 16, 24),
                  prefix_share: float = 0.0,
                  prefix_len: int = 16,
                  prefix_pool: int = 2) -> List[DecodeJob]:
    """Seeded mixed-arrival workload: exponential inter-arrival gaps
    (the memoryless traffic shape), cycled prompt lengths and token
    budgets — so requests genuinely join and leave mid-flight.

    ``prefix_share`` shapes the multi-tenant prompt-overlap regime the
    prefix cache targets (shared system preambles / few-shot
    templates): that fraction of requests draws its first
    ``prefix_len`` tokens from a small pool of ``prefix_pool`` shared
    prefixes (then a unique ``prompt_lens``-cycled suffix); the rest
    get a unique random prefix of the SAME length, so both arms of a
    cache A/B see identical prompt-length distributions and only the
    overlap differs (``bench.py decode_prefix_cache_v1``)."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(mean_gap_ms / 1000.0, size=n_requests)
    arrivals = np.cumsum(gaps)
    # draw the shared pool ONLY when the knob is on: prefix_share=0
    # callers (every pre-existing seeded workload) must keep their
    # exact historical prompt streams at the same seed
    shared = ([rng.integers(0, vocab, size=int(prefix_len))
               .astype(np.int32) for _ in range(max(prefix_pool, 1))]
              if prefix_share > 0.0 else [])
    jobs = []
    for i in range(n_requests):
        plen = prompt_lens[i % len(prompt_lens)]
        if prefix_share > 0.0:
            head = (shared[i % len(shared)]
                    if rng.random() < prefix_share
                    else rng.integers(0, vocab, size=int(prefix_len))
                    .astype(np.int32))
            prompt = np.concatenate(
                [head, rng.integers(0, vocab, size=plen)
                 .astype(np.int32)])
        else:
            prompt = rng.integers(0, vocab,
                                  size=plen).astype(np.int32)
        jobs.append(DecodeJob(
            arrival_s=float(arrivals[i]),
            prompt=prompt,
            max_new=int(max_new[i % len(max_new)])))
    return jobs


def cache_buffer_pointers(cache) -> List[int]:
    """The device buffer address of every leaf of a KV pool (the paged
    pool is one array a layer): a donated call that writes in place
    leaves every one where it was."""
    import jax
    return [leaf.unsafe_buffer_pointer()
            for leaf in jax.tree.leaves(cache)]


def _reset_jobs(jobs: List[DecodeJob]) -> None:
    for j in jobs:
        j.t_done = 0.0
        j.n_tokens = 0


def run_continuous(decoder, jobs: List[DecodeJob]) -> Dict[str, Any]:
    """The slot-level discipline, inline (no HTTP, no threads — the
    engine's own ceiling). Returns tokens/s plus the zero-alloc /
    zero-retrace evidence."""
    import jax
    _reset_jobs(jobs)
    compiles_before = decoder.n_compiles()
    n_slots = decoder.n_slots
    tokens = np.zeros(n_slots, np.int32)
    pos = np.zeros(n_slots, np.int32)
    free = list(range(n_slots))
    active: Dict[int, DecodeJob] = {}
    queue = sorted(jobs, key=lambda j: j.arrival_s)
    total_tokens = 0
    ptr0 = cache_buffer_pointers(decoder.cache)
    live_counts: List[int] = []
    t0 = time.perf_counter()
    while queue or active:
        now = time.perf_counter() - t0
        while queue and free and queue[0].arrival_s <= now:
            job = queue.pop(0)
            slot = free.pop()
            first = decoder.prefill(slot, job.prompt)
            job.n_tokens = 1
            total_tokens += 1
            tokens[slot] = first
            pos[slot] = len(job.prompt)
            active[slot] = job
            if job.n_tokens >= job.max_new:       # 1-token budgets
                job.t_done = time.perf_counter() - t0
                del active[slot]
                free.append(slot)
        if not active:
            if queue:
                time.sleep(max(min(queue[0].arrival_s - now, 0.002),
                               0.0))
            continue
        out = decoder.step(tokens, pos)
        live_counts.append(len(jax.live_arrays()))
        for slot, job in list(active.items()):
            tok = int(out[slot])
            job.n_tokens += 1
            total_tokens += 1
            pos[slot] += 1
            tokens[slot] = tok
            if job.n_tokens >= job.max_new or \
                    int(pos[slot]) >= decoder.max_len - 1:
                job.t_done = time.perf_counter() - t0
                tokens[slot] = 0
                pos[slot] = 0
                del active[slot]
                free.append(slot)
    makespan = time.perf_counter() - t0
    half = len(live_counts) // 2
    return {
        "mode": "continuous",
        "tokens": total_tokens,
        "makespan_s": round(makespan, 4),
        "tokens_per_s": round(total_tokens / makespan, 1),
        "mean_done_s": round(float(np.mean([j.t_done for j in jobs])),
                             4),
        "post_warmup_recompiles":
            decoder.n_compiles() - compiles_before,
        # the donation proof: the pool's device buffer never moved
        "cache_buffer_stable":
            cache_buffer_pointers(decoder.cache) == ptr0,
        # steady-state device allocation growth (second half vs first
        # sample): 0 = the warm loop allocates nothing that lives
        "live_array_growth":
            (max(live_counts[half:]) - live_counts[0])
            if half > 0 else 0,
    }


def run_static(decoder, jobs: List[DecodeJob]) -> Dict[str, Any]:
    """The whole-batch baseline: group the arrived requests, decode
    the whole group to its LONGEST member's budget, admit the next
    group only when the batch fully drains."""
    _reset_jobs(jobs)
    compiles_before = decoder.n_compiles()
    n_slots = decoder.n_slots
    tokens = np.zeros(n_slots, np.int32)
    pos = np.zeros(n_slots, np.int32)
    queue = sorted(jobs, key=lambda j: j.arrival_s)
    total_tokens = 0
    t0 = time.perf_counter()
    while queue:
        now = time.perf_counter() - t0
        if queue[0].arrival_s > now:
            time.sleep(min(queue[0].arrival_s - now, 0.002))
            continue
        batch: List[DecodeJob] = []
        while queue and len(batch) < n_slots and \
                queue[0].arrival_s <= time.perf_counter() - t0:
            batch.append(queue.pop(0))
        for slot, job in enumerate(batch):
            first = decoder.prefill(slot, job.prompt)
            job.n_tokens = 1
            total_tokens += 1
            tokens[slot] = first
            pos[slot] = len(job.prompt)
        # the whole batch runs to its longest member; early finishers
        # ride along as padding (their extra tokens are discarded)
        remaining = {slot: job for slot, job in enumerate(batch)
                     if job.n_tokens < job.max_new}
        for job in batch:
            if job.n_tokens >= job.max_new:
                job.t_done = time.perf_counter() - t0
        while remaining:
            out = decoder.step(tokens, pos)
            for slot, job in list(remaining.items()):
                job.n_tokens += 1
                total_tokens += 1
                pos[slot] += 1
                tokens[slot] = int(out[slot])
                if job.n_tokens >= job.max_new or \
                        int(pos[slot]) >= decoder.max_len - 1:
                    job.t_done = time.perf_counter() - t0
                    del remaining[slot]
        tokens[:] = 0
        pos[:] = 0
    makespan = time.perf_counter() - t0
    return {
        "mode": "static",
        "tokens": total_tokens,
        "makespan_s": round(makespan, 4),
        "tokens_per_s": round(total_tokens / makespan, 1),
        "mean_done_s": round(float(np.mean([j.t_done for j in jobs])),
                             4),
        "post_warmup_recompiles":
            decoder.n_compiles() - compiles_before,
    }


# ---------------------------------------------------------------------------
# scheduler-level session harness (paged + speculative A/B)
# ---------------------------------------------------------------------------


class _BenchPending:
    """The _PendingRequest slice a standalone DecodeScheduler touches
    (the same shim the direct-scheduler tests use)."""

    def __init__(self, payload, rid):
        self.payload = payload
        self.rid = rid
        self.deadline = None
        self.event = threading.Event()
        self.callbacks: list = []
        self.reply = None
        self.status = 200
        self.span = None
        self.trace = rid
        self.stream = None


def make_spec_model_pair(cfg, draft_layers: int = 1,
                         resid_scale: float = 0.05, seed: int = 0):
    """A (target params, draft params, draft cfg) triple whose
    truncated-layer draft AGREES with the target at trained-pair rates.

    Randomly initialized blocks drown the embedding stream in residual
    noise, so an early exit's argmax is uncorrelated with the full
    model's — unlike a real trained pair, where the draft exists
    because it agrees. Scaling each block's output projections by
    ``resid_scale`` restores the trained regime (the residual refines
    rather than replaces the stream), giving the ~0.8 greedy agreement
    a production draft is chosen for — so the bench measures the
    speculative MACHINERY at a realistic acceptance rate, which it
    reports and gates on rather than assumes."""
    from mmlspark_tpu.models import transformer as T
    params = T.init_params(cfg, seed=seed)
    params["blocks"] = [dict(b) for b in params["blocks"]]
    for b in params["blocks"]:
        b["wo"] = b["wo"] * resid_scale
        b["w2"] = b["w2"] * resid_scale
    draft_params, draft_cfg = T.layer_truncated_draft(
        params, cfg, draft_layers)
    return params, draft_params, draft_cfg


def run_scheduler_sessions(scheduler, jobs: List[DecodeJob],
                           timeout_s: float = 300.0,
                           payload_extra: Optional[Dict[str, Any]]
                           = None,
                           rid_prefix: str = "bench"
                           ) -> Dict[str, Any]:
    """Drive a live :class:`DecodeScheduler` with the whole workload
    (backlogged submission — every request queued up front, so
    concurrency is bounded by slots/pages, not arrival gaps) and
    collect the sessions-at-fixed-HBM evidence: peak concurrent
    sessions, tokens/s, prefill tokens/s (the prefix-cache A/B
    metric), per-request token sequences (the cross-layout parity
    probe), compile-count delta, and the donation pointer.
    ``payload_extra`` merges into every request's payload (sampling
    knobs for the seeded-parity probes)."""
    import json
    compiles_before = scheduler.decoder.n_compiles()
    prefill_s0 = scheduler.prefill_s
    prompt_tokens0 = scheduler.n_prompt_tokens
    prefills0 = scheduler.n_prefills
    ptr0 = cache_buffer_pointers(scheduler.decoder.cache)
    pendings = [_BenchPending(
        dict({"prompt": [int(t) for t in j.prompt],
              "max_new_tokens": int(j.max_new)},
             **(payload_extra or {})), f"{rid_prefix}-{i}")
        for i, j in enumerate(jobs)]
    t0 = time.perf_counter()
    for p in pendings:
        scheduler.submit(p)
    errors = 0
    sequences: List[List[int]] = []
    for p in pendings:
        if not p.event.wait(timeout_s):
            raise RuntimeError("bench request stranded")
        if p.status != 200:
            errors += 1
            sequences.append([])
        else:
            sequences.append(json.loads(p.reply)["tokens"])
    makespan = time.perf_counter() - t0
    total = sum(len(s) for s in sequences)
    out = {
        "n_requests": len(jobs),
        "tokens": total,
        "makespan_s": round(makespan, 4),
        "tokens_per_s": round(total / makespan, 1),
        "errors": errors,
        "sequences": sequences,
        "peak_concurrent_sessions": scheduler.slots_high_water,
        "post_warmup_recompiles":
            scheduler.decoder.n_compiles() - compiles_before,
        "cache_buffer_stable":
            cache_buffer_pointers(scheduler.decoder.cache) == ptr0,
        "slots_all_freed":
            scheduler.pool.n_free == scheduler.decoder.n_slots,
    }
    d_wall = scheduler.prefill_s - prefill_s0
    d_tokens = scheduler.n_prompt_tokens - prompt_tokens0
    out["prefill_tokens_per_s"] = (round(d_tokens / d_wall, 1)
                                   if d_wall > 0 else None)
    out["mean_prefill_ms"] = round(1000.0 * d_wall / max(
        scheduler.n_prefills - prefills0, 1), 3)
    if scheduler.pages is not None:
        # the refcounted idle invariant: free + index-cached covers
        # the claimable pool, every cached page held exactly once
        cached = (scheduler.prefix.n_cached
                  if scheduler.prefix is not None else 0)
        out["pages_all_freed"] = (
            scheduler.pages.n_free + cached
            == scheduler.pages.n_pages - 1
            and (scheduler.prefix is None
                 or scheduler.prefix.ledger_clean()))
        out["page_high_water"] = scheduler.pages.high_water
    if scheduler.prefix is not None:
        out["prefix_cache"] = scheduler.prefix.stats()
    spec = scheduler.stats().get("speculative")
    if spec is not None:
        out["acceptance_rate"] = spec["acceptance_rate"]
        out["spec_rounds"] = spec["rounds"]
    return out
