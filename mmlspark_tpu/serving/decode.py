"""Continuous batching for autoregressive decode.

The frame-serving plane (server.py) dispatches whole shape-bucketed
batches: right for stateless models, wrong for autoregressive decode,
where requests have private growing state (a KV cache) and finish at
different times — batching whole requests would hold every member
until the slowest one's last token. This module batches at the *slot*
level instead:

* a :class:`TransformerDecoder` owns ONE preallocated KV-cache pool
  (``models/transformer.init_paged_kv_cache``) plus the jitted
  prefill/step functions built over it — fixed shapes, donated cache,
  so a warm decode loop performs **zero device allocations and zero
  retraces** however requests churn;
* a :class:`DecodeScheduler` runs the step loop: between any two
  decode steps, waiting requests claim free slots (one bucketed
  prefill each), finished requests (EOS / token budget / cache-lane
  end / deadline / cancel) release theirs, and the single-token step
  always runs over the full fixed ``[n_slots]`` batch. The loop never
  stops or retraces while traffic flows — joiners splice in between
  steps, leavers just return an index.

Requests ride the server's existing admission machinery
(:class:`~mmlspark_tpu.serving.server.ServingServer` routes its
``decode_path`` here): replay/join/shed/deadline semantics, the reply
journal, root spans, and the trace id all behave exactly as on the
frame plane. Tokens are emitted incrementally into the request's
in-flight state (visible via ``GET /decode/stats``); the reply carries
the full sequence once the request leaves its slot.

The decode plane's memory is **paged** (docs/serving.md
"Paged KV cache"): the KV pool is a shared set of fixed-size pages
plus per-slot page tables, so cache HBM is spent on rows sequences
actually occupy — a :class:`PagePool` claims/frees pages between
steps with the same no-leak ledger as slots, admission sheds 429 on
page exhaustion, and a pool that runs dry mid-decode preempts (partial
tokens, ``pages_exhausted``) instead of OOMing. The page pool is
**content-addressable across requests** (docs/serving.md "Prefix
cache"): a :class:`PrefixCache` radix index keyed by
``page_size``-token prompt chunks maps a new prompt to its longest
cached prefix, whose pages attach to the new slot's table by
REFERENCE (``PagePool`` refcounts — a shared page frees only when its
last reader leaves), a finishing request's prompt-complete pages are
published into the index instead of freed (LRU-bounded; eviction
reclaims unreferenced pages under claim pressure), and the prefill
computes only the uncached suffix — exact, token-for-token the cold
path. With a draft model
configured, the scheduler runs **speculative rounds** (fused k-token
draft propose + one width-k target verify; exact greedy prefix
acceptance, rejection sampling for sampled opt-ins, acceptance-gated
by :class:`~mmlspark_tpu.serving.policy.SpeculationPolicy`). Requests
that ask for ``stream=1`` get their tokens **incrementally** as
chunked SSE events through either frontend's stream handle
(``pending.stream``); disconnects flip the handle's ``closed`` flag
and resolve through the same ``_finish`` as every other exit.

Observability: slot occupancy, decode steps, per-token counters,
prefill/step latency histograms, page-pool occupancy, speculative
acceptance, and queue-wait all land in the server's registry
(``docs/observability.md`` "Decode metrics"); every request's trace
shows ``queue_wait``/``prefill``/``decode`` children under its root.
The loop records itself too: every pass is a chain of ``decode.*``
phases (``core/profiling.span``: host events of any profiler trace,
on its clock) recorded once as a ``decode.pass`` span, counted under
``loop`` in ``GET /decode/stats``, and retained under route
``decode.loop`` when it stalls (``docs/observability.md`` "The decode
loop").
Chaos: a ``fault_plan`` drives the ``decode_prefill`` and
``decode_step`` sites — an injected step/verify fault 500s the
affected requests but **never strands a slot or a page**
(tests/test_serving_decode.py).
"""

from __future__ import annotations

import json
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from mmlspark_tpu.core.logs import get_logger
from mmlspark_tpu.core.profiling import collect, span
from mmlspark_tpu.core.resilience import SYSTEM_CLOCK, Clock
from mmlspark_tpu.parallel.sharding import bucket_ladder, bucket_target
from mmlspark_tpu.serving.tenancy import (
    ANONYMOUS_ID, FairCycle, ReleaseRateEwma,
)

logger = get_logger("serving.decode")


class StepInFlight:
    """A step that was dispatched and is not fetched yet: what a
    decoder's ``dispatch_step`` hands back and its ``fetch_step``, or
    the next ``dispatch_step`` in place of host tokens, takes.
    ``tokens`` is the step's greedy token a slot as it lies on the
    device, ``fetched`` the one array the host copies back (the same
    array where the program packs nothing beside its tokens),
    ``logits`` the device's, ``pos`` the host's positions the step ran
    at and ``seq`` its number among the steps its decoder dispatched."""

    __slots__ = ("seq", "tokens", "fetched", "logits", "pos")

    def __init__(self, seq: int, tokens, fetched, logits,
                 pos: np.ndarray):
        self.seq = seq
        self.tokens = tokens
        self.fetched = fetched
        self.logits = logits
        self.pos = pos


class DecodeOverloaded(RuntimeError):
    """The waiting queue is full: new decode work must shed (429)."""


class TransformerDecoder:
    """The model side of continuous batching: one KV pool + the jitted
    prefill/step machinery over it, with host-side bookkeeping.

    Not thread-safe by design — exactly one :class:`DecodeScheduler`
    loop thread drives it (the cache is DONATED through every call;
    two concurrent calls would race one buffer). ``eos_id`` is the
    stop token (None = never stops early; requests end on their token
    budget). ``warmup()`` compiles the step and every prompt bucket;
    after it, :meth:`n_compiles` staying flat is the zero-retrace
    evidence the bench gates on.

    The pool is a block-table layout — ``n_pages`` shared pages of ``page_size``
    rows plus per-slot page tables — so cache HBM is spent on rows
    sequences actually occupy instead of ``max_len`` per slot (page 0
    is the scratch page; see ``models/transformer.py``). ``n_pages``
    defaults to the dense equivalent (every slot can hold a full
    lane); set it lower to serve more slots at the same HBM — the
    scheduler's :class:`PagePool` admission keeps the pool honest.
    The pool's leaves are in ``cfg.dtype`` (float32 or bfloat16), one
    ``[n_loops * n_pages, page_size, H, Dh]`` array a layer for K and
    one for V: a looped stack (``cfg.recipe.n_loops`` passes over the
    same layers) keeps a row a (pass, layer, position), so its pool is
    ``n_loops`` times a single pass's while a page id still names ONE
    position's rows (in every pass and layer) and ``n_pages``,
    :meth:`rows_at`, :meth:`pages_for` and the scheduler's
    :class:`PagePool` count a position once
    (:attr:`kv_bytes_per_position` says what it costs). What differs
    between softmax configurations is what the program builders read
    from ``cfg`` (the recipe, the dtype), never the decoder class.
    ``prompt_buckets`` names the prefill ladder (ascending; each one
    page or less, or whole pages) where the traffic's prompts do not
    want the powers of two up to ``max_len``.
    Callers without a scheduler (direct API,
    ``testing/decode_load``) may omit page tables: an identity table
    (slot ``s`` -> pages ``[1 + s*pps, 1 + (s+1)*pps)``) stands in,
    which needs the full-size default pool.

    **Speculative decoding** (``draft_params``/``draft_cfg``): a small
    draft model (same vocab — e.g.
    :func:`~mmlspark_tpu.models.transformer.layer_truncated_draft`)
    proposes ``spec_k`` greedy tokens per slot in ONE fused device
    program, and a width-``spec_k`` verify step of the target scores
    them all at once; the scheduler accepts the longest agreeing
    prefix. The draft keeps a dense slot-lane cache (its layers are
    the cheap fraction — paging the target is where the HBM lives).
    Requires no mesh (the draft is replicated)."""

    def __init__(self, params, cfg, n_slots: int = 8,
                 max_len: int = 256, eos_id: Optional[int] = None,
                 donate: bool = True, mesh=None, page_size: int = 16,
                 n_pages: Optional[int] = None,
                 draft_params=None, draft_cfg=None, spec_k: int = 4,
                 attn_impl: str = "auto",
                 verify_ce_impl: Optional[str] = None,
                 prefix_cache: bool = True,
                 quantized_ffn: bool = False,
                 prompt_buckets: Optional[List[int]] = None):
        from mmlspark_tpu.models import transformer as T
        self.cfg = cfg
        #: passes a token makes over the layers (1: an unlooped stack)
        self.n_loops = int(cfg.recipe.n_loops)
        if cfg.recipe.looped and draft_params is not None:
            raise ValueError(
                "a looped stack has no speculation: its draft would be "
                "a model of fewer passes, which is not built (ROADMAP "
                "B12)")
        self.n_slots = int(n_slots)
        self.max_len = int(max_len)
        self.eos_id = eos_id
        self.mesh = mesh
        self.quantized_ffn = bool(quantized_ffn)
        if self.quantized_ffn:
            # int8-compute FFN (ISSUE 17 tentpole a): per-channel
            # scales derived ONCE here — construction is rollout stage
            # time, so the quantized tree warms/compiles pre-flip and
            # serving never requantizes. Attention/rope/softmax/the
            # residual stream stay f32 (quantize_decode_ffn docs);
            # row-wise parity vs the f32 tree is the rollout verify's
            # job, not an assumption.
            params = T.quantize_decode_ffn(params, cfg)
        cache_sharding = None
        if mesh is not None:
            # tensor-parallel decode: ONE model + ONE KV pool span the
            # mesh — heads/MLP-hidden shard over the model axis
            # (decode_param_specs), each device's cache holds exactly
            # its heads' lanes (decode_cache_spec — the head dim of
            # every leaf: the pool is one array a layer). The jitted
            # machinery below compiles the SAME programs as sharded
            # computations; shapes, donation, and compile-once are
            # unchanged.
            import jax
            from jax.sharding import NamedSharding, PartitionSpec
            is_spec = lambda x: isinstance(x, PartitionSpec)  # noqa: E731
            p_sh = jax.tree.map(
                lambda s: NamedSharding(mesh, s),
                T.decode_param_specs(cfg, mesh,
                                     quantized_ffn=self.quantized_ffn),
                is_leaf=is_spec)
            params = jax.device_put(params, p_sh)
            cache_sharding = NamedSharding(
                mesh, T.decode_cache_spec(mesh))
        self.params = params
        page_size = int(page_size)
        if page_size < 1 or page_size & (page_size - 1):
            # prompt buckets are powers of two: a pow2 page divides
            # every bucket >= itself (whole-chunk scatters) and
            # bounds the rest to the partial-page path — any other
            # size leaves buckets the prefill cannot chunk
            raise ValueError(
                f"page_size={page_size} must be a power of two")
        if self.max_len % page_size:
            raise ValueError(
                f"page_size={page_size} must divide "
                f"max_len={self.max_len}")
        self.page_size = page_size
        self.pages_per_slot = self.max_len // self.page_size
        self._ladder = (bucket_ladder(self.max_len)
                        if prompt_buckets is None
                        else sorted(int(b) for b in prompt_buckets))
        if any(b > page_size and b % page_size for b in self._ladder) \
                or not 0 < self._ladder[0] <= self._ladder[-1] \
                <= self.max_len:
            raise ValueError(
                f"prompt_buckets={self._ladder}: each one page "
                f"({page_size}) or less or whole pages, none past "
                f"max_len={self.max_len}")
        #: the longest prompt a prefill takes: the ladder's top bucket,
        #: with a row left to generate into (the scheduler refuses a
        #: longer one as a 400 and states this in ``/decode/stats``)
        self.max_prompt = min(self._ladder[-1], self.max_len - 1)
        # default pool = the dense equivalent + the scratch page:
        # identical HBM and admission behavior until the operator
        # shrinks it (or raises n_slots at the same pool)
        self.n_pages = (int(n_pages) if n_pages is not None
                        else 1 + self.n_slots * self.pages_per_slot)
        if self.n_pages < 2:
            raise ValueError("paged cache needs n_pages >= 2 "
                             "(page 0 is the scratch page)")
        # the decode-step gather engine (ROADMAP item 5 / PR 11
        # follow-up): "auto" runs the fused Pallas block-table
        # kernel on TPU (the page table aims each page DMA via
        # scalar prefetch — no per-layer lane materialization in
        # HBM) and the dense gather everywhere else; "dense" /
        # "pallas" / "pallas_interpret" force an engine
        # (interpret = the CPU parity-test mode), and the builders
        # refuse any other name. Under a TP mesh the kernel runs per
        # head slice (transformer._attn_kernel; token-for-token parity
        # vs the dense gather is test-pinned for the mesh path too).
        if attn_impl == "auto":
            from mmlspark_tpu.parallel.pallas_attention import (
                paged_attention_available)
            attn_impl = ("pallas" if paged_attention_available()
                         else "dense")
        self.attn_impl = attn_impl
        # the SAME resolved engine drives the prefill builders
        # (ISSUE 17): "pallas" runs the streaming flash kernels —
        # no [S, S] score matrix in the cold prefills, no [S, V]
        # lane materialization in the offset/prefix prefill —
        # "dense" keeps the softmax paths, interpret is CPU parity
        self._prefill = T.build_paged_prefill(
            cfg, self.page_size, self.pages_per_slot,
            donate=donate, cache_sharding=cache_sharding,
            attn_impl=attn_impl)
        self._step = T.build_paged_decode_step(
            cfg, self.n_slots, self.page_size, self.pages_per_slot,
            donate=donate, cache_sharding=cache_sharding,
            attn_impl=attn_impl)
        # the cross-request prefix cache's compute half: a
        # partial/offset prefill that computes KV only for the
        # uncached suffix [hit_len, S) while attending over the
        # shared prefix pages (the scheduler's PrefixCache is the
        # index half; prefix_cache=False skips building/warming it
        # — the A/B baseline)
        self._prefix_prefill = (
            T.build_paged_prefix_prefill(
                cfg, self.page_size, self.pages_per_slot,
                donate=donate, cache_sharding=cache_sharding,
                attn_impl=attn_impl)
            if prefix_cache else None)
        self.cache = T.init_paged_kv_cache(cfg, self.n_pages,
                                           self.page_size)
        #: bytes one position's K and V rows take in the pool: every
        #: layer's and, in a looped stack, every pass's
        self.kv_bytes_per_position = self.n_loops * sum(
            x.dtype.itemsize * int(np.prod(x.shape[2:]))
            for x in self.cache["k"] + self.cache["v"])
        self._split_fetched = T.split_fetched
        #: which output of ``_step`` behind the cache is the tokens
        #: alone (a looped stack's first is its packed fetch)
        self._tokens_out = 2 if cfg.recipe.looped else 0
        if 1 + self.n_slots * self.pages_per_slot <= self.n_pages:
            self._identity_tables = (
                1 + np.arange(self.n_slots * self.pages_per_slot,
                              dtype=np.int32)
            ).reshape(self.n_slots, self.pages_per_slot)
        else:
            self._identity_tables = None   # pool is undersized on
            # purpose: tables must come from the scheduler's pool
        if cache_sharding is not None:
            import jax
            self.cache = jax.device_put(self.cache, cache_sharding)
        # -- speculative decoding (optional)
        self.spec_k = int(spec_k)
        self.draft_cfg = draft_cfg
        self.draft_params = draft_params
        self.draft_cache = None
        self._draft_prefill = self._draft_step = None
        self._propose = self._verify = None
        self.verify_ce_impl: Optional[str] = None
        if draft_params is not None:
            if draft_cfg is None:
                raise ValueError("draft_params needs draft_cfg")
            if draft_cfg.vocab != cfg.vocab:
                raise ValueError("draft and target must share a vocab")
            if mesh is not None:
                raise ValueError(
                    "speculative decoding with a mesh is not wired "
                    "yet: the draft is replicated")
            if not 2 <= self.spec_k < self.max_len:
                raise ValueError(f"spec_k={spec_k} must be in "
                                 f"[2, max_len)")
            self.draft_cache = T.init_kv_cache(draft_cfg, self.n_slots,
                                               self.max_len)
            self._draft_prefill = T.build_prefill(draft_cfg,
                                                  donate=donate)
            self._draft_step = T.build_decode_step(
                draft_cfg, self.n_slots, self.max_len, donate=donate)
            self._propose = T.build_draft_propose(
                draft_cfg, self.n_slots, self.max_len, self.spec_k,
                donate=donate)
            # the verify/score pass also emits per-proposal target
            # log-probs (the acceptance-quality signal): scored by the
            # streaming fused-CE kernel when eligible (TPU,
            # lane-aligned d_model, tile-filling token count — a
            # [N, k-1] fetch instead of deriving from the [N, k, V]
            # logits), the XLA logsumexp path otherwise.
            self.verify_ce_impl = (
                verify_ce_impl if verify_ce_impl is not None
                else T.verify_ce_engine(cfg, self.n_slots, self.spec_k,
                                        sharded=mesh is not None))
            self._verify = T.build_paged_verify_step(
                cfg, self.n_slots, self.spec_k, self.page_size,
                self.pages_per_slot, donate=donate,
                cache_sharding=cache_sharding,
                with_scores=True, ce_impl=self.verify_ce_impl)

    #: the softmax block has ONE kind of cache row (a K/V row a
    #: position, kept for ever); a decoder with a second kind states a
    #: window here (``serving/eva_decode.py``)
    window: Optional[int] = None

    def rows_at(self, pos) -> "tuple[Any, Any]":
        """``(summary_rows, window_rows)`` a slot holds once the row of
        position ``pos`` is written: what the scheduler's admission,
        growth and release count (an int or an array of positions)."""
        return pos * 0, pos + 1

    def pages_for(self, pos: int) -> "tuple[int, int]":
        """Pages of each kind covering :meth:`rows_at`."""
        return 0, max(-(-(int(pos) + 1) // self.page_size), 1)

    def prefill_pages(self, prompt_len: int) -> "tuple[int, int]":
        """The most pages a prefill of ``prompt_len`` holds at once,
        the first generated row included."""
        return self.pages_for(prompt_len)

    def prefill_facts(self, prompt_len: int) -> Dict[str, int]:
        """What a ``decode.prefill`` span carries beyond the
        scheduler's own attributes: the prompt's real tokens and the
        passes each makes."""
        return {"prompt_tokens": int(prompt_len), "loops": self.n_loops}

    def prepare_facts(self, pos) -> Dict[str, int]:
        """What a ``decode.prepare`` span carries beyond the scheduler's
        own attributes, for a step at the live slots' positions
        ``pos``: nothing, for this kind."""
        return {}

    #: the last prefill's / step's expected exit passes (a looped
    #: stack: None / an array a slot)
    prefill_exit_pass: Optional[float] = None
    step_exit_pass: Optional[np.ndarray] = None
    #: windows turned into summary rows: none, for this kind
    n_compactions = 0
    #: a slot holds nothing but its rows (a decoder whose slots also
    #: hold a recurrent state says so: ``serving/hybrid_decode.py``)
    has_slot_state = False

    def lane(self, sum_pages, win_pages) -> np.ndarray:
        """A slot's page-table row from the pages it holds: the one
        place that says where each kind of page stands in the row (one
        kind here, in order of position)."""
        row = np.zeros(self.pages_per_slot, np.int32)
        row[:len(win_pages)] = win_pages
        return row

    @property
    def has_draft(self) -> bool:
        return self._verify is not None

    @property
    def has_prefix_prefill(self) -> bool:
        return self._prefix_prefill is not None

    def placement(self) -> Dict[str, Any]:
        """Where this decoder's params + KV pool live (the
        ``/decode/stats`` placement surface)."""
        if self.mesh is None:
            return {"mode": "single_device", "n_devices": 1}
        from mmlspark_tpu.parallel import dist
        out = {"mode": "tensor_parallel",
               "label": dist.placement_label(self.mesh)}
        out.update(dist.placement_report(
            {"params": self.params, "cache": self.cache}, self.mesh))
        return out

    # -- shapes --------------------------------------------------------------

    def prompt_buckets(self) -> List[int]:
        """The prefill shape ladder: pow2 buckets clamped at
        ``max_len`` (same policy as the frame plane's batch buckets —
        one ladder idiom framework-wide), or the ladder the
        constructor was given."""
        return list(self._ladder)

    def bucket_of(self, n: int) -> int:
        """The ladder's bucket a prompt (or suffix) of ``n`` tokens is
        padded to."""
        for b in self._ladder:
            if b >= n:
                return b
        raise ValueError(f"a prompt of {n} tokens is past the prefill "
                         f"ladder {self._ladder}")

    def pad_prompt(self, prompt: np.ndarray) -> np.ndarray:
        bucket = self.bucket_of(len(prompt))
        out = np.zeros(bucket, np.int32)
        out[:len(prompt)] = prompt
        return out

    # -- compute -------------------------------------------------------------

    def _table_for(self, slot: int, page_table) -> np.ndarray:
        if page_table is not None:
            return np.asarray(page_table, np.int32)
        if self._identity_tables is None:
            raise ValueError(
                "this paged pool is smaller than n_slots full lanes: "
                "page tables must come from the scheduler's PagePool")
        return self._identity_tables[slot]

    def prefill_logits(self, slot: int, prompt: np.ndarray,
                       page_table=None, draft: bool = True
                       ) -> "tuple[int, Any]":
        """Fill ``slot``'s claimed pages (``page_table``; identity
        fallback when omitted) from ``prompt``; returns the first generated greedy token AND the
        last-position logits (a device array — only a sampling caller
        pays the host fetch). With a draft configured, the draft's
        slot lane is prefilled too (both models must agree on the
        prompt before proposals mean anything) — unless
        ``draft=False``, for requests that can never speculate (the
        scheduler skips the wasted draft pass)."""
        import jax.numpy as jnp
        padded = self.pad_prompt(prompt)
        self.cache, nxt, logits = self._prefill(
            self.params, self.cache, jnp.asarray(padded),
            jnp.asarray(self._table_for(slot, page_table)),
            np.int32(len(prompt)))
        if self.has_draft and draft:
            self.draft_cache, _, _ = self._draft_prefill(
                self.draft_params, self.draft_cache,
                jnp.asarray(padded), np.int32(slot),
                np.int32(len(prompt)))
        return self._first_token(nxt), logits

    def _first_token(self, nxt) -> int:
        """A prefill's one fetch: the first generated token, and from
        a looped stack the last prompt position's expected exit pass
        with it (kept as :attr:`prefill_exit_pass`)."""
        if not self.cfg.recipe.looped:
            return int(nxt)
        tokens, exits = self._split_fetched(nxt)
        self.prefill_exit_pass = float(exits[0])
        return int(tokens[0])

    def prefill(self, slot: int, prompt: np.ndarray,
                page_table=None) -> int:
        """Greedy :meth:`prefill_logits` (compat surface)."""
        return self.prefill_logits(slot, prompt, page_table)[0]

    def prefill_prefix_logits(self, slot: int, prompt: np.ndarray,
                              hit_len: int, page_table,
                              draft: bool = True
                              ) -> "tuple[int, Any]":
        """Partial/offset prefill: the prompt's first ``hit_len``
        tokens (page-aligned, ``< len(prompt)``) already live in the
        shared prefix pages at the head of ``page_table`` — compute
        K/V only for the suffix (padded to its own bucket) while
        attending over the whole virtual lane. Token-for-token
        equivalent to :meth:`prefill_logits` (the shared pages ARE a
        previous cold prefill's rows). The draft cache (speculation)
        has no page plane, so the draft still prefills the FULL prompt
        into its dense slot lane — already-warmed prompt buckets, and
        the draft's cost is the cheap fraction by construction."""
        import jax.numpy as jnp
        if hit_len <= 0:
            return self.prefill_logits(slot, prompt, page_table,
                                       draft=draft)
        if hit_len % self.page_size or hit_len >= len(prompt):
            raise ValueError(
                f"hit_len={hit_len} must be page-aligned and < "
                f"prompt length {len(prompt)}")
        padded = self.pad_prompt(prompt[hit_len:])
        self.cache, nxt, logits = self._prefix_prefill(
            self.params, self.cache, jnp.asarray(padded),
            jnp.asarray(self._table_for(slot, page_table)),
            np.int32(len(prompt)), np.int32(hit_len))
        if self.has_draft and draft:
            self.draft_cache, _, _ = self._draft_prefill(
                self.draft_params, self.draft_cache,
                jnp.asarray(self.pad_prompt(prompt)), np.int32(slot),
                np.int32(len(prompt)))
        return self._first_token(nxt), logits

    #: steps dispatched so far: a step's sequence number, on its
    #: ``decode.dispatch`` span (``seq``) and on the ``decode.fetch``
    #: span that waited for it (``fetched``)
    n_dispatched = 0

    def dispatch_step(self, tokens, pos: np.ndarray,
                      page_tables=None) -> StepInFlight:
        """One token for every slot, as far as the host's part goes:
        the host-to-device copies and the call of the step program
        until it returns (the ``decode.dispatch`` span, in the pass the
        scheduler has open on this thread). ``tokens``/``pos`` are the
        full fixed ``[n_slots]`` arrays (free slots ride along at token
        0 / pos 0 with an all-scratch table row), or ``tokens`` is the
        :class:`StepInFlight` of the step before, not fetched yet:
        this step then takes that one's tokens as they lie on the
        device and queues behind it (``ahead`` on the span). Shared by
        every decoder class: ``_step`` returns ``(cache, fetched,
        logits, ...)`` and ``_tokens_out`` names the output that is
        the tokens."""
        import jax.numpy as jnp
        if page_tables is None:
            if self._identity_tables is None:
                raise ValueError("undersized paged pool needs "
                                 "scheduler page tables")
            page_tables = self._identity_tables
        ahead = isinstance(tokens, StepInFlight)
        self.n_dispatched += 1
        with span("decode.dispatch", seq=self.n_dispatched, ahead=ahead):
            if ahead:
                tokens = tokens.tokens
            elif self.mesh is None:
                tokens = jnp.asarray(tokens)
            else:
                # as a step's token output lies on the mesh (replicated:
                # _jit_decode), so that both forms run ONE executable
                import jax
                from jax.sharding import NamedSharding, PartitionSpec
                tokens = jax.device_put(
                    np.asarray(tokens),
                    NamedSharding(self.mesh, PartitionSpec()))
            self.cache, *outs = self._step(
                self.params, self.cache, tokens, jnp.asarray(pos),
                jnp.asarray(np.asarray(page_tables, np.int32)))
        return StepInFlight(self.n_dispatched, outs[self._tokens_out],
                            outs[0], outs[1], np.asarray(pos))

    def fetch_step(self, step: StepInFlight
                   ) -> "tuple[np.ndarray, Any]":
        """What is left of ``step`` on the device and the one copy back
        (the ``decode.fetch`` span): greedy next tokens plus the full
        per-slot logits (device array; fetched only when a sampler
        needs it). Shared by every decoder class: ``_read_fetched``
        says what a program packs beside its tokens."""
        with span("decode.fetch", fetched=step.seq) as sp:
            out = self._read_fetched(np.asarray(step.fetched), step.pos,
                                     sp.attrs)
        return out, step.logits

    def _read_fetched(self, fetched: np.ndarray, pos: np.ndarray,
                      attrs: Dict[str, Any]) -> np.ndarray:
        """A step's one copy back -> its tokens; what else it holds
        goes onto the span's ``attrs`` (a looped stack: ``[next tokens
        | expected exit passes]``)."""
        if not self.cfg.recipe.looped:
            return fetched
        out, exits = self._split_fetched(fetched)
        live = pos > 0
        self.step_exit_pass = exits
        attrs["exit_pass_mean"] = float(
            exits[live].mean() if live.any() else exits.mean())
        return out

    def step_logits(self, tokens: np.ndarray, pos: np.ndarray,
                    page_tables=None) -> "tuple[np.ndarray, Any]":
        """:meth:`dispatch_step` and :meth:`fetch_step` in a row: a
        step nobody runs ahead of."""
        return self.fetch_step(self.dispatch_step(tokens, pos,
                                                  page_tables))

    def step(self, tokens: np.ndarray, pos: np.ndarray,
             page_tables=None) -> np.ndarray:
        """Greedy :meth:`step_logits` (compat surface)."""
        return self.step_logits(tokens, pos, page_tables)[0]

    # -- speculative compute -------------------------------------------------

    def propose(self, tokens: np.ndarray, pos: np.ndarray
                ) -> np.ndarray:
        """``spec_k`` chained greedy draft steps in ONE device program
        -> proposals ``[n_slots, spec_k]`` (the draft cache advances
        in place)."""
        import jax.numpy as jnp
        with span("decode.dispatch"):
            self.draft_cache, props = self._propose(
                self.draft_params, self.draft_cache,
                jnp.asarray(tokens), jnp.asarray(pos))
        with span("decode.fetch"):
            return np.asarray(props)

    def draft_step_logits(self, tokens: np.ndarray, pos: np.ndarray
                          ) -> "tuple[np.ndarray, Any]":
        """One draft step with logits — the slow proposal path a
        sampled speculative slot needs (per-step draft distributions
        on host for rejection sampling)."""
        import jax.numpy as jnp
        with span("decode.dispatch"):
            self.draft_cache, nxt, logits = self._draft_step(
                self.draft_params, self.draft_cache,
                jnp.asarray(tokens), jnp.asarray(pos))
        with span("decode.fetch"):
            return np.asarray(nxt), logits

    def verify_logits(self, tokens: np.ndarray, pos: np.ndarray,
                      page_tables
                      ) -> "tuple[np.ndarray, Any, np.ndarray]":
        """The target's width-``spec_k`` scoring pass: ``tokens`` is
        ``[n_slots, spec_k]`` (column 0 = each slot's current input
        token, columns 1.. = draft proposals). Returns the greedy
        argmax per position, the full logits (device array — fetched
        only when a sampled slot needs rejection sampling), and the
        per-proposal target log-probs ``[n_slots, spec_k - 1]``
        (fused-CE or XLA per ``verify_ce_impl``)."""
        import jax.numpy as jnp
        with span("decode.dispatch"):
            self.cache, toks, logits, scores = self._verify(
                self.params, self.cache, jnp.asarray(tokens),
                jnp.asarray(pos),
                jnp.asarray(np.asarray(page_tables, np.int32)))
        with span("decode.fetch"):
            return np.asarray(toks), logits, np.asarray(scores)

    def _warm_step(self, tables: np.ndarray) -> None:
        """The step in both forms the scheduler's loop runs it: from
        host tokens, and ahead of a step that is not fetched yet, from
        that one's tokens on the device (one executable on jax 0.9:
        both are uncommitted int32 arrays of one shape, and under a
        mesh :meth:`dispatch_step` places the host's tokens as a
        step's output lies; were it two, both are compiled here and
        counted). Shared by every decoder class."""
        zeros = np.zeros(self.n_slots, np.int32)
        first = self.dispatch_step(zeros, zeros.copy(), tables)
        self.fetch_step(self.dispatch_step(first, zeros.copy(), tables))
        self.fetch_step(first)

    def n_compiles(self) -> int:
        """Compiled-executable count across every jitted entry point
        (prefill buckets, the step, and the draft/propose/verify
        machinery when speculation is on): flat after warmup = zero
        retraces."""
        n = int(self._prefill._cache_size() + self._step._cache_size())
        for fn in (self._draft_prefill, self._draft_step,
                   self._propose, self._verify,
                   self._prefix_prefill):
            if fn is not None:
                n += int(fn._cache_size())
        return n

    def warmup(self) -> int:
        """Compile the decode step, every prefill bucket, and (when
        speculation is on) the draft/propose/verify machinery before
        traffic (the cache content it writes lands on the scratch page
        and the draft's free lanes, which the next real prefill
        overwrites). Returns
        the compile count — the post-warmup baseline."""
        zeros_t = np.zeros(self.n_slots, np.int32)
        zero_tables = np.zeros((self.n_slots, self.pages_per_slot),
                               np.int32)
        self._warm_step(zero_tables)
        for bucket in self._ladder:
            self.prefill(0, np.zeros(min(bucket, self.max_len - 1),
                                     np.int32), zero_tables[0])
        if self._prefix_prefill is not None:
            # the offset prefill compiles per SUFFIX bucket — the same
            # pow2 ladder (hit depth is a traced scalar, not a shape)
            import jax.numpy as jnp
            for bucket in self._ladder:
                self.cache, _, _ = self._prefix_prefill(
                    self.params, self.cache,
                    jnp.asarray(np.zeros(bucket, np.int32)),
                    jnp.asarray(zero_tables[0]),
                    np.int32(1), np.int32(0))
        if self.has_draft:
            self.propose(zeros_t, zeros_t.copy())
            self.draft_step_logits(zeros_t, zeros_t.copy())
            self.verify_logits(
                np.zeros((self.n_slots, self.spec_k), np.int32),
                zeros_t.copy(), zero_tables)
        return self.n_compiles()

def decoder_for(params, cfg, **kwargs):
    """The decoder of ``cfg``'s block kind: the configuration object
    says which block it describes (``block_kind``; a config without
    one is the softmax block), and the serving plane builds the decoder
    that holds that kind's cache."""
    kind = getattr(cfg, "block_kind", "softmax")
    if kind == "eva":
        from mmlspark_tpu.serving.eva_decode import EvaByteDecoder
        return EvaByteDecoder(params, cfg, **kwargs)
    if kind == "granite_hybrid":
        from mmlspark_tpu.serving.hybrid_decode import HybridDecoder
        return HybridDecoder(params, cfg, **kwargs)
    if kind != "softmax":
        raise ValueError(f"no decoder for block kind {kind!r}")
    return TransformerDecoder(params, cfg, **kwargs)


class Sampler:
    """Per-request seeded token sampling over the step's full logits.

    Greedy decode stays the device-side argmax (no logits transfer);
    a request that asks for ``temperature > 0`` gets temperature /
    top-k / nucleus (top-p) sampling on host from its slot's logits
    row, driven by its own ``numpy`` PRNG — so one ``seed`` makes a
    sampled decode bit-for-bit reproducible whatever other requests
    share the batch (slot independence extends to randomness)."""

    __slots__ = ("temperature", "top_k", "top_p", "seed", "_rng")

    def __init__(self, temperature: float, top_k: int = 0,
                 top_p: float = 1.0, seed: Optional[int] = None):
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.seed = seed
        self._rng = np.random.default_rng(seed)

    def probs(self, logits: np.ndarray) -> np.ndarray:
        """The transformed distribution (temperature, then top-k, then
        nucleus restriction, renormalized) — the ``p``/``q`` both
        sides of speculative rejection sampling score against."""
        l = logits.astype(np.float64) / max(self.temperature, 1e-6)
        if 0 < self.top_k < l.size:
            kth = np.partition(l, -self.top_k)[-self.top_k]
            l = np.where(l < kth, -np.inf, l)
        l = l - l.max()
        p = np.exp(l)
        p /= p.sum()
        if self.top_p < 1.0:
            order = np.argsort(-p, kind="stable")
            cum = np.cumsum(p[order])
            # smallest prefix whose mass reaches top_p (>= 1 token)
            keep = int(np.searchsorted(cum, self.top_p)) + 1
            mask = np.zeros(p.size, bool)
            mask[order[:keep]] = True
            p = np.where(mask, p, 0.0)
            p /= p.sum()
        return p

    def sample(self, logits: np.ndarray) -> int:
        return int(self._rng.choice(logits.size,
                                    p=self.probs(logits)))

    def draw(self, p: np.ndarray) -> int:
        """Draw from an explicit distribution with this request's own
        PRNG (speculative residual draws stay per-request seeded)."""
        return int(self._rng.choice(p.size, p=p))

    def uniform(self) -> float:
        """One accept/reject draw from the request's PRNG."""
        return float(self._rng.random())

    def describe(self) -> Dict[str, Any]:
        return {"temperature": self.temperature, "top_k": self.top_k,
                "top_p": self.top_p, "seed": self.seed}


class SlotPool:
    """Free-slot index pool. Claim/release are O(1) under one lock —
    release checks the claimed SET, not the free list (the old ``slot
    in self._free`` scan was O(n_free) per release inside the step
    loop, the same ledger mistake :class:`PagePool` already fixed);
    the scheduler loop is the only claimer, but cancel paths and tests
    read ``n_free`` concurrently."""

    def __init__(self, n_slots: int):
        self.n_slots = int(n_slots)
        self._free = list(range(self.n_slots - 1, -1, -1))
        self._claimed: set = set()
        self._lock = threading.Lock()

    def claim(self) -> Optional[int]:
        with self._lock:
            if not self._free:
                return None
            slot = self._free.pop()
            self._claimed.add(slot)
            return slot

    def release(self, slot: int) -> None:
        with self._lock:
            if slot not in self._claimed:
                raise RuntimeError(f"slot {slot} double-released")
            self._claimed.discard(slot)
            self._free.append(slot)

    @property
    def n_free(self) -> int:
        with self._lock:
            return len(self._free)


class PagePool:
    """Refcounted free-page index pool over the paged KV cache. Page 0
    is the scratch page (unclaimed table entries route writes there)
    and is never handed out, so a pool of ``n_pages`` holds
    ``n_pages - 1`` claimable pages.

    Every claimed page carries a **refcount**: ``claim`` hands out
    fresh pages at refcount 1, ``ref`` adds a reader to
    already-claimed pages (how a request attaches a cached prefix —
    and how the :class:`PrefixCache` itself pins the pages it
    publishes), and ``release`` drops a reference — a page returns to
    the free list only when its LAST holder releases it. ``claim`` is
    all-or-nothing — a request either gets every page it asked for or
    none (no partial grabs to leak on the error path). The high-water
    mark and the idle invariant (``n_free`` plus index-held pages ==
    ``n_pages - 1``, every surviving refcount exactly the index's own)
    are the page-leak ledger the chaos tests assert — refcounts, not
    raw ownership."""

    def __init__(self, n_pages: int):
        self.n_pages = int(n_pages)
        self._free = list(range(self.n_pages - 1, 0, -1))
        # page -> refcount for claimed pages: O(1) double-release
        # detection AND the sharing ledger in one structure
        self._ref: Dict[int, int] = {}
        self._lock = threading.Lock()
        self.high_water = 0

    def claim(self, n: int = 1) -> Optional[List[int]]:
        with self._lock:
            if n > len(self._free):
                return None
            pages = [self._free.pop() for _ in range(n)]
            for p in pages:
                self._ref[p] = 1
            if len(self._ref) > self.high_water:
                self.high_water = len(self._ref)
            return pages

    def ref(self, pages: List[int]) -> None:
        """Add one reader to each already-claimed page (attaching a
        shared prefix). Raises on a page nobody holds — refcounts on
        free pages would resurrect reclaimed state."""
        with self._lock:
            for p in pages:
                if p not in self._ref:
                    raise RuntimeError(
                        f"page {p} ref'd while unclaimed")
            for p in pages:
                self._ref[p] += 1

    def release(self, pages: List[int]) -> None:
        with self._lock:
            for p in pages:
                if p not in self._ref:
                    raise RuntimeError(f"page {p} double-released")
                self._ref[p] -= 1
                if self._ref[p] == 0:
                    del self._ref[p]
                    self._free.append(p)

    def refcount(self, page: int) -> int:
        with self._lock:
            return self._ref.get(page, 0)

    @property
    def n_free(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def n_claimed(self) -> int:
        with self._lock:
            return len(self._ref)


class _RadixNode:
    """One cached page: keyed in its parent by the ``page_size``-token
    chunk whose K/V rows the page holds. ``parent``/``key`` back-links
    make leaf eviction O(log n) per victim (pop a leaf, its parent
    becomes the next candidate) instead of a full re-walk each."""

    __slots__ = ("children", "page", "last_used", "parent", "key",
                 "tenant")

    def __init__(self, page: int, now: float, parent=None, key=None,
                 tenant: str = ""):
        self.children: Dict[tuple, "_RadixNode"] = {}
        self.page = page
        self.last_used = now
        self.parent = parent
        self.key = key
        # the tenant whose finished request published this page ("" =
        # unattributed): quota charging and over-quota-first eviction
        # key off it; SHARING stays tenant-blind (lookup never checks)
        self.tenant = tenant


class PrefixCache:
    """Content-addressed index over the paged KV pool: a radix tree
    keyed at page granularity (``page_size``-token chunks of prompt
    token ids) mapping a new prompt to its longest cached prefix
    (docs/serving.md "Prefix cache").

    The tree holds ONE reference on every published page (via
    :meth:`PagePool.ref` semantics — publication transfers the
    finishing request's reference instead of freeing the page), so a
    cached page with refcount 1 is **unreferenced** — evictable — and
    refcount > 1 means live readers are attached. ``lookup`` walks
    whole chunks, refs the matched pages for the caller (the caller
    releases them at finish like any claimed page), and stamps the
    path's LRU clocks; ``publish`` inserts a finished request's
    fully-written PROMPT pages (never a page its owner might still
    write: generated-token pages and the partial tail page stay
    private and are freed). ``evict_for`` reclaims LRU unreferenced
    leaves under pressure; ``max_pages`` bounds the resident set.

    Thread safety: one lock over the tree. Pool refcount mutations for
    matched/published pages happen under it, so a concurrent
    ``release`` can never free a page between the radix match and the
    ``ref`` that pins it."""

    def __init__(self, pool: PagePool, page_size: int,
                 max_pages: Optional[int] = None,
                 clock: Clock = SYSTEM_CLOCK):
        self.pool = pool
        self.page_size = int(page_size)
        # default bound: the whole claimable pool — eviction under
        # claim pressure keeps live requests ahead of cache residency
        self.max_pages = (int(max_pages) if max_pages is not None
                          else pool.n_pages - 1)
        self.clock = clock
        self._root = _RadixNode(page=0, now=0.0)
        self._lock = threading.Lock()
        self.n_cached = 0
        self.n_lookups = 0
        self.n_hits = 0
        self.n_hit_tokens = 0
        self.n_published = 0
        self.n_evicted = 0
        # per-tenant residency: publication charges the owning tenant;
        # quotas bound a tenant's resident pages (eviction inside the
        # over-quota tenant first — one flood cannot monopolize the
        # shared index). Tenants without a quota are unbounded.
        self._quotas: Dict[str, int] = {}
        self._tenant_pages: Dict[str, int] = {}

    def set_quota(self, tenant_id: str,
                  max_pages: Optional[int]) -> None:
        """Bound ``tenant_id``'s resident cached pages (``None``
        removes the bound). Enforced at publish time: an over-quota
        tenant evicts ITS OWN LRU pages to make room, never another
        tenant's."""
        with self._lock:
            if max_pages is None:
                self._quotas.pop(tenant_id, None)
            else:
                self._quotas[tenant_id] = int(max_pages)

    def _charge_locked(self, tenant: str, n: int) -> None:
        c = self._tenant_pages.get(tenant, 0) + n
        if c > 0:
            self._tenant_pages[tenant] = c
        else:
            self._tenant_pages.pop(tenant, None)

    def _chunks(self, tokens, n: int):
        ps = self.page_size
        return [tuple(int(t) for t in tokens[i * ps:(i + 1) * ps])
                for i in range(n)]

    def lookup(self, prompt) -> "tuple[int, List[int]]":
        """Longest cached prefix of ``prompt`` -> ``(hit_len,
        pages)``, with the pages ref'd for the caller. ``hit_len`` is
        page-aligned and capped at ``len(prompt) - 1`` — the last
        prompt position is always computed by the (partial) prefill,
        which needs its logits for the first generated token.

        Does NOT count itself: a head-of-line request short of suffix
        pages re-queues and looks up again next pass, so the exported
        (monotonic) counters are bumped once per ADMITTED request via
        :meth:`count` instead of once per attempt."""
        max_chunks = (len(prompt) - 1) // self.page_size
        with self._lock:
            node, pages = self._root, []
            now = self.clock.now()
            for chunk in self._chunks(prompt, max_chunks):
                child = node.children.get(chunk)
                if child is None:
                    break
                child.last_used = now
                pages.append(child.page)
                node = child
            if not pages:
                return 0, []
            self.pool.ref(pages)
            return len(pages) * self.page_size, pages

    def count(self, hit_len: int) -> None:
        """Record one admitted request's lookup outcome in the hit
        ledger (monotonic — these back Prometheus counters)."""
        with self._lock:
            self.n_lookups += 1
            if hit_len > 0:
                self.n_hits += 1
                self.n_hit_tokens += hit_len

    def miss_count(self) -> int:
        """``misses = lookups - hits`` from ONE locked snapshot — the
        two fields update together under the lock, so an unlocked
        two-field read could tear mid-update and hand Prometheus a
        transiently decreasing counter (read as a reset)."""
        with self._lock:
            return self.n_lookups - self.n_hits

    def publish(self, prompt, pages: List[int],
                tenant: Optional[str] = None) -> "set":
        """Insert a finished request's prompt-complete pages
        (``pages[i]`` holds prompt rows ``[i*ps, (i+1)*ps)``) into the
        tree. Only pages newly ABSORBED by the index (their reference
        transferred from the request to the cache) are returned — the
        caller releases everything else: chunks already present keep
        the incumbent page (identical content — K/V is a pure function
        of the token prefix) and the duplicate stays the caller's to
        free. Absorption respects ``max_pages``: LRU unreferenced
        pages are evicted to make room, and when nothing is evictable
        the remaining chunks simply stay unpublished. ``tenant``
        attributes the fresh pages to their owner: a tenant at its
        :meth:`set_quota` bound evicts its OWN LRU pages first, and
        when none are evictable its surplus chunks stay unpublished
        (the caller frees them) — other tenants' residency is never
        taxed for one tenant's churn."""
        n_chunks = min(len(prompt) // self.page_size, len(pages))
        if n_chunks == 0:
            return set()
        owner = tenant or ""
        quota = self._quotas.get(owner) if owner else None
        absorbed: set = set()
        with self._lock:
            # size the eviction ONCE: count the chunks actually
            # missing (cheap path walk), then a single heap-seeded
            # _evict_locked covers them all — the per-chunk fallback
            # below only fires when eviction came up short, so a warm
            # cache at its bound pays one tree walk per publish, not
            # one per fresh chunk
            chunks = self._chunks(prompt, n_chunks)
            node, missing = self._root, 0
            for chunk in chunks:
                if node is not None:
                    node = node.children.get(chunk)
                if node is None:
                    missing += 1
            shortfall = self.n_cached + missing - self.max_pages
            if missing and shortfall > 0:
                self._evict_pressure_locked(shortfall)
            node = self._root
            now = self.clock.now()
            path: set = set()            # every node on this publish's
            # chain — fresh or matched. A mid-publish eviction that
            # removed one (a fresh page is a refcount-1 leaf until the
            # next chunk lands; a MATCHED incumbent can be refcount-1
            # too when this publisher duplicated rather than attached
            # it) would orphan the subtree being extended — its pages
            # unreachable forever, the ledger permanently dirty.
            for i, chunk in enumerate(chunks):
                child = node.children.get(chunk)
                if child is None:
                    if quota is not None and \
                            self._tenant_pages.get(owner, 0) >= quota \
                            and not self._evict_locked(
                                1, exclude=path, tenant=owner):
                        break    # at quota, nothing of OURS evictable
                    if self.n_cached >= self.max_pages and \
                            not self._evict_locked(1, exclude=path):
                        break            # full and pinned: stop here
                    child = _RadixNode(pages[i], now, parent=node,
                                       key=chunk, tenant=owner)
                    node.children[chunk] = child
                    self.n_cached += 1
                    self.n_published += 1
                    self._charge_locked(owner, 1)
                    absorbed.add(pages[i])
                else:
                    child.last_used = now
                path.add(id(child))
                node = child
        return absorbed

    def _nodes_locked(self):
        """Every node in the tree (root excluded). Caller holds the
        lock."""
        stack = [self._root]
        while stack:
            nd = stack.pop()
            for child in nd.children.values():
                yield child
                stack.append(child)

    def _evict_locked(self, n: int, exclude=frozenset(),
                      tenant: Optional[str] = None) -> int:
        """Evict up to ``n`` LRU leaves whose page has no reader
        beyond the index itself (refcount 1). Leaves only: an
        interior node's descendants are reachable exclusively through
        it — but evicting a leaf can TURN its parent into one, so
        candidates ride a heap seeded by one walk and parents join as
        their last child goes (O(n_cached + evicted·log) instead of a
        full re-walk per victim). ``exclude`` holds the node ids an
        in-flight publish is building under (never evict the chain
        being extended). ``tenant`` restricts victims to one tenant's
        pages (the over-quota-first path)."""
        import heapq
        heap = [(nd.last_used, i, nd)
                for i, nd in enumerate(self._nodes_locked())
                if not nd.children
                and (tenant is None or nd.tenant == tenant)]
        heapq.heapify(heap)
        seq = len(heap)
        evicted = 0
        while evicted < n and heap:
            _, _, nd = heapq.heappop(heap)
            if nd.children or nd.parent is None \
                    or nd.parent.children.get(nd.key) is not nd:
                continue                 # stale entry: re-parented or
                # already evicted this round
            if id(nd) in exclude or \
                    self.pool.refcount(nd.page) != 1:
                continue                 # pinned or publish-in-flight
            nd.parent.children.pop(nd.key)
            self.pool.release([nd.page])
            self.n_cached -= 1
            self.n_evicted += 1
            self._charge_locked(nd.tenant, -1)
            evicted += 1
            parent = nd.parent
            if not parent.children and parent is not self._root \
                    and (tenant is None or parent.tenant == tenant):
                heapq.heappush(heap, (parent.last_used, seq, parent))
                seq += 1
        return evicted

    def _evict_pressure_locked(self, n: int,
                               exclude=frozenset()) -> int:
        """Claim-pressure eviction: reclaim from OVER-QUOTA tenants
        first (most-over first), then fall back to global LRU — so a
        tenant camping past its budget pays for pool pressure before
        anyone inside theirs does."""
        evicted = 0
        if self._quotas:
            over = sorted(
                ((self._tenant_pages.get(t, 0) - q, t)
                 for t, q in self._quotas.items()
                 if self._tenant_pages.get(t, 0) > q),
                reverse=True)
            for surplus, t in over:
                if evicted >= n:
                    break
                evicted += self._evict_locked(
                    min(n - evicted, surplus), exclude=exclude,
                    tenant=t)
        if evicted < n:
            evicted += self._evict_locked(n - evicted,
                                          exclude=exclude)
        return evicted

    def evict_for(self, n_needed: int) -> int:
        """Reclaim LRU unreferenced cached pages until the pool can
        hand out ``n_needed`` pages (or nothing evictable remains).
        Returns the number evicted."""
        with self._lock:
            short = n_needed - self.pool.n_free
            return self._evict_pressure_locked(short) if short > 0 \
                else 0

    @property
    def n_evictable(self) -> int:
        """Cached pages no live request holds — reclaimable headroom.
        O(n_cached) tree walk with a pool-lock hop per page: a stats /
        test surface, NOT for per-request paths (admission uses the
        O(1) ``n_cached`` upper bound instead)."""
        with self._lock:
            return sum(1 for nd in self._nodes_locked()
                       if self.pool.refcount(nd.page) == 1)

    def ledger_clean(self) -> bool:
        """The IDLE/drain refcount invariant: every cached page is
        held by exactly the index (refcount 1) and free + cached
        accounts for the whole claimable pool — no request left a
        reference behind. Meaningful only with no requests live (a
        healthy reader mid-decode holds refcount 2); scrape it at
        drain, alert on it at idle."""
        with self._lock:
            pages = [nd.page for nd in self._nodes_locked()]
            if len(pages) != self.n_cached:
                return False
        if any(self.pool.refcount(p) != 1 for p in pages):
            return False
        return (self.pool.n_free + len(pages)
                == self.pool.n_pages - 1)

    def clear(self) -> int:
        """Release every cached page back to the pool (drain /
        shutdown). Pages with live readers lose only the index's
        reference. Returns the number of entries dropped."""
        with self._lock:
            pages = [nd.page for nd in self._nodes_locked()]
            self._root.children.clear()
            dropped, self.n_cached = self.n_cached, 0
            self._tenant_pages.clear()
            if pages:
                self.pool.release(pages)
            return dropped

    def stats(self) -> Dict[str, Any]:
        return {"page_size": self.page_size,
                "max_pages": self.max_pages,
                "cached_pages": self.n_cached,
                "evictable_pages": self.n_evictable,
                "lookups": self.n_lookups,
                "hits": self.n_hits,
                "hit_rate": (round(self.n_hits / self.n_lookups, 4)
                             if self.n_lookups else None),
                "hit_tokens": self.n_hit_tokens,
                "published_pages": self.n_published,
                "evicted_pages": self.n_evicted,
                "tenant_pages": dict(self._tenant_pages),
                "tenant_quotas": dict(self._quotas),
                "ledger_clean": self.ledger_clean()}


#: tokens-per-request histogram ladder (powers of two): its own edges,
#: NOT the latency buckets — the registry rejects bucket mismatches
#: per metric name, so the ladder is explicit here
TOKENS_PER_REQUEST_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0,
                              128.0, 256.0, 512.0, 1024.0)

#: per-request cap on timeline spans (first_token + spec_round events)
#: so a 100k-token decode cannot flood the flight recorder ring
_MAX_TIMELINE_SPANS = 128

#: the phases of one pass of the scheduler's loop, each a
#: ``decode.<phase>`` span (docs/observability.md "The decode loop"):
#: the keys of ``/decode/stats`` -> ``loop`` and of a ``decode.pass``
#: span's ``phases_ms``
LOOP_PHASES = ("admit", "prefill", "prepare", "dispatch", "fetch",
               "emit", "compact", "idle")
#: the order a pass ran in, ``order`` on its ``decode.prepare``
#: (:meth:`DecodeScheduler._run_step`): its step went out behind a step
#: in flight; was left in flight with nothing before it; today's order
#: (prepare, dispatch, fetch, emit); the pass only fetched the step in
#: flight; a speculative round
PASS_ORDERS = ("ahead", "start", "in_turn", "fetch_only", "spec_round")
#: what held a pass that is not ``ahead`` / ``start`` to today's order,
#: ``held_by`` on its ``decode.prepare`` and the keys of
#: ``/decode/stats`` -> ``held_by``: the first condition of
#: :meth:`DecodeScheduler._may_run_ahead` that said no, in its order
HELD_BY = ("free_slot", "sampler", "speculative", "riders_changed",
           "last_token", "lane_full", "window_fill", "cancelled",
           "stream_closed", "deadline", "no_pages")
#: the phases of a pass in which the device can be left with nothing of
#: the loop's queued (:meth:`DecodeScheduler._record_pass`): the keys of
#: a pass's ``starved_ms`` and of ``/decode/stats`` -> ``loop.starved``
STARVED_PHASES = ("admit", "prepare", "dispatch", "emit")
#: the route ``decode.pass`` spans are captured under, and how many
#: times the running median of the passes that ran a step a pass must
#: last to be retained as slow (``GET /traces``)
LOOP_ROUTE = "decode.loop"
SLOW_PASS_MULTIPLE = 5.0


def pass_view(phases) -> Dict[str, Any]:
    """What a ``decode.pass`` span's ``phases`` say, spelled out: the
    spans of one pass as they closed, ``(name, t0_ns, t1_ns, attrs)``,
    as ``{"phases_ms": {phase: milliseconds}, "prefills": [one entry a
    ``decode.prefill``: its attributes, ``start_ms`` into the pass and
    ``ms``], **the other phases' attributes}`` (``admitted``,
    ``active``, ``pages_in_use``, ``n_pages``, ``emitted``; ``order``,
    the order the pass ran in (``PASS_ORDERS``), and in a pass held to
    today's order ``held_by``, the rule's word (``HELD_BY``); of the
    step the pass dispatched ``seq`` and ``ahead``, whether a step was
    in flight as it went out, and ``fetched``, the sequence number of
    the step the pass's fetch waited for: ``seq`` less one in a pass
    that ran ahead). Read-side: the loop stores the spans as they
    are."""
    t0 = min(p[1] for p in phases)
    view: Dict[str, Any] = {"phases_ms": {}, "prefills": []}
    for name, a, b, attrs in phases:
        key = name[7:]                           # "decode.<phase>"
        view["phases_ms"][key] = \
            view["phases_ms"].get(key, 0.0) + (b - a) * 1e-6
        if key == "prefill":
            view["prefills"].append(dict(
                attrs, start_ms=(a - t0) * 1e-6, ms=(b - a) * 1e-6))
        elif attrs:
            view.update(attrs)
    view.pop("traces", None)       # the pass carries them itself
    return view


def stall_word(ns: int, cpu_ns: int, proc_ns: int) -> str:
    """Who ran in a pass of ``ns`` nanoseconds in which the loop's
    thread had ``cpu_ns`` of CPU time and the whole process
    ``proc_ns``: ``on_cpu`` (the thread computed for over half of it),
    ``contended`` (it did not, and the process's threads together did:
    the frontend, a client, a compile ran while the loop waited for
    them or beside them), ``blocked`` (neither: nothing of this
    process ran; the runtime's wait, or the machine stood still)."""
    if 2 * cpu_ns > ns:
        return "on_cpu"
    return "contended" if 2 * proc_ns > ns else "blocked"


class _DecodeRequest:
    """Per-request decode state, riding alongside the server's
    ``_PendingRequest`` (``pending`` — reply/status/event/callbacks/
    deadline/trace/span/stream all live there)."""

    __slots__ = ("pending", "prompt", "max_new", "produced", "slot",
                 "cancelled", "t_submit", "t_prefill", "t_decode",
                 "t_first", "t_last", "n_timeline",
                 "sampler", "spec", "pages", "sum_pages", "hit_len")

    def __init__(self, pending, prompt: np.ndarray, max_new: int,
                 sampler: Optional[Sampler] = None,
                 spec: Optional[bool] = None):
        self.pending = pending
        self.prompt = prompt
        self.max_new = int(max_new)
        self.sampler = sampler
        # speculative opt-in/out from the payload; None = default
        # (greedy slots speculate when a draft exists, sampled slots
        # only on explicit opt-in — rejection sampling changes PRNG
        # consumption, so a seeded client must ask for it)
        self.spec = spec
        self.produced: List[int] = []       # incremental emission
        self.slot: Optional[int] = None
        self.pages: List[int] = []          # held KV pages:
        # the first hit_len // page_size are SHARED prefix pages
        # (ref'd, read-only), the rest privately claimed
        # pages of the second kind of row (a finished window's
        # summaries; decoders with a ``window``): ahead of ``pages`` in
        # the slot's table, kept until the request leaves
        self.sum_pages: List[int] = []
        self.hit_len = 0                    # cached-prefix depth
        self.cancelled = False
        self.t_submit: float = 0.0
        self.t_prefill: float = 0.0
        self.t_decode: float = 0.0
        # token-level timeline stamps (scheduler clock): first emitted
        # token and the latest emit — TTFT/TPOT fall out at _finish
        self.t_first: float = 0.0
        self.t_last: float = 0.0
        self.n_timeline = 0                 # timeline spans recorded

    @property
    def stream(self):
        return getattr(self.pending, "stream", None)


class DecodeScheduler:
    """The continuous-batching step loop.

    ``submit()`` (any thread) parses and enqueues; the loop thread
    admits waiting requests into free slots between steps, runs the
    fixed-shape decode step while any slot is live, and resolves
    requests through the server's commit path (journal + spans +
    waiter release) — or a standalone default when unbound (direct
    scheduler tests).

    Slot lifecycle (docs/serving.md "Continuous batching"):

    ``waiting -> prefill(slot claimed) -> stepping -> released`` on
    the first of: EOS, ``max_new_tokens`` produced, cache lane full
    (``max_len``), deadline expired, cancel, or an injected/real step
    fault. Every exit path releases the slot — the slot-leak chaos
    test churns all of them and asserts ``n_free == n_slots`` after.
    """

    def __init__(self, decoder: TransformerDecoder,
                 max_waiting: int = 256,
                 max_new_tokens_default: int = 64,
                 clock: Clock = SYSTEM_CLOCK,
                 fault_plan=None,
                 registry=None, tracer=None,
                 idle_wait_s: float = 0.02,
                 spec_policy="auto",
                 prefix_cache="auto",
                 prefix_cache_pages: Optional[int] = None):
        from mmlspark_tpu.serving.policy import SpeculationPolicy
        self.decoder = decoder
        #: the longest prompt admitted: a decoder that pads a prompt
        #: to ONE bucket of a ladder states its top (a shorter ladder
        #: than max_len is a smaller limit); one that walks a prompt in
        #: tiles or windows takes any that leaves a row to generate
        self.max_prompt = getattr(decoder, "max_prompt",
                                  decoder.max_len - 1)
        # acceptance-gated speculation (serving/policy.py): "auto"
        # installs the default policy when a draft exists, None runs
        # speculation unconditionally, or pass a configured
        # SpeculationPolicy
        if spec_policy == "auto":
            spec_policy = (SpeculationPolicy() if decoder.has_draft
                           else None)
        self.spec_policy = spec_policy
        self.max_waiting = int(max_waiting)
        self.max_new_tokens_default = int(max_new_tokens_default)
        self.clock = clock
        self.fault_plan = fault_plan
        self.tracer = tracer
        self.idle_wait_s = float(idle_wait_s)
        self.pool = SlotPool(decoder.n_slots)
        # the page plane: the shared page pool plus the live
        # [n_slots, pages_per_slot] tables the jitted step/verify
        # read — unclaimed entries stay 0 (the scratch page)
        self.pages = PagePool(decoder.n_pages)
        self._tables = np.zeros(
            (decoder.n_slots, decoder.pages_per_slot), np.int32)
        # the cross-request prefix cache: "auto" turns it on exactly
        # when the decoder built the offset-prefill machinery
        # (prefix_cache=False there is the A/B baseline)
        self.prefix: Optional[PrefixCache] = None
        if prefix_cache == "auto":
            prefix_cache = decoder.has_prefix_prefill
        if prefix_cache:
            if not decoder.has_prefix_prefill:
                raise ValueError(
                    "prefix_cache=True needs a decoder built "
                    "with prefix_cache=True (the offset-prefill "
                    "machinery)")
            self.prefix = PrefixCache(
                self.pages, decoder.page_size,
                max_pages=prefix_cache_pages, clock=clock)
        self._waiting: deque = deque()
        self._by_rid: Dict[str, _DecodeRequest] = {}
        self._active: Dict[int, _DecodeRequest] = {}
        self._tokens = np.zeros(decoder.n_slots, np.int32)
        self._pos = np.zeros(decoder.n_slots, np.int32)
        self._lock = threading.Lock()
        self._work = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # resolved by bind(); standalone default releases the pending
        # directly (event + callbacks), no journal
        self._commit: Callable[[Any], None] = self._standalone_commit
        self.n_requests = 0
        self.n_steps = 0
        self.n_tokens = 0
        self.n_prefills = 0
        # the prefill-throughput ledger the prefix-cache A/B gates on:
        # prompt tokens SERVED (cached prefix included) over prefill
        # wall-clock — a hit shrinks the wall, not the numerator
        self.n_prompt_tokens = 0
        self.prefill_s = 0.0
        self.n_step_faults = 0
        # the step in flight: dispatched in a pass that is over and not
        # fetched yet, ``(decoder's StepInFlight, the requests it
        # carries by slot, its dispatch's start in seconds)``; steps
        # dispatched behind one; tokens of lanes whose request had left
        # by the time their step was fetched; the last fetch's end
        self._flight: Optional[tuple] = None
        self.n_steps_ahead = 0
        self.n_tokens_discarded = 0
        self._t_fetched = 0.0
        self.slots_high_water = 0
        self.n_page_preempts = 0
        # speculative ledger: acceptance_rate = accepted / proposed
        self.n_spec_rounds = 0
        self.n_spec_proposed = 0
        #: EWMA of the verify score-head's per-proposal target log-
        #: probs (fused-CE/XLA — acceptance quality, not just rate)
        self.spec_proposal_logp = None
        self.n_spec_accepted = 0
        self.releases: Dict[str, int] = {}   # finish_reason -> count
        # goodput: tokens delivered by CLEAN finishes (eos/length) —
        # the numerator; n_tokens stays the all-reasons denominator
        self.n_goodput_tokens = 0
        # the loop's own record: the phases the open pass has closed so
        # far (the loop thread's; the decoder's dispatch and fetch land
        # here too), each phase's [count, nanoseconds] since start, and
        # the lengths of the last passes that ran a step, whose median
        # sets the slow-pass threshold
        self._pass: list = []
        self.loop = {f"decode.{name}": [0, 0] for name in LOOP_PHASES}
        self._pass_ns: deque = deque(maxlen=64)
        self._slow_ns: Optional[float] = None
        # what _record_pass carries from pass to pass: whether a step
        # of this loop is in flight and the last one's sequence number,
        # the nanoseconds the device was left without work by phase,
        # the passes each word of the rule held, and the loop thread's
        # and the process's CPU clocks as the pass before ended
        self._in_flight = False
        self._seq_out = 0
        self.starved_ns = dict.fromkeys(STARVED_PHASES, 0)
        self.held_by = dict.fromkeys(HELD_BY, 0)
        self._cpu_ns = (time.thread_time_ns(), time.process_time_ns())
        # tenancy hooks (wired by bind() against the server's
        # registry): slot-release EWMA feeds honest decode-429
        # Retry-After; the fair cycle orders slot claims per tenant
        self._server = None
        self.release_ewma = ReleaseRateEwma(clock=clock)
        self._fair = FairCycle()
        self._m_prefill = None
        self._m_step = None
        self._m_spec_round = None
        self._m_queue_wait = None
        self._m_ttft = None
        self._m_tpot = None
        self._m_tokens_req = None
        self._m_device = None
        if registry is not None:
            self._register_metrics(registry)

    # -- wiring --------------------------------------------------------------

    def bind(self, server) -> None:
        """Attach to a :class:`ServingServer`: its registry, tracer,
        clock, and commit path (journaled exactly-once replies) become
        this scheduler's."""
        self.clock = server.clock
        self.tracer = server.tracer
        self._commit = server._commit
        self._server = server
        self.release_ewma = ReleaseRateEwma(clock=server.clock)
        # per-tenant prefix-cache page budgets come from the registry
        if self.prefix is not None \
                and getattr(server, "tenancy", None) is not None:
            for t in server.tenancy.tenants.values():
                if t.max_cache_pages is not None:
                    self.prefix.set_quota(t.id, t.max_cache_pages)
        self._register_metrics(server.registry)

    def _register_metrics(self, m) -> None:
        m.gauge("serving_decode_slots_in_use",
                "KV-cache slots currently decoding."
                ).set_function(lambda: len(self._active))
        m.gauge("serving_decode_slots_free",
                "Free KV-cache slots.").set_function(
            lambda: self.pool.n_free)
        m.gauge("serving_decode_waiting",
                "Decode requests admitted but not yet in a slot."
                ).set_function(lambda: len(self._waiting))
        for name, help_, fn in (
            ("serving_decode_requests_total",
             "Decode requests that entered the scheduler.",
             lambda: self.n_requests),
            ("serving_decode_steps_total",
             "Single-token decode steps executed (each covers every "
             "live slot).", lambda: self.n_steps),
            ("serving_decode_steps_ahead_total",
             "Decode steps dispatched behind a step that was not "
             "fetched yet (the host's turn ran beside the device).",
             lambda: self.n_steps_ahead),
            ("serving_decode_tokens_total",
             "Tokens emitted to live requests.",
             lambda: self.n_tokens),
            ("serving_decode_tokens_discarded_total",
             "Tokens of a step in flight whose request had left by "
             "the time it was fetched (never emitted).",
             lambda: self.n_tokens_discarded),
            ("serving_decode_prefills_total",
             "Prompt prefills (slot claims).",
             lambda: self.n_prefills),
            ("serving_decode_step_faults_total",
             "Decode steps that raised (injected or real); affected "
             "requests 500, slots are released.",
             lambda: self.n_step_faults),
            ("serving_decode_page_preempts_total",
             "Requests finished early because the page pool could not "
             "grow their lane mid-decode (finish_reason "
             "pages_exhausted).", lambda: self.n_page_preempts),
            ("serving_decode_spec_rounds_total",
             "Speculative rounds executed (one draft propose + one "
             "target verify each).", lambda: self.n_spec_rounds),
            ("serving_decode_spec_proposed_total",
             "Draft tokens proposed to the verifier.",
             lambda: self.n_spec_proposed),
            ("serving_decode_spec_accepted_total",
             "Draft tokens the target accepted (acceptance rate = "
             "accepted / proposed).", lambda: self.n_spec_accepted),
        ):
            m.counter(name, help_).set_function(fn)
        starved = m.counter(
            "serving_decode_device_starved_seconds_total",
            "Seconds the decode loop left the device with nothing of "
            "its own queued, by the phase it spent them in: the rate "
            "is the share of wall time in which the host is the "
            "ceiling.", labels=("phase",))
        for phase in STARVED_PHASES:
            starved.labels(phase).set_function(
                lambda phase=phase: self.starved_ns[phase] / 1e9)
        m.gauge("serving_decode_pages_free",
                "Free KV-cache pages in the shared pool."
                ).set_function(lambda: self.pages.n_free)
        m.gauge("serving_decode_pages_in_use",
                "KV-cache pages currently held by live slots "
                "(prefix-cache residents are NOT in use — see "
                "serving_decode_pages_cached).").set_function(
            self._pages_in_use)
        m.gauge("serving_decode_page_high_water",
                "Most pages ever simultaneously claimed."
                ).set_function(lambda: self.pages.high_water)
        if self.prefix is not None:
            m.gauge("serving_decode_pages_cached",
                    "KV-cache pages resident in the prefix-cache "
                    "radix index (held by the index; refcount 1 = "
                    "evictable).").set_function(
                lambda: self.prefix.n_cached)
            lk = m.counter(
                "serving_decode_prefix_lookups_total",
                "Prefix-cache radix lookups at admission, by result.",
                labels=("result",))
            lk.labels("hit").set_function(lambda: self.prefix.n_hits)
            lk.labels("miss").set_function(
                lambda: self.prefix.miss_count())
            m.counter("serving_decode_prefix_hit_tokens_total",
                      "Prompt tokens served from cached prefix pages "
                      "instead of recomputed at prefill."
                      ).set_function(lambda: self.prefix.n_hit_tokens)
            m.counter("serving_decode_prefix_evicted_pages_total",
                      "Cached pages reclaimed by LRU eviction under "
                      "pool pressure.").set_function(
                lambda: self.prefix.n_evicted)
        self._m_prefill = m.histogram(
            "serving_prefill_latency_ms",
            "Prompt prefill wall-clock per prompt bucket.",
            labels=("bucket",))
        self._m_step = m.histogram(
            "serving_decode_step_latency_ms",
            "Single-token decode step wall-clock (all slots at once).")
        self._m_spec_round = m.histogram(
            "serving_decode_spec_round_latency_ms",
            "Speculative round wall-clock (draft propose + target "
            "verify + host acceptance, all slots at once).")
        # billing-grade device-time attribution: the same family the
        # server's dispatch stage charges (get-or-create — one counter
        # per registry). Steps/spec rounds run ALL active slots at
        # once, so their wall time is pro-rated equally across the
        # tenants riding those slots; prefill is per-request and
        # charges whole.
        self._m_device = m.counter(
            "serving_tenant_device_ms_total",
            "Device wall-clock milliseconds attributed to each tenant: "
            "batch dispatch pro-rated by rows, decode steps pro-rated "
            "by active slots, prefill charged to its request.",
            labels=("tenant",))
        self._m_queue_wait = m.histogram(
            "serving_decode_queue_wait_ms",
            "Submit -> slot-claim wait per decode request.")
        # token-level decode timelines (ISSUE 18): observed once per
        # request at _finish — EVERY release reason, not just clean EOS
        self._m_ttft = m.histogram(
            "serving_decode_ttft_ms",
            "Time-to-first-token: admit -> first emitted token "
            "(socket-edge stamp for streamed replies).",
            labels=("route", "tenant"))
        self._m_tpot = m.histogram(
            "serving_decode_tpot_ms",
            "Time-per-output-token: mean inter-token gap after the "
            "first.", labels=("route", "tenant"))
        self._m_tokens_req = m.histogram(
            "serving_decode_tokens_per_request",
            "Tokens delivered per request, by finish reason.",
            labels=("reason",), buckets=TOKENS_PER_REQUEST_BUCKETS)
        m.counter("serving_decode_goodput_tokens_total",
                  "Tokens delivered by clean finishes (eos/length) — "
                  "the goodput numerator; serving_decode_tokens_total "
                  "is the all-reasons denominator."
                  ).set_function(lambda: self.n_goodput_tokens)
        m.gauge("serving_decode_kv_pool_bytes",
                "Live bytes held by the paged KV pool."
                ).set_function(self._cache_bytes)
        if self.prefix is not None:
            m.gauge("serving_decode_prefix_cache_bytes",
                    "Bytes held by prefix-cache resident pages."
                    ).set_function(
                lambda: self._cache_bytes()
                * self.prefix.n_cached // max(self.pages.n_pages, 1))

    def _pages_in_use(self) -> int:
        """Pages live requests hold: claimable less free less the
        prefix cache's residents (shared prefix pages count once)."""
        return (self.pages.n_pages - 1 - self.pages.n_free
                - (self.prefix.n_cached if self.prefix is not None
                   else 0))

    def _cache_bytes(self) -> int:
        """Exposition-time view: bytes of the decoder's KV tree."""
        try:
            from mmlspark_tpu.parallel.dist import tree_bytes
            return int(tree_bytes(self.decoder.cache))
        except Exception:  # noqa: BLE001 — a view must never raise
            return 0

    def _timeline_labels(self, req: _DecodeRequest
                         ) -> "tuple[str, str]":
        """``(route, tenant)`` labels for the timeline histograms.
        Route is the server's decode path; the tenant label rides the
        tenancy registry's BoundedLabelSet so an unbounded tenant
        population collapses into 'other' instead of minting children
        without bound."""
        route = "decode"
        tenant = ANONYMOUS_ID
        srv = self._server
        if srv is not None:
            route = getattr(srv, "decode_path", None) or route
            ten = getattr(srv, "tenancy", None)
            tid = getattr(req.pending, "tenant", None)
            if ten is not None and tid:
                tenant = ten.label_of(tid)
        return route, tenant

    def _charge_device_ms(self, total_ms: float,
                          reqs: "Iterable[_DecodeRequest]") -> None:
        """Pro-rate one step/round/prefill's device wall-clock equally
        across the tenants whose requests rode it (each active slot
        advances one token per step — equal shares are the honest
        split). One counter inc per distinct tenant per step; tenant
        labels ride the tenancy registry's BoundedLabelSet via
        :meth:`_timeline_labels`."""
        if self._m_device is None or total_ms <= 0:
            return
        counts: "dict[str, int]" = {}
        n = 0
        for req in reqs:
            _, tenant = self._timeline_labels(req)
            counts[tenant] = counts.get(tenant, 0) + 1
            n += 1
        if not n:
            return
        share = total_ms / n
        for tenant, k in counts.items():
            self._m_device.labels(tenant).inc(share * k)

    # -- admission (any thread) ----------------------------------------------

    def overloaded(self) -> bool:
        return len(self._waiting) >= self.max_waiting

    def queue_pressure(self) -> "tuple[int, int]":
        """``(depth, capacity)`` of the waiting queue — the pressure
        signal priority-aware shedding evaluates."""
        return len(self._waiting), self.max_waiting

    def retry_after_hint(self) -> Optional[float]:
        """Honest decode-429 ``Retry-After`` from the slot-release
        EWMA scaled by the queue ahead; ``None`` while the EWMA is
        cold or stale (caller falls back to the constant)."""
        return self.release_ewma.retry_after(len(self._waiting))

    def _bucket_of(self, n: int) -> int:
        """The shape a prefill of ``n`` tokens runs in, for the span
        and the histogram's label: the decoder's ladder is its one
        owner; a decoder with no ladder over whole prompts (it walks
        them in tiles or windows) is labelled by the power of two."""
        of = getattr(self.decoder, "bucket_of", None)
        return (of(n) if of is not None
                else bucket_target(n, self.decoder.max_len))

    def parse(self, payload: Any
              ) -> "tuple[np.ndarray, int, Optional[Sampler], Optional[bool]]":
        """Payload -> (prompt tokens, max_new, sampler, speculative).
        Raises ValueError on anything the decode plane cannot serve
        (the caller 400s)."""
        if not isinstance(payload, dict):
            raise ValueError("decode payload must be a JSON object")
        prompt = payload.get("prompt")
        if not isinstance(prompt, list) or not prompt or \
                not all(isinstance(t, int) and not isinstance(t, bool)
                        and 0 <= t for t in prompt):
            # bool is an int subclass: [true, false] must 400, not
            # silently decode as tokens [1, 0]
            raise ValueError(
                'decode payload needs "prompt": [token ids] '
                '(non-empty list of non-negative ints)')
        if any(t >= self.decoder.cfg.vocab for t in prompt):
            raise ValueError(
                f"prompt token out of range (vocab "
                f"{self.decoder.cfg.vocab})")
        if len(prompt) > self.max_prompt:
            raise ValueError(
                f"prompt length {len(prompt)} > {self.max_prompt}, the "
                f"longest this decoder prefills (max_len "
                f"{self.decoder.max_len}, a row left to generate)")
        max_new = payload.get("max_new_tokens",
                              self.max_new_tokens_default)
        if not isinstance(max_new, int) or isinstance(max_new, bool) \
                or max_new < 1:
            raise ValueError('"max_new_tokens" must be a positive int')
        # the cache lane bounds the sequence: clamp the budget to it
        max_new = min(max_new, self.decoder.max_len - len(prompt))
        spec = payload.get("speculative")
        if spec is not None and not isinstance(spec, bool):
            raise ValueError('"speculative" must be a boolean')
        stream = payload.get("stream")
        if stream is not None and not isinstance(stream, bool):
            raise ValueError('"stream" must be a boolean')
        return np.asarray(prompt, np.int32), max_new, \
            self._parse_sampling(payload), spec

    @staticmethod
    def _parse_sampling(payload: dict) -> Optional[Sampler]:
        """Request-selectable sampling: ``temperature`` (> 0 turns
        sampling on; 0/absent = greedy, the default), ``top_k``,
        ``top_p``, ``seed``. Bad values 400 like any other payload
        error."""
        temp = payload.get("temperature", 0)
        if isinstance(temp, bool) or not isinstance(temp, (int, float)) \
                or not np.isfinite(temp) or temp < 0:
            raise ValueError(
                '"temperature" must be a finite number >= 0 '
                '(0 = greedy)')
        top_k = payload.get("top_k", 0)
        if isinstance(top_k, bool) or not isinstance(top_k, int) \
                or top_k < 0:
            raise ValueError('"top_k" must be an int >= 0 (0 = off)')
        top_p = payload.get("top_p", 1.0)
        if isinstance(top_p, bool) or not isinstance(top_p, (int, float)) \
                or not 0.0 < float(top_p) <= 1.0:
            raise ValueError('"top_p" must be in (0, 1]')
        seed = payload.get("seed")
        if seed is not None and (isinstance(seed, bool)
                                 or not isinstance(seed, int)):
            raise ValueError('"seed" must be an int')
        if float(temp) == 0.0:
            if "temperature" not in payload and \
                    (int(top_k) > 0 or float(top_p) < 1.0):
                # EFFECTIVE knobs with temperature ABSENT: serve them
                # at temperature 1 rather than silently decoding
                # greedy. An EXPLICIT "temperature": 0 always wins —
                # 0 is documented as greedy, and overriding it to
                # unseeded T=1 sampling would hand the client exactly
                # the nondeterminism it asked to avoid. No-op values
                # (top_k: 0, top_p: 1.0 — both documented "off") stay
                # greedy either way.
                return Sampler(1.0, int(top_k), float(top_p), seed)
            return None
        return Sampler(float(temp), int(top_k), float(top_p), seed)

    def _claim_pages(self, n: int) -> Optional[List[int]]:
        """Claim ``n`` fresh pages, evicting LRU unreferenced cached
        pages first when the free list alone cannot cover it."""
        got = self.pages.claim(n)
        if got is None and self.prefix is not None:
            self.prefix.evict_for(n)
            got = self.pages.claim(n)
        return got

    def _release_pages(self, req: _DecodeRequest,
                       publish: bool) -> None:
        """Drop the request's page references. On a clean finish the
        prompt-complete pages are PUBLISHED into the prefix index
        (their reference transfers to the cache — a future prompt
        sharing the prefix attaches them instead of recomputing);
        everything else — shared-prefix refs, the partial prompt tail,
        generated-token pages — is released. Publication is refused
        for ``error`` finishes: a faulted step's cache state is
        suspect, and poisoning the index would wrong every future
        match."""
        pages, req.pages = req.pages, []
        if req.sum_pages:
            self.pages.release(req.sum_pages)
            req.sum_pages = []
        absorbed = set()
        if self.prefix is not None and publish:
            absorbed = self.prefix.publish(
                req.prompt, pages,
                tenant=getattr(req.pending, "tenant", None))
        rest = [p for p in pages if p not in absorbed]
        if rest:
            self.pages.release(rest)

    def _spec_capable(self, req: _DecodeRequest) -> bool:
        """Whether this request may EVER enter a speculative cohort:
        explicit payload opt-in/out wins; greedy defaults on, sampled
        defaults off (rejection sampling changes seeded-PRNG
        consumption). Fixed for the request's lifetime — it decides
        the draft prefill at admission and the draft-cache catch-up
        obligation on non-speculative rounds."""
        if not self.decoder.has_draft:
            return False
        return (req.spec if req.spec is not None
                else req.sampler is None)

    def submit(self, pending, parsed=None) -> None:
        """Enqueue one admitted request (already past the server's
        replay/join/shed/doa checks). Raises ValueError on a bad
        payload (caller replies 400), DecodeOverloaded when the
        waiting queue is full OR the page pool cannot hold the prompt
        (caller replies 429 + Retry-After — page exhaustion is
        backpressure, never a mid-decode OOM). ``parsed`` lets a
        caller that already validated the payload (the streaming
        pre-check) pass its :meth:`parse` tuple instead of paying a
        second pass."""
        prompt, max_new, sampler, spec = (
            parsed if parsed is not None else self.parse(
                pending.payload))
        req = _DecodeRequest(pending, prompt, max_new, sampler, spec)
        req.t_submit = self.clock.now()
        # admission-time page check: the prompt (plus the first
        # generated row) must fit the pool outright. Advisory —
        # running slots may grow before this request reaches a
        # slot, and _admit_waiting re-checks — but it turns a
        # full pool into an honest 429 instead of a queued
        # request that can never start.
        need = sum(self.decoder.prefill_pages(len(prompt)))
        # cache-full admission sheds BEFORE touching shared state:
        # cached pages count as reclaimable headroom (eviction
        # frees them at claim time), but no lookup, ref, or
        # eviction happens for a request that only sheds.
        # n_cached is the O(1) UPPER bound (pinned cached pages
        # are not really evictable) — an optimistic admit just
        # waits head-of-line like any page-tight request, which
        # this check is already advisory about.
        avail = self.pages.n_free + (
            self.prefix.n_cached if self.prefix is not None
            else 0)
        if avail < need:
            raise DecodeOverloaded(
                f"decode page pool exhausted ({need} pages "
                f"needed, {avail} free or evictable)")
        with self._lock:
            if len(self._waiting) >= self.max_waiting:
                raise DecodeOverloaded("decode waiting queue full")
            self._waiting.append(req)
            self._by_rid[pending.rid] = req
            self.n_requests += 1
        self._work.set()

    def cancel(self, rid: str) -> bool:
        """Flag a waiting or in-slot request cancelled; it resolves
        (partial tokens, ``finish_reason: "cancelled"``) and frees its
        slot at the next loop pass. Returns False for unknown rids."""
        with self._lock:
            req = self._by_rid.get(rid)
            if req is None:
                return False
            req.cancelled = True
        self._work.set()
        return True

    # -- resolution ----------------------------------------------------------

    @staticmethod
    def _standalone_commit(p) -> None:
        p.event.set()
        for cb in p.callbacks:
            try:
                cb(p)
            except Exception:  # noqa: BLE001 — mirror server._release
                logger.warning("reply callback failed", exc_info=True)

    def _now(self) -> float:
        return (self.tracer.clock.now() if self.tracer is not None
                else self.clock.now())

    def _add_span(self, req: _DecodeRequest, name: str, t0: float,
                  t1: float, status: str = "ok", **attrs) -> None:
        if self.tracer is not None and req.pending.span is not None:
            self.tracer.add(name, t0, t1, parent=req.pending.span,
                            status=status, **attrs)

    def _finish(self, req: _DecodeRequest, reason: str,
                status: int = 200,
                error: Optional[str] = None) -> None:
        """Resolve a request and free whatever it held — slot AND
        pages; EVERY exit path funnels here, so neither can leak."""
        if req.slot is not None:
            with self._lock:
                # under the lock so stats() can snapshot _active
                # against the loop thread's churn
                self._active.pop(req.slot, None)
            self._tokens[req.slot] = 0
            self._pos[req.slot] = 0
            self._tables[req.slot, :] = 0
            self.pool.release(req.slot)
            self.release_ewma.note()
            t1 = self._now()
            self._add_span(req, "decode", req.t_decode, t1,
                           status="ok" if status == 200 else "error",
                           slot=req.slot, n_tokens=len(req.produced),
                           finish_reason=reason)
            req.slot = None
        if req.pages or req.sum_pages:
            # a step in flight may still write this request's next row
            # into one of these pages: whoever claims them next is
            # dispatched behind that step (:meth:`_emit_step`), and a
            # published page is a full prompt page, which no step writes
            self._release_pages(req, publish=reason != "error")
        with self._lock:
            self._by_rid.pop(req.pending.rid, None)
            self.releases[reason] = self.releases.get(reason, 0) + 1
        p = req.pending
        # token-level timeline: EVERY release reason lands in the
        # histograms — cancel/deadline/preempt/fault partial counts
        # included, so goodput can never undercount failure modes
        n = len(req.produced)
        clean = reason in ("eos", "length")
        if clean:
            self.n_goodput_tokens += n
        if self._m_tokens_req is not None:
            self._m_tokens_req.labels(reason).observe(float(n))
        if n > 0 and req.t_first > 0.0 and self._m_ttft is not None:
            route, tenant = self._timeline_labels(req)
            t_first = req.t_first
            # streamed replies prefer the SOCKET-EDGE stamp (first
            # chunk actually written to the client) — comparable to
            # t_submit only on the real monotonic clock
            s_edge = getattr(req.stream, "t_first", 0.0) or 0.0
            if s_edge > 0.0 and self.clock is SYSTEM_CLOCK:
                t_first = s_edge
            self._m_ttft.labels(route, tenant).observe(
                max(t_first - req.t_submit, 0.0) * 1000.0)
            if n >= 2 and req.t_last >= req.t_first:
                self._m_tpot.labels(route, tenant).observe(
                    (req.t_last - req.t_first) / (n - 1) * 1000.0)
        # emitted tokens billed to the owning tenant exactly once, at
        # resolution (partial emissions from preempts/faults included)
        tid = getattr(p, "tenant", None)
        if tid and req.produced and self._server is not None \
                and getattr(self._server, "tenancy", None) is not None:
            self._server.tenancy.note_tokens(tid, n)
            if clean:
                self._server.tenancy.note_goodput_tokens(tid, n)
        if status == 200:
            p.status = 200
            body = {"tokens": req.produced,
                    "n_tokens": len(req.produced),
                    "prompt_len": int(len(req.prompt)),
                    "finish_reason": reason}
            p.reply = json.dumps(body).encode()
        else:
            p.status = status
            body = {"error": error or reason,
                    "tokens": req.produced,
                    "n_tokens": len(req.produced),
                    "finish_reason": reason}
            p.reply = json.dumps(body).encode()
        stream = req.stream
        if stream is not None and not stream.closed:
            # the terminal SSE event mirrors the JSON reply (plus the
            # done marker) and ends the chunked body; the connection
            # returns to keep-alive. The journal still gets the plain
            # reply — a replayed rid is served non-streamed.
            stream.finish(b"data: " + json.dumps(
                dict(body, done=True)).encode() + b"\n\n")
        self._commit(p)

    # -- the loop ------------------------------------------------------------

    def start(self) -> "DecodeScheduler":
        if self._thread is None or not self._thread.is_alive():
            self._stop.clear()
            self._thread = threading.Thread(target=self._loop,
                                            daemon=True,
                                            name="decode-scheduler")
            self._thread.start()
        return self

    def stop(self, timeout: float = 5.0) -> None:
        self._stop.set()
        self._work.set()
        if self._thread is not None:
            self._thread.join(timeout=timeout)
            if self._thread.is_alive():
                # the loop is stuck inside a prefill/step (hung device,
                # first-compile of a big model): finishing its in-slot
                # requests from HERE would race its own retirement path
                # — double slot releases, double commits. Leave them to
                # the daemon thread; stranded clients 504 at
                # request_timeout (the server stop() idiom).
                logger.warning(
                    "decode loop did not stop in %.1fs; leaving "
                    "in-flight slots to it", timeout)
                return
        # the loop is dead: resolve stragglers so no client hangs
        with self._lock:
            waiting = list(self._waiting)
            self._waiting.clear()
        for req in waiting:
            self._finish(req, "error", status=503,
                         error="decode scheduler stopping")
        for req in list(self._active.values()):
            self._finish(req, "error", status=503,
                         error="decode scheduler stopping")

    def _loop(self) -> None:
        """Every pass is a chain of ``decode.*`` spans (admit [with a
        prefill child per request admitted], then prepare, dispatch,
        fetch, emit, or idle), owned here and recorded once, as one
        ``decode.pass`` span, when the pass ends. Which step the
        pass's fetch waits for (the one it dispatched, or the one a
        pass before did, with its own left in flight) is
        :meth:`_run_step`'s to say, from what the slots hold."""
        with collect() as self._pass:
            # (this thread's CPU clock, not the constructor's)
            self._cpu_ns = (time.thread_time_ns(), time.process_time_ns())
            while not self._stop.is_set():
                with span("decode.admit") as sp:
                    # dead waiters resolve EVERY pass, slots full or
                    # not: with every slot pinned by long decodes, a
                    # cancelled/expired waiter must still get its
                    # prompt reply (and stop counting toward
                    # overloaded()) instead of rotting until the
                    # frontend's request_timeout
                    self._reap_waiting()
                    sp.attrs = {"admitted": self._admit_waiting()}
                if self._active or self._flight is not None:
                    self._run_step()
                else:
                    # fully idle (nothing waiting either) -> block
                    # until submit()/cancel()/stop() wakes us, no 50 Hz
                    # poll; with waiters held back by deadline-less
                    # slots the short timeout keeps their deadlines
                    # honest
                    with span("decode.idle"):
                        self._work.wait(self.idle_wait_s
                                        if self._waiting else None)
                    self._work.clear()
                self._record_pass(self._pass[:])
                self._pass.clear()
        self._flight = None     # a stopped loop leaves nothing queued

    def _record_pass(self, phases: list) -> None:
        """One finished pass: its phases into the cumulative ``loop``
        counters, and the pass itself ONCE into the tracer's ring as a
        root ``decode.pass`` span under a trace id of its own: start,
        end, the step's sequence number and ``phases``, the spans as
        they closed (:func:`pass_view` reads them); the sequence number
        counts the steps dispatched so far, the one in flight with
        them. This runs between two steps, and with no step in flight
        the device waits for it: it does the least it can. A
        pass that ran a step or a prefill and lasted over
        ``SLOW_PASS_MULTIPLE`` times the running median of the passes
        that ran a step is retained under route ``decode.loop`` like
        any slow request, with its view spelled out beside the
        phases; a pass that only waited for work is no stall.

        **The starved account**: the time the loop left the device
        with nothing of its own queued, by the phase it spent it in
        (``STARVED_PHASES``), on the pass as ``starved_ms`` and summed
        in ``starved_ns``. One bit is carried from pass to pass, "a
        step of this loop is in flight": a ``decode.dispatch`` sets it
        at its end, the ``decode.fetch`` of step k clears it unless a
        step after k went out, and so does a ``decode.prefill`` (it
        waits for its token, behind whatever was queued) and a
        ``decode.idle``. Walking the phases as they closed, every
        ``admit`` (less its ``prefill`` children), ``prepare``,
        ``dispatch`` and ``emit`` that ran with the bit clear is
        starved time. A ``prefill``, a ``compact`` and a speculative
        round are device work of another program and count as busy;
        ``idle`` (no live slot) is no starvation. This is the host's
        account, not the device's: it leaves out the copy back at the
        tail of a ``decode.fetch`` (the device is idle there if nothing
        is queued) and counts the tail of a ``decode.dispatch`` after
        the program was queued, as well as the turn after a
        ``decode.compact``, which only queues its program; it cannot
        see the device's gaps INSIDE a prefill walk.

        **Who ran**: ``cpu_ms`` and ``proc_cpu_ms`` are the loop
        thread's and the process's CPU time since the pass before was
        recorded (two clock reads a pass); a retained pass says from
        them whether it was ``on_cpu``, ``contended`` or ``blocked``
        (:func:`stall_word`)."""
        loop = self.loop
        t0 = t1 = 0
        worked = stepped = other_program = False
        riders = ()
        # the bit as the pass found it (an admit closes after its
        # prefills) and as the walk carries it
        found = in_flight = self._in_flight
        seq_out = self._seq_out
        pf_ns = pf_end = 0
        starved: Dict[str, int] = {}
        for name, a, b, attrs in phases:
            acc = loop[name]
            acc[0] += 1
            acc[1] += b - a
            if name == "decode.admit":
                t0 = a           # the first span opened, whatever
                if not found:                     # closed before it
                    starved["admit"] = b - a - pf_ns
                elif pf_end:
                    starved["admit"] = b - pf_end
            elif name == "decode.prepare":
                riders = attrs["traces"]
                other_program = attrs.get("order") == "spec_round"
                if not in_flight:
                    starved["prepare"] = b - a
            elif name == "decode.dispatch":
                stepped = True
                if not (in_flight or other_program):
                    starved["dispatch"] = \
                        starved.get("dispatch", 0) + b - a
                in_flight = True
                seq_out = attrs["seq"] if attrs else 0
            elif name == "decode.fetch":
                worked = True    # a pass that only fetched waited too
                in_flight = seq_out > attrs["fetched"] if attrs else False
            elif name == "decode.emit":
                if not (in_flight or other_program):
                    starved["emit"] = starved.get("emit", 0) + b - a
            elif name == "decode.prefill":
                worked = True
                pf_ns += b - a
                pf_end = b
                in_flight = False
            elif name == "decode.idle":
                in_flight = False
            t1 = b
        self._in_flight = in_flight
        self._seq_out = seq_out
        if starved:
            total = self.starved_ns
            for phase, ns in starved.items():
                total[phase] += ns
                starved[phase] = ns * 1e-6
        if self.tracer is None:
            return
        cpu, proc = time.thread_time_ns(), time.process_time_ns()
        cpu0, proc0 = self._cpu_ns
        self._cpu_ns = cpu, proc
        ns = t1 - t0
        if stepped:
            self._pass_ns.append(ns)
            if loop["decode.prepare"][0] % 32 == 0:
                self._slow_ns = SLOW_PASS_MULTIPLE * sorted(
                    self._pass_ns)[len(self._pass_ns) // 2]
                self.tracer.set_threshold(LOOP_ROUTE,
                                          self._slow_ns * 1e-6)
        # until the median is known the tracer's own default decides
        slow = (stepped or worked) and (self._slow_ns is None
                                        or ns >= self._slow_ns)
        self.tracer.add(
            "decode.pass", t0 * 1e-9, t1 * 1e-9, None, capture=slow,
            route=LOOP_ROUTE, step=(
                self.n_steps + (self._flight is not None)
                if stepped else None),
            traces=riders, phases=phases,
            starved_ms=starved, cpu_ms=(cpu - cpu0) * 1e-6,
            proc_cpu_ms=(proc - proc0) * 1e-6,
            **(dict(pass_view(phases),
                    stall=stall_word(ns, cpu - cpu0, proc - proc0))
               if slow else {}))

    def _reap_waiting(self) -> None:
        with self._lock:
            if not self._waiting:
                return
            keep, dead = deque(), []
            for req in self._waiting:
                p = req.pending
                s = req.stream
                if req.cancelled or (p.deadline is not None
                                     and p.deadline.expired) \
                        or (s is not None and s.closed):
                    dead.append(req)
                else:
                    keep.append(req)
            self._waiting = keep
        for req in dead:
            if req.cancelled:
                self._finish(req, "cancelled")
            elif req.stream is not None and req.stream.closed:
                # the streaming client hung up before a slot was
                # claimed: never journaled (status != 200) so a retry
                # re-executes
                self._finish(req, "disconnected", status=500,
                             error="client disconnected")
            else:
                self._finish(req, "deadline", status=504,
                             error="deadline exceeded before decode")

    def _pop_waiting(self) -> Optional[_DecodeRequest]:
        """Next waiter to try for a slot. FIFO without tenancy; with
        fair-share on, a deficit-weighted round-robin across the
        tenants PRESENT in the queue picks whose oldest request goes
        next — a 10:1 flood from one tenant still leaves the victim
        claiming slots at its weighted share (the bounded-starvation
        guarantee lives in :class:`~mmlspark_tpu.serving.tenancy.
        FairCycle`)."""
        with self._lock:
            if not self._waiting:
                return None
            ten = (getattr(self._server, "tenancy", None)
                   if self._server is not None else None)
            if ten is None or not ten.fair_share \
                    or len(self._waiting) == 1:
                return self._waiting.popleft()
            present: Dict[str, float] = {}
            for r in self._waiting:
                tid = getattr(r.pending, "tenant", None) or ANONYMOUS_ID
                if tid not in present:
                    present[tid] = ten.weight_of(tid)
            if len(present) == 1:
                return self._waiting.popleft()
            pick = self._fair.choose(present)
            for i, r in enumerate(self._waiting):
                if (getattr(r.pending, "tenant", None)
                        or ANONYMOUS_ID) == pick:
                    del self._waiting[i]
                    return r
            return self._waiting.popleft()

    def _admit_waiting(self) -> int:
        """Between steps: claim free slots (and the prompt's pages)
        for waiting requests — one prefill each, under a
        ``decode.prefill`` span whose two ends are the request's
        ``prefill`` span, ``prefill_s`` and the prefill histogram.
        Cancelled/expired/disconnected waiters resolve WITHOUT ever
        claiming anything; a head-of-queue request the page pool
        cannot hold yet WAITS (admission order preserved — pages free
        as running requests finish). Returns the requests admitted."""
        admitted = 0
        while self.pool.n_free > 0:
            req = self._pop_waiting()
            if req is None:
                return admitted
            p = req.pending
            if req.cancelled:
                self._finish(req, "cancelled")
                continue
            if p.deadline is not None and p.deadline.expired:
                self._finish(req, "deadline", status=504,
                             error="deadline exceeded before decode")
                continue
            s = req.stream
            if s is not None and s.closed:
                self._finish(req, "disconnected", status=500,
                             error="client disconnected")
                continue
            hit_len = 0
            shared: List[int] = []
            if self.prefix is not None:
                # longest cached prefix: matched pages arrive
                # ref'd — on any bail-out below they are released
                # (the cache keeps its own reference)
                hit_len, shared = self.prefix.lookup(req.prompt)
            # rows as the decoder counts them: the summary pages
            # the prompt leaves, then the most window pages the
            # prefill holds at once
            n_sum, n_win = self.decoder.prefill_pages(len(req.prompt))
            own = self._claim_pages(n_sum + n_win - len(shared))
            if own is None:
                # not enough pages YET: head-of-line waits for
                # running requests to release theirs (it looks up
                # afresh next pass — the hit ledger only counts
                # ADMITTED requests, so retry ticks cost nothing)
                if shared:
                    self.pages.release(shared)
                with self._lock:
                    self._waiting.appendleft(req)
                return admitted
            pages = shared + own
            slot = self.pool.claim()
            if slot is None:      # raced a concurrent release? retry
                self.pages.release(pages)
                with self._lock:
                    self._waiting.appendleft(req)
                return admitted
            if self.prefix is not None:
                # one monotonic hit-ledger bump per ADMITTED request
                self.prefix.count(hit_len)
            self._tables[slot] = self.decoder.lane(pages[:n_sum],
                                                   pages[n_sum:])
            table = self._tables[slot]
            sp = span("decode.prefill",
                      prompt_len=len(req.prompt), prefix_hit=hit_len,
                      slot=slot, others_active=len(self._active),
                      trace=getattr(p, "trace", None))
            try:
                with sp:
                    # what the decoder says of this prefill is its own
                    # code: a refusal there fails this request, below,
                    # and not the loop
                    bucket = self._bucket_of(len(req.prompt) - hit_len)
                    sp.attrs.update(
                        self.decoder.prefill_facts(len(req.prompt)),
                        bucket=bucket)
                    sp.attrs["queue_wait_ms"] = (
                        sp.t0 * 1e-9 - req.t_submit) * 1e3
                    if self.fault_plan is not None:
                        self.fault_plan.raise_at("decode_prefill",
                                                 clock=self.clock)
                    if hit_len > 0:
                        first, last_logits = \
                            self.decoder.prefill_prefix_logits(
                                slot, req.prompt, hit_len, table,
                                draft=self._spec_capable(req))
                    else:
                        first, last_logits = \
                            self.decoder.prefill_logits(
                                slot, req.prompt, table,
                                draft=self._spec_capable(req))
                    if req.sampler is not None:
                        # the request's own seeded PRNG picks the first
                        # generated token from the prompt's last logits
                        first = req.sampler.sample(
                            np.asarray(last_logits))
            except Exception as e:  # noqa: BLE001 — injected or real
                self.pool.release(slot)
                self.pages.release(pages)
                self._tables[slot, :] = 0
                self._add_span(req, "queue_wait", req.t_submit,
                               sp.t0 * 1e-9)
                self._add_span(req, "prefill", sp.t0 * 1e-9,
                               sp.t1 * 1e-9, status="error")
                self._finish(req, "error", status=500,
                             error=f"prefill failed: {e}")
                continue
            # the span's two ends are the only clock reads of a prefill
            t0, t1 = sp.t0 * 1e-9, sp.t1 * 1e-9
            self._add_span(req, "queue_wait", req.t_submit, t0)
            if self._m_queue_wait is not None:
                self._m_queue_wait.labels().observe(
                    (t0 - req.t_submit) * 1000.0)
            req.t_prefill = t1
            req.t_decode = t1
            admitted += 1
            self.n_prefills += 1
            self.n_prompt_tokens += len(req.prompt)
            self.prefill_s += t1 - t0
            if self._m_prefill is not None:
                self._m_prefill.labels(
                    self._bucket_of(len(req.prompt))).observe(
                    (t1 - t0) * 1000.0)
            # prefill runs ONE request: its whole wall time is that
            # request's tenant's device time
            self._charge_device_ms((t1 - t0) * 1000.0, (req,))
            self._add_span(req, "prefill", t0, t1, slot=slot,
                           prompt_len=len(req.prompt),
                           prefix_hit=hit_len)
            req.slot = slot
            if pages:
                # windows a prefill walked are compacted by now: what
                # stays claimed is what the first step needs (all of
                # it, where rows are one a position)
                keep = n_sum + self.decoder.pages_for(len(req.prompt))[1]
                if len(pages) > keep:
                    self.pages.release(pages[keep:])
                req.sum_pages, pages = pages[:n_sum], pages[n_sum:keep]
                self._tables[slot] = self.decoder.lane(req.sum_pages, pages)
            req.pages = pages
            req.hit_len = hit_len
            req.produced.append(first)
            self.n_tokens += 1
            # the first token exists HERE (prefill emits it): stamp
            # both timeline marks and drop the instant event on the
            # request's span so /trace/<id> shows the cadence start
            req.t_first = t1
            req.t_last = t1
            if req.n_timeline < _MAX_TIMELINE_SPANS:
                req.n_timeline += 1
                self._add_span(
                    req, "first_token", t1, t1,
                    ttft_ms=round((t1 - req.t_submit) * 1000.0, 3))
            self._tokens[slot] = first
            self._pos[slot] = len(req.prompt)
            with self._lock:
                self._active[slot] = req
                if len(self._active) > self.slots_high_water:
                    self.slots_high_water = len(self._active)
            self._emit_stream(req, [first])
            self._retire_if_done(req, first)
        return admitted

    def _retire_if_done(self, req: _DecodeRequest, tok: int) -> bool:
        """Post-token finish checks, cheapest terminal first."""
        eos = self.decoder.eos_id
        if eos is not None and tok == eos:
            self._finish(req, "eos")
            return True
        if len(req.produced) >= req.max_new:
            self._finish(req, "length")
            return True
        if req.slot is not None and \
                int(self._pos[req.slot]) >= self.decoder.max_len - 1:
            self._finish(req, "length")   # cache lane exhausted
            return True
        if req.cancelled:
            self._finish(req, "cancelled")
            return True
        s = req.stream
        if s is not None and s.closed:
            self._finish(req, "disconnected", status=500,
                         error="client disconnected mid-stream")
            return True
        p = req.pending
        if p.deadline is not None and p.deadline.expired:
            self._finish(req, "deadline", status=504,
                         error="deadline exceeded mid-decode")
            return True
        return False

    def _emit_stream(self, req: _DecodeRequest, toks) -> None:
        """Incremental token delivery for a streaming request: one SSE
        event per emitted token (speculative rounds emit a small
        burst). No-op for non-streamed requests and closed streams."""
        s = req.stream
        if s is None or s.closed:
            return
        base = len(req.produced) - len(toks)
        for off, tok in enumerate(toks):
            s.emit(b'data: {"token": %d, "i": %d}\n\n'
                   % (int(tok), base + off))

    def _ensure_pages(self, req: _DecodeRequest, upto_pos: int) -> bool:
        """Grow ``req``'s page table to cover virtual row
        ``upto_pos``; False when the pool cannot (caller decides:
        preempt for the step's own row, degrade to non-speculative
        for lookahead rows)."""
        need = self.decoder.pages_for(upto_pos)[1]
        have = len(req.pages)
        if need <= have:
            return True
        # growth evicts unreferenced cached pages before giving up:
        # live decodes always outrank cache residency
        got = self._claim_pages(need - have)
        if got is None:
            return False
        req.pages.extend(got)
        self._tables[req.slot] = self.decoder.lane(req.sum_pages, req.pages)
        return True

    def _compact_filled_windows(self, slots) -> None:
        """After a step: a slot of ``slots`` (those the step gave a
        token and that are still taken) whose step wrote its window's
        last row turns that window into summary rows
        (``decoder.compact``: the second kind of row, in pages claimed
        here and kept until the request leaves) and gives the window's
        pages back. A slot that took no part in the step is left alone:
        a request admitted behind a step in flight whose prompt ends on
        a window's edge has that window compacted by its prefill. A
        pool that cannot hold the summaries ends the request like any
        other growth (``pages_exhausted``); a compaction that raises
        ends it like a failed step."""
        window = self.decoder.window
        for slot in slots:
            req = self._active[slot]
            if int(self._pos[slot]) % window:
                continue
            with span("decode.compact", slot=slot,
                      pos=int(self._pos[slot])) as sp:
                new = self._claim_pages(
                    self.decoder.summary_pages_per_window)
                if new is None:
                    self.n_page_preempts += 1
                    self._finish(req, "pages_exhausted")
                    continue
                try:
                    self.decoder.compact(req.pages, new)
                except Exception as e:  # noqa: BLE001 — as a failed step
                    self.pages.release(new)
                    self.n_step_faults += 1
                    logger.warning("compaction failed", exc_info=True)
                    self._finish(req, "error", status=500,
                                 error=f"compaction failed: {e}")
                    continue
                self.pages.release(req.pages)
                req.pages = []
                req.sum_pages.extend(new)
                self._tables[slot] = self.decoder.lane(req.sum_pages, ())
                sp.attrs["pages_returned"] = self.decoder.window_pages

    def _prepare_round(self):
        """Pre-step upkeep: reap dead slots, grow pages for every
        live slot's next row (preempting — finish_reason
        ``pages_exhausted`` — when the pool is dry), and pick the
        speculative cohort (spec-enabled slots whose lookahead window
        fits their lane and the pool). Returns the cohort dict."""
        for req in list(self._active.values()):
            p = req.pending
            s = req.stream
            if req.cancelled:
                self._finish(req, "cancelled")
            elif s is not None and s.closed:
                self._finish(req, "disconnected", status=500,
                             error="client disconnected mid-stream")
            elif p.deadline is not None and p.deadline.expired:
                self._finish(req, "deadline", status=504,
                             error="deadline exceeded mid-decode")
        for slot, req in list(self._active.items()):
            if not self._ensure_pages(req, int(self._pos[slot])):
                # the pool cannot hold this slot's NEXT row: the
                # request ends with its partial output rather
                # than corrupt anyone — never a mid-decode OOM
                self.n_page_preempts += 1
                self._finish(req, "pages_exhausted")
        spec: Dict[int, _DecodeRequest] = {}
        if self.decoder.has_draft:
            if self.spec_policy is not None \
                    and not self.spec_policy.should_speculate():
                # acceptance collapsed below break-even: single steps
                # until a probe round says the workload turned
                # draft-friendly again
                return spec
            k = self.decoder.spec_k
            for slot, req in self._active.items():
                if not self._spec_capable(req):
                    continue
                if int(self._pos[slot]) + k >= self.decoder.max_len:
                    continue          # lane end: single steps finish it
                if not self._ensure_pages(
                        req, int(self._pos[slot]) + k - 1):
                    continue          # pool tight: degrade, not block
                spec[slot] = req
        return spec

    def _live_rows(self, ahead: int = 0) -> "tuple[int, int]":
        """``(summary_rows, window_rows)`` over the live slots, as the
        decoder counts them at each slot's position (``ahead`` rows on,
        for the step behind one in flight): the rows a step reads by
        kind."""
        live = list(self._active)
        if not live:
            return 0, 0
        n_sum, n_win = self.decoder.rows_at(self._pos[live] + ahead)
        return int(np.sum(n_sum)), int(np.sum(n_win))

    def _device_interval(self, i0: int) -> "tuple[float, float]":
        """Seconds (the tracer's clock) from the start of the first to
        the end of the last ``decode.dispatch``/``decode.fetch`` span
        the decoder closed since the pass held ``i0`` phases: a step's
        or a round's wall time, from the spans' own clock reads."""
        done = self._pass[i0:]
        if not done:               # a decoder that opens no span
            now = time.perf_counter()
            return now, now
        return done[0][1] * 1e-9, done[-1][2] * 1e-9

    def _may_run_ahead(self, riders) -> Optional[str]:
        """What holds the step this pass dispatches to today's order
        (a word of :data:`HELD_BY`), or None: the step may still be in
        flight when the pass ends, so that the next pass queues another
        behind it before it fetches (``riders``: the requests by slot
        of the step in flight now, whose tokens the host has not seen,
        or None with nothing in flight). Decided from what the host
        sees before the fetch, and by nothing else; the FIRST condition
        that says no names the pass (``held_by`` on its
        ``decode.prepare``):

        * ``free_slot``: not every slot is taken: a free slot's next
          request would wait for every queued step, not for what is
          left of one;
        * ``sampler`` / ``speculative``: a sampled token is drawn on
          the host from the fetched logits, and a speculative round (or
          the draft's catch-up step) is another program between two
          steps (the riders of a step in flight were greedy as it went
          out, and stay so);
        * ``riders_changed``: the slots no longer hold the requests
          the step in flight carries (one left at the last emit);
        * ``last_token`` / ``lane_full`` / ``cancelled`` /
          ``stream_closed`` / ``deadline``: a slot is known to end with
          the token in flight (its ``max_new``-th, the lane's last row,
          a cancel, a closed stream or a deadline already seen), so
          the pass that frees a slot finds nothing queued behind it;
        * ``window_fill``: the step in flight fills a window (a decoder
          with two kinds of row): its compaction runs between that step
          and the next, so that pass keeps the order fetch, compact,
          dispatch (one pass in a window's length);
        * ``no_pages``: a lane cannot grow to the row the step behind
          the one in flight writes (grown HERE, a row on from
          :meth:`_prepare_round`'s): a slot the pool cannot serve ends
          for want of pages after its token is out, in the next pass's
          upkeep, with nothing queued behind it.

        An ``eos_id`` token cannot be seen before the fetch: that
        request retires at its emit and the step queued behind carries
        one lane more (:meth:`_emit_step` discards it)."""
        if self.pool.n_free or not self._active:
            return "free_slot"
        if riders is None:
            for req in self._active.values():
                if req.sampler is not None:
                    return "sampler"
                if self._spec_capable(req):
                    return "speculative"
            return None
        if riders != self._active:
            return "riders_changed"
        last_row = self.decoder.max_len - 1
        window = self.decoder.window
        for slot, req in riders.items():
            pos = int(self._pos[slot]) + 1
            s, d = req.stream, req.pending.deadline
            if len(req.produced) + 1 >= req.max_new:
                return "last_token"
            if pos >= last_row:
                return "lane_full"
            if window and pos % window == 0:
                return "window_fill"
            if req.cancelled:
                return "cancelled"
            if s is not None and s.closed:
                return "stream_closed"
            if d is not None and d.expired:
                return "deadline"
        for slot, req in riders.items():
            if not self._ensure_pages(req, int(self._pos[slot]) + 1):
                return "no_pages"
        return None

    def _run_step(self) -> None:
        """The pass's step work, ordered by what the slots hold
        (:meth:`_may_run_ahead`), in one of three ways:

        * nothing in flight and the rule false: today's order, prepare,
          dispatch, fetch, emit through ``decoder.step_logits`` (or a
          speculative round);
        * the rule holds: a step is dispatched and LEFT in flight. The
          first such pass ends there; every later one queues its step
          behind the one in flight, on that one's tokens as they lie on
          the device, and only then fetches that one and emits its
          tokens: prepare, dispatch, fetch, emit again, but the fetch
          waits for a step dispatched a pass ago, and the host's turn
          runs beside the device;
        * the rule stops holding with a step in flight: the pass only
          fetches and emits (its ``decode.prepare`` prepares nothing
          and says what the fetched step read)."""
        flight, self._flight = self._flight, None
        in_flight, riders, t_dispatch = flight or (None, None, 0.0)
        with span("decode.prepare") as sp:
            # with a step in flight nobody is reaped or preempted
            # before its token is out: a dead slot retires at its emit
            spec = self._prepare_round() if flight is None else {}
            held = self._may_run_ahead(riders)
            ahead = held is None
            # the rows of the step this pass dispatches: one on, behind
            # a step in flight; a pass that only fetches stamps what
            # the step it fetches read
            n_ahead = 1 if ahead and flight is not None else 0
            if spec:
                order = "spec_round"
            elif ahead:
                order = "ahead" if n_ahead else "start"
            else:
                order = "in_turn" if flight is None else "fetch_only"
            sum_rows, win_rows = self._live_rows(n_ahead)
            sp.attrs = {
                "active": len(self._active),
                "pages_in_use": self._pages_in_use(),
                "n_pages": self.pages.n_pages - 1,
                # slots whose recurrent state this step advances (a
                # state a slot, beside the rows a position)
                "state_slots": (len(self._active)
                                if self.decoder.has_slot_state else 0),
                # the rows this step reads, by kind: positions (a
                # looped stack reads each in ``loops`` passes)
                "window_rows": win_rows, "summary_rows": sum_rows,
                "loops": getattr(self.decoder, "n_loops", 1),
                # the order this pass runs in (PASS_ORDERS); where the
                # rule held it to today's, ``held_by`` says which
                "order": order,
                "traces": [getattr(r.pending, "trace", None)
                           for r in self._active.values()]}
            sp.attrs.update(self.decoder.prepare_facts(
                self._pos[list(self._active)] + n_ahead))
            if held is not None:
                sp.attrs["held_by"] = held
                self.held_by[held] += 1
        if spec:
            self._run_spec_round(spec)
            return
        i0 = len(self._pass)
        if flight is None and not ahead:
            # today's order (``step_logits`` is dispatch and fetch in a
            # row: tests and the benchmark's planted faults wrap it)
            if not self._active:
                return
            reqs = dict(self._active)
            try:
                if self.fault_plan is not None:
                    self.fault_plan.raise_at("decode_step",
                                             clock=self.clock)
                out, logits = self.decoder.step_logits(
                    self._tokens, self._pos, self._tables)
            except Exception as e:  # noqa: BLE001 — injected or real
                self._fail_step(e)
                return
            self._emit_step(out, logits, reqs, *self._device_interval(i0))
            return
        queued = None
        if ahead:
            try:
                if self.fault_plan is not None:
                    self.fault_plan.raise_at("decode_step",
                                             clock=self.clock)
                # copies of the host's arrays: they change under a
                # step that is still in flight
                step = self.decoder.dispatch_step(
                    self._tokens.copy() if flight is None else in_flight,
                    self._pos + n_ahead, self._tables.copy())
            except Exception as e:  # noqa: BLE001 — injected or real
                self._fail_step(e)
                return
            queued = (step, dict(self._active), self._device_interval(i0)[0])
            self.n_steps_ahead += n_ahead
        if flight is not None:
            i0 = len(self._pass)
            try:
                out, logits = self.decoder.fetch_step(in_flight)
            except Exception as e:  # noqa: BLE001 — surfaces at its fetch
                # the step queued behind ran on this one's cache: both
                # are lost, and counted as the one fault they are
                self._fail_step(e)
                return
            # a step queued behind another started when that one ended
            self._emit_step(out, logits, riders,
                            max(t_dispatch, self._t_fetched),
                            self._device_interval(i0)[1])
        self._flight = queued

    def _fail_step(self, e: Exception) -> None:
        """A failed step loses the affected requests (500, never
        journaled — clients may retry) but NEVER a slot or page; a
        step still in flight is dropped with it."""
        self.n_step_faults += 1
        logger.warning("decode step failed; failing %d in-slot "
                       "requests", len(self._active), exc_info=True)
        for req in list(self._active.values()):
            self._finish(req, "error", status=500,
                         error=f"decode step failed: {e}")

    def _emit_step(self, out: np.ndarray, step_logits,
                   reqs: Dict[int, _DecodeRequest],
                   t0: float, t1: float) -> None:
        """A fetched step's tokens to the requests that rode it
        (``reqs``, as they stood at its dispatch), its wall time
        ``t0``-``t1`` (the end of the fetch before, or its own dispatch
        if that came later, to the end of its fetch) to the step
        histogram and the tenants. A request that left while the step
        was in flight (an ``eos_id`` token, a cancel, a closed stream,
        a deadline: nothing the host saw before it queued the step)
        has a lane in it all the same: that token is DISCARDED, never
        streamed, never in ``produced`` or ``n_tokens``. Its row went
        to a page, and with a state a slot its state to a slot, that
        were released when the request left: whatever takes them over
        (a prefill, another slot's growth, a first tile's reset) is
        dispatched behind the step in flight, and so runs behind it on
        the device."""
        self._t_fetched = t1
        self.n_steps += 1
        if self._m_step is not None:
            self._m_step.labels().observe((t1 - t0) * 1000.0)
        self._charge_device_ms((t1 - t0) * 1000.0, reqs.values())
        if self.decoder.has_draft and any(
                self._spec_capable(r) for r in self._active.values()):
            # draft-cache catch-up: a spec-capable slot stepping
            # WITHOUT the draft (policy suppression, page-tight
            # degradation, lane-end neighbours) would leave holes in
            # its draft lane, and a later probe round would propose
            # from garbage — acceptance would never recover. One cheap
            # draft step per plain round (same inputs/positions as the
            # target step) keeps both caches in lockstep; the draft's
            # token outputs are discarded.
            try:
                self.decoder.draft_step_logits(self._tokens, self._pos)
            except Exception:  # noqa: BLE001 — the draft is advisory:
                logger.warning(  # a broken draft must not fail decode
                    "draft catch-up step failed", exc_info=True)
        with span("decode.emit") as sp:
            # one host fetch of the full [n_slots, vocab] logits per
            # step, paid ONLY while a sampling request is in a slot —
            # pure-greedy batches keep the token-only transfer
            logits_np = None
            if any(r.sampler is not None for r in reqs.values()):
                logits_np = np.asarray(step_logits)
            emitted = 0
            for slot, req in reqs.items():
                if self._active.get(slot) is not req:
                    # (the slot may hold its next request by now, which
                    # took no part in this step)
                    self.n_tokens_discarded += 1
                    continue
                tok = (int(out[slot]) if req.sampler is None
                       else req.sampler.sample(logits_np[slot]))
                req.produced.append(tok)
                self.n_tokens += 1
                emitted += 1
                req.t_last = t1      # one store/token: the TPOT stamp
                self._pos[slot] += 1
                self._tokens[slot] = tok
                self._emit_stream(req, [tok])
                self._retire_if_done(req, tok)
            sp.attrs = {"emitted": emitted}
        if self.decoder.window:
            self._compact_filled_windows(
                [slot for slot, req in reqs.items()
                 if self._active.get(slot) is req])

    def _run_spec_round(self, spec: Dict[int, _DecodeRequest]) -> None:
        """One speculative round: draft proposes ``spec_k`` tokens per
        slot, the target verifies them in ONE width-k pass, and each
        speculative slot accepts its longest agreeing prefix (exact
        argmax match for greedy slots, Leviathan rejection sampling
        for sampled opt-ins). Non-speculative slots ride the verify
        and consume only its first position — exactly a single step
        for them (their lookahead writes land on scratch/overwritten
        rows by construction)."""
        k = self.decoder.spec_k
        sampled_spec = [s for s, r in spec.items()
                        if r.sampler is not None]
        i0 = len(self._pass)
        try:
            if self.fault_plan is not None:
                self.fault_plan.raise_at("decode_step",
                                         clock=self.clock)
            if not sampled_spec:
                # the fast path: k chained greedy draft steps in ONE
                # device program — one host round-trip per round
                props = self.decoder.propose(self._tokens, self._pos)
                draft_probs = None
            else:
                # sampled proposals need per-step draft distributions
                # on host: k separate draft steps, each slot drawing
                # from its own transformed draft distribution with
                # its own PRNG
                props = np.zeros((self.decoder.n_slots, k), np.int32)
                draft_probs: Dict[int, list] = {s: [] for s in
                                                sampled_spec}
                cur = self._tokens.copy()
                for j in range(k):
                    nxt, dlogits = self.decoder.draft_step_logits(
                        cur, self._pos + j)
                    dl_np = np.asarray(dlogits)
                    for s in range(self.decoder.n_slots):
                        if s in draft_probs:
                            q = spec[s].sampler.probs(dl_np[s])
                            draft_probs[s].append(q)
                            props[s, j] = spec[s].sampler.draw(q)
                        else:
                            props[s, j] = int(nxt[s])
                    cur = props[:, j].copy()
            ver_in = np.concatenate(
                [self._tokens[:, None], props[:, :k - 1]],
                axis=1).astype(np.int32)
            out_tok, ver_logits, ver_scores = \
                self.decoder.verify_logits(ver_in, self._pos,
                                           self._tables)
        except Exception as e:  # noqa: BLE001 — injected or real
            self.n_step_faults += 1
            logger.warning("speculative round failed; failing %d "
                           "in-slot requests", len(self._active),
                           exc_info=True)
            for req in list(self._active.values()):
                self._finish(req, "error", status=500,
                             error=f"decode step failed: {e}")
            return
        t0, t1 = self._device_interval(i0)
        self.n_spec_rounds += 1
        if self._m_spec_round is not None:
            self._m_spec_round.labels().observe((t1 - t0) * 1000.0)
        self._charge_device_ms((t1 - t0) * 1000.0,
                               self._active.values())
        with span("decode.emit") as sp:
            n_before = self.n_tokens
            logits_np = None
            if any(r.sampler is not None
                   for r in self._active.values()):
                logits_np = np.asarray(ver_logits)
            if spec:
                # per-proposal target log-probs from the verify's
                # fused-CE (or XLA) score head: the acceptance-QUALITY
                # signal — acceptance counts say how often the draft
                # agreed, this says how close the misses were
                sl = sorted(spec)
                mean_logp = float(np.mean(ver_scores[sl]))
                prev = self.spec_proposal_logp
                self.spec_proposal_logp = (
                    mean_logp if prev is None
                    else 0.8 * prev + 0.2 * mean_logp)
            round_proposed = round_accepted = 0
            for slot, req in list(self._active.items()):
                if slot not in spec:
                    # non-speculative rider: position 0 of the verify
                    # IS its single step
                    tok = (int(out_tok[slot, 0]) if req.sampler is None
                           else req.sampler.sample(logits_np[slot, 0]))
                    self._accept_tokens(req, slot, [tok], t_emit=t1)
                    continue
                self.n_spec_proposed += k
                round_proposed += k
                acc_before = round_accepted
                emitted: List[int] = []
                if req.sampler is None:
                    for j in range(k):
                        tgt = int(out_tok[slot, j])
                        emitted.append(tgt)
                        if int(props[slot, j]) != tgt:
                            break
                        self.n_spec_accepted += 1
                        round_accepted += 1
                else:
                    smp = req.sampler
                    for j in range(k):
                        d = int(props[slot, j])
                        p_t = smp.probs(logits_np[slot, j])
                        q_d = draft_probs[slot][j]
                        accept = (q_d[d] > 0.0 and
                                  smp.uniform() <= min(
                                      1.0, float(p_t[d] / q_d[d])))
                        if accept:
                            emitted.append(d)
                            self.n_spec_accepted += 1
                            round_accepted += 1
                            continue
                        resid = np.maximum(p_t - q_d, 0.0)
                        tot = resid.sum()
                        emitted.append(smp.draw(resid / tot) if tot > 0
                                       else smp.draw(p_t))
                        break
                # per-round timeline span: the token cadence a
                # /trace/<id> tree shows (bounded per request — see
                # _MAX_TIMELINE_SPANS)
                if req.n_timeline < _MAX_TIMELINE_SPANS:
                    req.n_timeline += 1
                    self._add_span(req, "spec_round", t0, t1,
                                   proposed=k,
                                   accepted=round_accepted - acc_before,
                                   emitted=len(emitted))
                self._accept_tokens(req, slot, emitted, t_emit=t1)
            if self.spec_policy is not None:
                self.spec_policy.note(round_proposed, round_accepted)
            sp.attrs = {"emitted": self.n_tokens - n_before}

    def _accept_tokens(self, req: _DecodeRequest, slot: int,
                       toks: List[int],
                       t_emit: Optional[float] = None) -> None:
        """Fold a burst of emitted tokens into the slot's state,
        stopping at the first terminal condition (EOS / budget / lane
        end / cancel / deadline) — unconsumed acceptances beyond a
        terminal are dropped, their cache rows repaired by later
        writes like any rejected proposal."""
        if t_emit is not None:
            req.t_last = t_emit      # whole burst emitted at one wall
        for tok in toks:
            tok = int(tok)
            req.produced.append(tok)
            self.n_tokens += 1
            self._pos[slot] += 1
            self._tokens[slot] = tok
            self._emit_stream(req, [tok])
            if self._retire_if_done(req, tok):
                break

    # -- observability -------------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            # snapshot under the lock: the loop thread churns _active
            # and the release ledger while scrapes read them
            waiting = len(self._waiting)
            active = sorted(self._active.items())
            releases = dict(self.releases)
            rows = self._live_rows()
            routings = getattr(self.decoder, "expert_routings", None)
        slots = [{"slot": s,
                  "rid": r.pending.rid,
                  "prompt_len": int(len(r.prompt)),
                  "n_tokens": len(r.produced),   # incremental progress
                  "max_new_tokens": r.max_new,
                  "n_pages": len(r.pages) + len(r.sum_pages),
                  "prefix_hit_tokens": r.hit_len,
                  "streaming": r.stream is not None,
                  "sampling": (r.sampler.describe()
                               if r.sampler is not None else None)}
                 for s, r in active]
        from mmlspark_tpu.parallel.dist import tree_bytes
        claimable = self.pages.n_pages - 1
        free = self.pages.n_free
        cached = (self.prefix.n_cached
                  if self.prefix is not None else 0)
        pages = {"page_size": self.decoder.page_size,
                 "n_pages": claimable,
                 "free": free,
                 # pages live requests hold (shared prefix pages
                 # count once however many readers share them)
                 "in_use": claimable - free - cached,
                 "cached": cached,
                 "high_water": self.pages.high_water,
                 "n_preempts": self.n_page_preempts,
                 "pool_bytes": tree_bytes(self.decoder.cache),
                 "per_slot": {str(s): len(r.pages) + len(r.sum_pages)
                              for s, r in active}}
        spec = None
        if self.decoder.has_draft:
            proposed = self.n_spec_proposed
            spec = {"k": self.decoder.spec_k,
                    "draft_layers": self.decoder.draft_cfg.n_layers,
                    "rounds": self.n_spec_rounds,
                    "proposed": proposed,
                    "accepted": self.n_spec_accepted,
                    "acceptance_rate": (
                        round(self.n_spec_accepted / proposed, 4)
                        if proposed else None),
                    "proposal_logp_ewma": (
                        round(self.spec_proposal_logp, 4)
                        if self.spec_proposal_logp is not None
                        else None),
                    "verify_ce_impl": self.decoder.verify_ce_impl,
                    "policy": (self.spec_policy.status()
                               if self.spec_policy is not None
                               else None)}
        return {"n_slots": self.decoder.n_slots,
                "slots_in_use": len(slots),
                "slots_free": self.pool.n_free,
                "slots_high_water": self.slots_high_water,
                "max_len": self.decoder.max_len,
                # the longest prompt admitted (a prefill ladder that
                # stops short of max_len is the smaller limit)
                "max_prompt": self.max_prompt,
                # passes a token makes over the layers, and what one
                # position's K/V rows cost in the pool (every layer's
                # and pass's; None: a decoder that does not say)
                "n_loops": getattr(self.decoder, "n_loops", 1),
                "kv_bytes_per_position": getattr(
                    self.decoder, "kv_bytes_per_position", None),
                # the decode-step gather engine: "pallas" = the fused
                # block-table kernel, "dense" = the materialized-lane
                # gather (CPU/mesh fallback)
                "attn_impl": self.decoder.attn_impl,
                # the prefill engine rides the same selection: under
                # "pallas" the cold prefills run streaming flash
                # attention (no [S, S] scores) and the prefix prefill
                # the fused block-table kernel (no [S, V] lane)
                "attn_impl_prefill": self.decoder.attn_impl,
                # int8-compute FFN: True when the served tree carries
                # quantize_decode_ffn's int8 weights + scale vectors
                "quantized_ffn": getattr(self.decoder,
                                         "quantized_ffn", False),
                "pages": pages,
                # the cross-request prefix cache (None = disabled):
                # radix hit counters, resident/evictable pages, and
                # the refcount ledger verdict
                "prefix_cache": (self.prefix.stats()
                                 if self.prefix is not None else None),
                "speculative": spec,
                "placement": self.decoder.placement(),
                "waiting": waiting,
                "max_waiting": self.max_waiting,
                "n_requests": self.n_requests,
                "n_steps": self.n_steps,
                # of them, steps dispatched behind a step that was not
                # fetched yet (the host's turn ran beside the device),
                # and tokens of lanes whose request had left when their
                # step was fetched (never emitted, not in n_tokens)
                "n_steps_ahead": self.n_steps_ahead,
                # passes held to today's order, by the first condition
                # of _may_run_ahead that said no (HELD_BY)
                "held_by": dict(self.held_by),
                "n_tokens_discarded": self.n_tokens_discarded,
                "n_tokens": self.n_tokens,
                # goodput: tokens from requests that resolved cleanly
                # (eos/length) vs everything emitted — cancelled/
                # deadline/preempted work is real device time wasted
                "goodput": {
                    "tokens": self.n_goodput_tokens,
                    "total_tokens": self.n_tokens,
                    "ratio": (round(self.n_goodput_tokens
                                    / self.n_tokens, 4)
                              if self.n_tokens else None)},
                "n_prefills": self.n_prefills,
                "n_prompt_tokens": self.n_prompt_tokens,
                "prefill_s": round(self.prefill_s, 4),
                # prompt tokens served per prefill wall second —
                # cached-prefix tokens count (the cache shrinks the
                # denominator), so this is the prefix-cache A/B metric
                "prefill_tokens_per_s": (
                    round(self.n_prompt_tokens / self.prefill_s, 1)
                    if self.prefill_s > 0 else None),
                # the loop's passes by phase (LOOP_PHASES): how many
                # decode.<phase> spans closed and their seconds, from
                # the same clock reads as the spans and as prefill_s
                "loop": {**{k[7:]: {"n": n, "s": round(ns * 1e-9, 6)}
                            for k, (n, ns) in self.loop.items()},
                         # the seconds the loop left the device with
                         # nothing of its own queued, by the phase it
                         # spent them in (_record_pass)
                         "starved": {k: round(ns * 1e-9, 6) for k, ns
                                     in self.starved_ns.items()}},
                "n_step_faults": self.n_step_faults,
                # the two kinds of cache row (docs/serving.md "Two
                # kinds of row"): windows turned into summaries so far
                # (after a step or inside a prefill: the decoder counts
                # both), and the rows the live slots hold now
                "n_compactions": self.decoder.n_compactions,
                "window_rows": rows[1], "summary_rows": rows[0],
                # a state a slot (docs/serving.md): requests whose
                # first prefill tile reset their slot's recurrent
                # state, and the routings each expert this chip holds
                # received over every step (None: no such block kind)
                "n_state_resets": getattr(self.decoder,
                                          "n_state_resets", 0),
                "expert_routings": (None if routings is None
                                    else routings.tolist()),
                "n_compiles": self.decoder.n_compiles(),
                # the live honest-429 inputs: slot-release gap EWMA
                # and the Retry-After a shed client would be told now
                # (None while the EWMA is cold — constant fallback)
                "release_gap_s": (
                    round(self.release_ewma.gap_s(), 4)
                    if self.release_ewma.gap_s() is not None else None),
                "retry_after_hint": (
                    round(self.retry_after_hint(), 4)
                    if self.retry_after_hint() is not None else None),
                "releases": releases,
                "active": slots}
