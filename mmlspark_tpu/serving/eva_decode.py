"""The decode plane's model side for the EVA block kind
(``models/evabyte.py``): the surface :class:`~mmlspark_tpu.serving.
decode.DecodeScheduler` drives, as :class:`~mmlspark_tpu.serving.
decode.TransformerDecoder` has it, over a cache that holds TWO kinds of
row in the one page pool.

A slot at position ``pos`` holds ``W / C`` summary rows for each of its
``pos // W`` finished windows (whole pages, never given back while the
request lives) and ``pos % W`` exact rows of the window it is in (given
back when the window fills). Its page table lists the summary pages
first, then the window pages, so the rows a step reads are a prefix of
the slot's virtual lane. The scheduler asks this class how many pages
of each kind a position needs (:meth:`pages_for`, :meth:`prefill_pages`)
and calls :meth:`compact` when a step filled a window's last row; a
prompt is prefilled window by window inside :meth:`prefill_logits`.

Built by :func:`~mmlspark_tpu.serving.decode.decoder_for` from a config
whose ``block_kind`` is ``"eva"``. No prefix cache and no speculation
for this kind (``has_prefix_prefill`` / ``has_draft`` are false): a
request's state is summaries of its own bytes, and sharing it across
requests is ROADMAP B8's next step.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

from mmlspark_tpu.serving.decode import TransformerDecoder


class EvaByteDecoder:
    """One page pool a layer + the jitted window-prefill, step and
    compaction programs over it. Not thread-safe: one scheduler loop
    drives it (the cache is donated through every call)."""

    mesh = None
    quantized_ffn = False
    has_draft = False
    has_prefix_prefill = False
    has_slot_state = False
    #: the smallest prefill bucket, as a share of the window
    MIN_BUCKET_SHARE = 16

    def __init__(self, params, cfg, n_slots: int = 8,
                 max_len: int = 16384, eos_id: Optional[int] = None,
                 donate: bool = True, page_size: int = 16,
                 n_pages: Optional[int] = None, attn_impl: str = "auto",
                 prefix_cache: bool = False, draft_params=None):
        from mmlspark_tpu.models import evabyte as E
        if prefix_cache or draft_params is not None:
            raise ValueError(
                "the EVA block kind has neither a prefix cache nor "
                "speculation: its cache is summaries of a request's own "
                "bytes (ROADMAP B8)")
        self.cfg = cfg
        self.n_slots = int(n_slots)
        self.max_len = int(max_len)
        self.eos_id = eos_id
        self.page_size = ps = int(page_size)
        self.window = int(cfg.window)
        per_window = cfg.summaries_per_window
        if ps < 1 or ps & (ps - 1) or self.window % ps or per_window % ps:
            raise ValueError(
                f"page_size={ps} must be a power of two dividing the "
                f"window ({self.window}) and a window's {per_window} "
                f"summary rows")
        #: pages of a full window, and of the summaries it leaves
        self.window_pages = self.window // ps
        self.summary_pages_per_window = per_window // ps
        # a slot never finishes the window that holds max_len - 1
        max_rows = ((self.max_len - 1) // self.window) * per_window
        # the prefill attends over [summary rows | the tile]: keep the
        # summary rows a length the flash kernel tiles well
        unit = min(1024, self.window)
        self.max_summary_pages = max(
            -(-max_rows // unit) * unit, ps) // ps
        self.pages_per_slot = self.max_summary_pages + self.window_pages
        self.n_pages = (int(n_pages) if n_pages is not None
                        else 1 + self.n_slots * self.pages_per_slot)
        if self.n_pages < 2:
            raise ValueError("paged cache needs n_pages >= 2 "
                             "(page 0 is the scratch page)")
        if attn_impl not in ("auto", "dense", "pallas", "pallas_interpret"):
            raise ValueError(f"unknown attn_impl {attn_impl!r}")
        if attn_impl == "auto":
            from mmlspark_tpu.parallel.pallas_attention import (
                paged_attention_available)
            attn_impl = "pallas" if paged_attention_available() else "dense"
        self.attn_impl = attn_impl
        self.params = params
        self._summ = E.summary_params(params)
        self.cache = E.init_cache(cfg, self.n_pages, ps)
        self._prefill = E.build_eva_prefill(cfg, ps, donate=donate,
                                            attn_impl=attn_impl)
        self._step = E.build_eva_step(cfg, ps, donate=donate,
                                      attn_impl=attn_impl)
        self._compact = E.build_eva_compact(cfg, ps, donate=donate)
        #: windows turned into summary rows, by :meth:`compact`
        self.n_compactions = 0
        self._identity_tables = None
        if 1 + self.n_slots * self.pages_per_slot <= self.n_pages:
            self._identity_tables = (
                1 + np.arange(self.n_slots * self.pages_per_slot,
                              dtype=np.int32)
            ).reshape(self.n_slots, self.pages_per_slot)

    # -- the cache's rows, as the scheduler counts them ------------------

    def rows_at(self, pos) -> "tuple[Any, Any]":
        """``(summary_rows, window_rows)`` a slot holds once the row of
        position ``pos`` is written (``pos`` an int or an array)."""
        return ((pos // self.window) * self.cfg.summaries_per_window,
                pos % self.window + 1)

    def pages_for(self, pos: int) -> "tuple[int, int]":
        """``(summary_pages, window_pages)`` for :meth:`rows_at`."""
        n_sum, n_win = self.rows_at(int(pos))
        return n_sum // self.page_size, -(-n_win // self.page_size)

    def prefill_pages(self, prompt_len: int) -> "tuple[int, int]":
        """The most pages a prefill of ``prompt_len`` holds at once, the
        first generated row included: while it walks a finished window
        it holds that window whole."""
        n_sum, n_win = self.pages_for(prompt_len)
        if prompt_len >= self.window:
            n_win = self.window_pages
        return n_sum, n_win

    def prefill_facts(self, prompt_len: int) -> Dict[str, int]:
        """What a ``decode.prefill`` span carries for this kind."""
        full, rest = divmod(int(prompt_len), self.window)
        return {"windows": full + (1 if rest else 0),
                "summary_rows_written":
                    full * self.cfg.summaries_per_window}

    prepare_facts = TransformerDecoder.prepare_facts

    def lane(self, sum_pages, win_pages) -> np.ndarray:
        """A slot's page-table row from the pages it holds: the summary
        pages first, then the window's, so that the live rows are a
        prefix of the lane (``build_eva_step`` finds a position's row
        by the same rule). The one place that lays a row out;
        :meth:`_lane_parts` is its inverse."""
        row = np.zeros(self.pages_per_slot, np.int32)
        n_sum = len(sum_pages)
        row[:n_sum] = sum_pages
        row[n_sum:n_sum + len(win_pages)] = win_pages
        return row

    def _lane_parts(self, row: np.ndarray, n_sum: int
                    ) -> "tuple[np.ndarray, np.ndarray]":
        """``row`` as the prefill and compaction programs take it: the
        summary pages (padded to the most a slot holds) and the window's
        pages."""
        sum_table = np.zeros(self.max_summary_pages, np.int32)
        sum_table[:n_sum] = row[:n_sum]
        win_table = np.zeros(self.window_pages, np.int32)
        held = row[n_sum:n_sum + self.window_pages]
        win_table[:len(held)] = held
        return sum_table, win_table

    # -- shapes ----------------------------------------------------------

    def prompt_buckets(self) -> List[int]:
        """The window tile's shape ladder: powers of two up to the
        window."""
        b = max(self.page_size, self.window // self.MIN_BUCKET_SHARE)
        out = []
        while b < self.window:
            out.append(b)
            b *= 2
        return out + [self.window]

    def _bucket(self, n: int) -> int:
        return next(b for b in self.prompt_buckets() if b >= n)

    def placement(self) -> Dict[str, Any]:
        return {"mode": "single_device", "n_devices": 1}

    # -- compute ---------------------------------------------------------

    def _table_for(self, slot: int, page_table) -> np.ndarray:
        if page_table is not None:
            return np.asarray(page_table, np.int32)
        if self._identity_tables is None:
            raise ValueError(
                "this pool is smaller than n_slots full lanes: page "
                "tables must come from the scheduler's PagePool")
        return self._identity_tables[slot]

    def prefill_logits(self, slot: int, prompt: np.ndarray,
                       page_table=None, draft: bool = True
                       ) -> "tuple[int, Any]":
        """Walk ``prompt`` window by window into the pages of
        ``page_table`` (``prefill_pages(len(prompt))`` of them: the
        summary pages first, then the window's): each finished window
        is written, attended and compacted before the next starts.
        Returns the first generated greedy byte and the last position's
        logits (a device array)."""
        import jax.numpy as jnp
        w_len, per = self.window, self.summary_pages_per_window
        full, rest = divmod(len(prompt), w_len)
        sum_table, win_table = self._lane_parts(
            self._table_for(slot, page_table), full * per)
        sum_dev, win_dev = jnp.asarray(sum_table), jnp.asarray(win_table)
        nxt = logits = None
        for w in range(full):
            self.cache, nxt, logits, _ = self._prefill(
                self.params, self.cache,
                jnp.asarray(prompt[w * w_len:(w + 1) * w_len], jnp.int32),
                sum_dev, win_dev, np.int32(w * w_len), np.int32(w_len))
            self.compact(win_dev, sum_table[w * per:(w + 1) * per])
        if rest:
            tile = np.zeros(self._bucket(rest), np.int32)
            tile[:rest] = prompt[full * w_len:]
            self.cache, nxt, logits, _ = self._prefill(
                self.params, self.cache, jnp.asarray(tile), sum_dev,
                win_dev, np.int32(full * w_len), np.int32(rest))
        return int(nxt), logits

    def prefill(self, slot: int, prompt: np.ndarray,
                page_table=None) -> int:
        return self.prefill_logits(slot, prompt, page_table)[0]

    # one step: the softmax decoder's own dispatch and fetch, by call
    # (``eva_step`` returns ``(cache, next_tokens, logits, further)``:
    # the tokens are the one fetch, and nothing is packed beside them)
    _tokens_out = 0
    n_dispatched = 0
    dispatch_step = TransformerDecoder.dispatch_step
    fetch_step = TransformerDecoder.fetch_step
    step_logits = TransformerDecoder.step_logits
    step = TransformerDecoder.step
    _warm_step = TransformerDecoder._warm_step

    def _read_fetched(self, fetched: np.ndarray, pos, attrs
                      ) -> np.ndarray:
        return fetched

    def compact(self, window_pages, summary_pages) -> None:
        """A finished window's rows (in ``window_pages``, all
        ``window_pages`` of them) -> its summaries in
        ``summary_pages``."""
        import jax.numpy as jnp
        self.cache = self._compact(
            self._summ, self.cache, jnp.asarray(window_pages, jnp.int32),
            jnp.asarray(summary_pages, jnp.int32))
        self.n_compactions += 1

    def n_compiles(self) -> int:
        return int(self._prefill._cache_size() + self._step._cache_size()
                   + self._compact._cache_size())

    def warmup(self) -> int:
        """Compile the step, the compaction and every tile bucket (what
        they write lands on the scratch page). Returns the compile
        count."""
        self._warm_step(
            np.zeros((self.n_slots, self.pages_per_slot), np.int32))
        scratch = np.zeros(self.pages_per_slot, np.int32)
        for bucket in self.prompt_buckets():
            # the full window's bucket also runs the compaction
            self.prefill(0, np.zeros(min(bucket, self.max_len - 1),
                                     np.int32), scratch)
        self.n_compactions = 0
        return self.n_compiles()
