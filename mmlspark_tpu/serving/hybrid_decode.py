"""The decode plane's model side for the Granite-hybrid block kind
(``models/granite_hybrid.py``): the surface :class:`~mmlspark_tpu.
serving.decode.DecodeScheduler` drives, as :class:`~mmlspark_tpu.serving.
decode.TransformerDecoder` has it, over a cache of TWO parts.

*Rows a position*: the attention layers' K/V rows, in one page pool a
layer with ``n_kv_heads`` heads. These are what :meth:`rows_at`,
:meth:`pages_for` and :meth:`prefill_pages` count, and what the
scheduler's ``PagePool`` claims and gives back. *A state a slot*: each
Mamba layer's recurrent state and conv tail, one fixed-size entry a
slot, overwritten by every step and every tile. The scheduler counts
nothing of it: it is the slot's, and a request's first prefill tile
starts it from zeros whatever the slot's last request left
(``n_state_resets``).

A prompt is walked tile by tile inside :meth:`prefill_logits`: ONE tile
program (``prefill_tile`` tokens, the last tile padded), the state
carried from tile to tile, so the decoder compiles two programs,
whatever the prompts' lengths.

Built by :func:`~mmlspark_tpu.serving.decode.decoder_for` from a config
whose ``block_kind`` is ``"granite_hybrid"``. No prefix cache and no
speculation for this kind (``has_prefix_prefill`` / ``has_draft`` are
false): a shared prefix would need the state as it stood at the
prefix's end (a snapshot a cached prefix), a rejected draft the state
before it (a rollback); neither is built (ROADMAP B8).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

from mmlspark_tpu.serving.decode import TransformerDecoder


class HybridDecoder:
    """The two-part cache + the jitted tile-prefill and step programs
    over it. Not thread-safe: one scheduler loop drives it (the cache is
    donated through every call)."""

    mesh = None
    quantized_ffn = False
    has_draft = False
    has_prefix_prefill = False
    #: this kind's rows are of one kind, kept for ever (no window)
    window: Optional[int] = None
    n_compactions = 0
    #: a slot holds state that is not rows (``decode.prepare`` stamps
    #: how many slots do)
    has_slot_state = True

    def __init__(self, params, cfg, n_slots: int = 16,
                 max_len: int = 10240, eos_id: Optional[int] = None,
                 donate: bool = True, page_size: int = 16,
                 n_pages: Optional[int] = None, attn_impl: str = "auto",
                 prefill_tile: int = 512, prefix_cache: bool = False,
                 draft_params=None):
        from mmlspark_tpu.models import granite_hybrid as GH
        if prefix_cache or draft_params is not None:
            raise ValueError(
                "the Granite-hybrid block kind has neither a prefix "
                "cache nor speculation: a shared prefix needs a snapshot "
                "of the recurrent state at its end and a rejected draft "
                "a rollback of it (ROADMAP B8)")
        self.cfg = cfg
        self.n_slots = int(n_slots)
        self.max_len = int(max_len)
        self.eos_id = eos_id
        self.page_size = ps = int(page_size)
        self.prefill_tile = tile = int(prefill_tile)
        if ps < 1 or ps & (ps - 1) or tile % ps or self.max_len % tile:
            raise ValueError(
                f"page_size={ps} must be a power of two dividing "
                f"prefill_tile={tile}, which must divide max_len="
                f"{self.max_len}")
        self.pages_per_slot = self.max_len // ps
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
        self.cache = GH.init_cache(cfg, self.n_slots, self.n_pages, ps)
        self._prefill = GH.build_hybrid_prefill(cfg, ps, donate=donate,
                                                attn_impl=attn_impl)
        self._step = GH.build_hybrid_step(cfg, ps, donate=donate,
                                          attn_impl=attn_impl)
        #: requests whose first tile started a slot's state from zeros
        self.n_state_resets = 0
        #: routings each held expert received, over every step so far
        self.expert_routings = np.zeros(len(cfg.experts_held), np.int64)
        self._identity_tables = None
        if 1 + self.n_slots * self.pages_per_slot <= self.n_pages:
            self._identity_tables = (
                1 + np.arange(self.n_slots * self.pages_per_slot,
                              dtype=np.int32)
            ).reshape(self.n_slots, self.pages_per_slot)

    # -- the cache's rows, as the scheduler counts them ------------------
    # the attention layers' K/V rows are one a position, kept for
    # ever, in order of position: the softmax decoder's own counting
    # and table layout, by call; the recurrent state is not rows

    rows_at = TransformerDecoder.rows_at
    pages_for = TransformerDecoder.pages_for
    prefill_pages = TransformerDecoder.prefill_pages
    lane = TransformerDecoder.lane
    _table_for = TransformerDecoder._table_for
    placement = TransformerDecoder.placement
    prefill = TransformerDecoder.prefill
    # one step: the shared dispatch and fetch (``hybrid_step`` returns
    # ``(cache, fetched, logits, next_tokens)``)
    _tokens_out = 2
    n_dispatched = 0
    dispatch_step = TransformerDecoder.dispatch_step
    fetch_step = TransformerDecoder.fetch_step
    step_logits = TransformerDecoder.step_logits
    step = TransformerDecoder.step
    _warm_step = TransformerDecoder._warm_step

    def prefill_facts(self, prompt_len: int) -> Dict[str, int]:
        """What a ``decode.prefill`` span carries for this kind."""
        return {"tiles": -(-int(prompt_len) // self.prefill_tile),
                "prompt_tokens": int(prompt_len)}

    # -- shapes ----------------------------------------------------------

    def prompt_buckets(self) -> List[int]:
        """One tile shape: a prompt is as many tiles as it needs."""
        return [self.prefill_tile]

    # -- compute ---------------------------------------------------------

    def prefill_logits(self, slot: int, prompt: np.ndarray,
                       page_table=None, draft: bool = True
                       ) -> "tuple[int, Any]":
        """Walk ``prompt`` tile by tile: the first tile starts slot
        ``slot``'s state from zeros, every tile carries it on and
        appends its K/V rows to the pages of ``page_table``. Returns
        the first generated greedy token and the last position's logits
        (a device array)."""
        import jax.numpy as jnp
        tile = self.prefill_tile
        if not 0 < len(prompt) < self.max_len:
            raise ValueError(f"prompt of {len(prompt)} tokens; this "
                             f"decoder holds 1 to {self.max_len - 1}")
        table = jnp.asarray(self._table_for(slot, page_table))
        nxt = logits = None
        for pos0 in range(0, len(prompt), tile):
            real = min(tile, len(prompt) - pos0)
            tokens = np.zeros(tile, np.int32)
            tokens[:real] = prompt[pos0:pos0 + real]
            self.cache, nxt, logits = self._prefill(
                self.params, self.cache, jnp.asarray(tokens), table,
                np.int32(slot), np.int32(pos0), np.int32(real))
        self.n_state_resets += 1
        return int(nxt), logits

    def _read_fetched(self, fetched: np.ndarray, pos, attrs
                      ) -> np.ndarray:
        """A step's one copy back, ``[next tokens | routings |
        touched]`` -> the tokens; the ``decode.fetch`` span carries
        what the experts received: ``expert_routings`` (a held expert's
        routings, the layers summed), ``expert_load_max`` and
        ``experts_touched`` ((layer, held expert) pairs that received
        any)."""
        out, routings = np.split(fetched[:-1], [self.n_slots])
        self.expert_routings += routings
        attrs.update(expert_routings=routings.tolist(),
                     expert_load_max=int(routings.max()),
                     experts_touched=int(fetched[-1]))
        return out

    def n_compiles(self) -> int:
        return int(self._prefill._cache_size() + self._step._cache_size())

    def warmup(self) -> int:
        """Compile the step and the tile (what they write lands on the
        scratch page and in slot 0's state, which the slot's first
        request resets). Returns the compile count: two."""
        scratch = np.zeros((self.n_slots, self.pages_per_slot), np.int32)
        self._warm_step(scratch)
        self.prefill(0, np.zeros(1, np.int32), scratch[0])
        self.n_state_resets = 0
        self.expert_routings[:] = 0
        return self.n_compiles()
