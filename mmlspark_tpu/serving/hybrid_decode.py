"""The decode plane's model side for the Granite-hybrid block kind
(``models/granite_hybrid.py``, LFM2's layer included): the surface
:class:`~mmlspark_tpu.serving.decode.DecodeScheduler` drives, as
:class:`~mmlspark_tpu.serving.decode.TransformerDecoder` has it, over a
cache of TWO parts.

*Rows a position*: the attention layers' K/V rows, in one page pool a
layer with ``n_kv_heads`` heads, and the ``"mla"`` layers' latent rows
(the normed latent and the rotary key all heads share, one pool a layer,
the same pages). These are what :meth:`rows_at`,
:meth:`pages_for` and :meth:`prefill_pages` count, and what the
scheduler's ``PagePool`` claims and gives back. *A state a slot*: each
Mamba layer's recurrent state and conv tail, each conv layer's tail of
``v`` rows, one fixed-size entry a slot, overwritten by every step and
every tile. The scheduler counts nothing of it: it is the slot's, and a
request's first prefill tile starts it from zeros whatever the slot's
last request left (``n_state_resets``). A configuration of ``"mla"``
(and attention) layers alone has no such state (``has_slot_state`` is
false).

A prompt is walked tile by tile inside :meth:`prefill_logits`: ONE tile
program (``prefill_tile`` tokens, the last tile padded), the state
carried from tile to tile, so the decoder compiles two programs,
whatever the prompts' lengths.

Built by :func:`~mmlspark_tpu.serving.decode.decoder_for` from a config
whose ``block_kind`` is ``"granite_hybrid"``. No prefix cache and no
speculation for this kind (``has_prefix_prefill`` / ``has_draft`` are
false): a shared prefix would need the state as it stood at the
prefix's end (a snapshot a cached prefix), a rejected draft the state
before it (a rollback); neither is built (ROADMAP B8). A stack of
latent layers alone holds no such state, but the prefix cache over
latent pages is not built either (ROADMAP B3).
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
        #: a slot holds state that is not rows, where there are Mamba or
        #: conv layers (``decode.prepare`` stamps how many slots do)
        self.has_slot_state = any(k in ("mamba", "conv")
                                  for k in cfg.layer_types)
        self._n_mla = sum(k == "mla" for k in cfg.layer_types)
        # the kernel walks a slot's live entries only at whole 128-lane
        # heads: a narrower head (LFM2's 64) is kept zero-padded to them
        lanes = cfg.d_head
        if attn_impl != "dense":
            from mmlspark_tpu.parallel.pallas_attention import (
                paged_head_lanes)
            lanes = paged_head_lanes(cfg.d_head, cfg.n_kv_heads)
        # the latent kernel's copies take a row of whole 128-lane
        # registers: Kimi-K2's 576-lane row is kept zero-padded to 640
        lat_lanes = None
        if self._n_mla and attn_impl != "dense":
            from mmlspark_tpu.parallel.pallas_mla import latent_pool_lanes
            lat_lanes = latent_pool_lanes(cfg.latent_lanes)
        self.cache = GH.init_cache(cfg, self.n_slots, self.n_pages, ps,
                                   head_lanes=lanes, latent_lanes=lat_lanes)
        #: table entries the latent kernel copies at a time (None: no
        #: ``"mla"`` layer, or the dense twin in its place)
        self._latent_fetch = None
        if lat_lanes is not None:
            from mmlspark_tpu.parallel.pallas_mla import latent_fetch_pages
            self._latent_fetch = latent_fetch_pages(
                self.pages_per_slot, ps, lat_lanes,
                self.cache["lat"][0].dtype)
        self._prefill = GH.build_hybrid_prefill(cfg, ps, donate=donate,
                                                attn_impl=attn_impl)
        self._step = GH.build_hybrid_step(cfg, ps, donate=donate,
                                          attn_impl=attn_impl)
        #: requests whose first tile started a slot's state from zeros
        self.n_state_resets = 0
        #: routings each held expert received, over every step so far
        self.expert_routings = np.zeros(len(cfg.experts_held), np.int64)
        #: (MoE layer, held expert) pairs: what a step of the masked
        #: form reads
        self._pairs_held = ((cfg.n_layers - cfg.n_dense_layers)
                            * len(cfg.experts_held))
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
        """What a ``decode.prefill`` span carries for this kind; with
        ``"mla"`` layers also ``latent_rows_expanded``, the lane rows
        the walk pushes up through ``W_UK`` / ``W_UV`` in one such
        layer (tile ``k`` expands the ``k + 1`` tiles of rows up to its
        own end)."""
        tiles = -(-int(prompt_len) // self.prefill_tile)
        facts = {"tiles": tiles, "prompt_tokens": int(prompt_len)}
        if self._n_mla:
            facts["latent_rows_expanded"] = (
                self.prefill_tile * tiles * (tiles + 1) // 2)
        return facts

    def prepare_facts(self, pos) -> Dict[str, int]:
        """What a ``decode.prepare`` span carries for this kind; where
        ``paged_latent_decode_attention`` walks the ``"mla"`` layers,
        ``latent_fetches``, the fetches one layer's walk makes over the
        live slots at positions ``pos`` (``pos // page_size + 1``
        entries a slot, ``fetch`` at a time), and
        ``latent_fetches_full``, those of ``fetch`` entries, whose
        copies go out as straight-line code."""
        if self._latent_fetch is None:
            return {}
        walk = np.minimum(np.asarray(pos) // self.page_size + 1,
                          self.pages_per_slot)
        fetch = self._latent_fetch
        return {"latent_fetches": int(np.sum(-(-walk // fetch))),
                "latent_fetches_full": int(np.sum(walk // fetch))}

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
        routings, the layers summed), ``expert_load_max``,
        ``experts_touched`` ((layer, held expert) pairs that received
        any) and ``experts_read`` (the pairs whose weights the step
        copied: the touched ones under the Pallas kernels, every held
        expert of every MoE layer under the dense path)."""
        out, routings = np.split(fetched[:-1], [self.n_slots])
        self.expert_routings += routings
        touched = int(fetched[-1])
        attrs.update(expert_routings=routings.tolist(),
                     expert_load_max=int(routings.max()),
                     experts_touched=touched,
                     experts_read=(touched if self.attn_impl != "dense"
                                   else self._pairs_held))
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
