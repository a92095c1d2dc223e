"""EvaByte, the EVA block kind (models/evabyte.py, serving/
eva_decode.py), at a small size on the CPU: d_model 64, 4 heads x 16,
window 32, chunk 4, 3 layers, vocab 320, float32 so that tolerances are
tight. The yardstick is benchmark/reference_evabyte.py: plain
jax.numpy, weights from a seed, one full forward with the mask written
out.

Tolerances, and why: program and reference compute the same float32
sums in another order (windows from a cache, streaming softmax in the
kernels), under ``default_matmul_precision("highest")``; logits are of
order 1, the observed gap is 1e-6, and ``TOL`` leaves five times that.
Leaving the summaries out moves logits by over 1 (``test_summaries_
left_out_fails``), a million times ``TOL``.
"""

import json
import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
import reference_evabyte as RE  # noqa: E402

from mmlspark_tpu.models import evabyte as E  # noqa: E402
from mmlspark_tpu.models import transformer as T  # noqa: E402
from mmlspark_tpu.parallel.pallas_attention import (  # noqa: E402
    eva_prefill_attention)
from mmlspark_tpu.parallel.ring_attention import dense_attention  # noqa: E402
from mmlspark_tpu.serving import DecodeScheduler, TransformerDecoder  # noqa: E402
from mmlspark_tpu.serving.decode import decoder_for  # noqa: E402
from mmlspark_tpu.serving.eva_decode import EvaByteDecoder  # noqa: E402

TOL = 5e-6
SEED = 5
W, C = 32, 4
M = RE.Model(vocab=320, d_model=64, n_heads=4, d_head=16, d_ff=160,
             n_layers=3, window=W, chunk=C, n_pred_heads=8,
             rope_theta=100000.0, norm_eps=1e-5, init_std=0.05)
CFG = E.EvaByteConfig(vocab=320, d_model=64, n_heads=4, d_head=16,
                      d_ff=160, n_layers=3, window=W, chunk=C,
                      init_std=0.05, dtype="float32")
TOKENS = np.random.default_rng(0).integers(0, 320, size=112).astype(np.int32)


@pytest.fixture(scope="module", autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def params():
    return RE.make_params(M, SEED)


@pytest.fixture(scope="module")
def reference():
    return RE.logits(M, SEED, TOKENS)


class _Pending:
    def __init__(self, payload, rid):
        self.payload, self.rid = payload, rid
        self.deadline = self.span = self.reply = None
        self.event = threading.Event()
        self.callbacks = []
        self.status = 200
        self.trace = rid


def _generate(sched, prompts, max_new):
    pend = [_Pending({"prompt": [int(t) for t in p],
                      "max_new_tokens": int(n)}, f"r{i}")
            for i, (p, n) in enumerate(zip(prompts, max_new))]
    for p in pend:
        sched.submit(p)
    for p in pend:
        assert p.event.wait(60)
    return [json.loads(p.reply) for p in pend]


def _recording(dec):
    """Every step's positions and logits, as the scheduler's loop got
    them."""
    seen = []
    inner = dec.step_logits

    def step_logits(tokens, pos, tables=None):
        out, logits = inner(tokens, pos, tables)
        seen.append((np.array(pos), np.asarray(logits)))
        return out, logits

    dec.step_logits = step_logits
    return seen


@pytest.fixture(scope="module")
def served(params):
    """One decoder + scheduler for the cases below (slot 0 is the only
    one a lone request takes)."""
    dec = decoder_for(params, CFG, n_slots=3, max_len=128, page_size=4,
                      attn_impl="dense")
    seen = _recording(dec)
    sched = DecodeScheduler(dec, max_new_tokens_default=4).start()
    yield dec, sched, seen
    sched.stop()


# ---------------------------------------------------------------------------
# the plain forward


@pytest.mark.parametrize("length", [20, 32, 33, 112])
def test_forward_matches_reference(params, reference, length):
    got = jax.jit(lambda p, t: E.forward_logits(p, t, CFG))(
        params, jnp.asarray(TOKENS[:length]))
    want = reference[:length] if length == 112 \
        else RE.logits(M, SEED, TOKENS[:length])
    assert got.shape == (length, 8 * 320) and got.dtype == jnp.float32
    assert np.abs(np.asarray(got) - want).max() < TOL


def test_one_window_is_causal_softmax_attention():
    """With no summary visible the window's attention is the repo's
    ``dense_attention``; and a model never past its first window does
    not read ``phi`` or ``mu`` at all."""
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.normal(size=(W, 4, 16)), jnp.float32)
               for _ in range(3))
    ks = vs = jnp.asarray(rng.normal(size=(8, 4, 16)), jnp.float32)
    got = E.eva_attention_dense(q, k, v, ks, vs, 0, 16 ** -0.5)
    want = dense_attention(q[None], k[None], v[None], causal=True)[0]
    assert np.abs(np.asarray(got - want)).max() < TOL
    params = RE.make_params(M, SEED)
    other = dict(params, blocks=[dict(b, phi=b["phi"] * 0 + 3.0,
                                      mu=b["mu"] - 1.0)
                                 for b in params["blocks"]])
    a, b = (np.asarray(E.forward_logits(p, jnp.asarray(TOKENS[:W]), CFG))
            for p in (params, other))
    assert np.array_equal(a, b)


def test_summaries_left_out_fails(params, reference):
    """The fault of the mechanism: without the summary terms the same
    comparison fails, past the first window only."""
    got = np.asarray(E.forward_logits(params, jnp.asarray(TOKENS), CFG))
    broken = RE.logits(M, SEED, TOKENS, drop_summaries=True)
    gap = np.abs(got - broken).max(axis=-1)
    assert gap[:W].max() < TOL          # one window: nothing to leave out
    assert gap[W:].max() > 0.5          # measured 2.2
    assert np.abs(got - reference).max() < TOL


# ---------------------------------------------------------------------------
# prefill, compaction and decoding through the cache


@pytest.mark.parametrize(
    "prompt_len", [3, 4, 5, 31, 32, 33, 70],
    ids=["before_chunk", "on_chunk", "after_chunk", "before_window",
         "on_window", "after_window", "third_window"])
def test_prefill_then_decode_matches_reference(served, prompt_len):
    """A prompt that ends before, on and after a chunk's and a window's
    boundary, prefilled window by window and then decoded through the
    cache until it lies in its fourth window: every step's logits
    against ONE full forward of the reference over prompt + served
    bytes."""
    dec, sched, seen = served
    seen.clear()
    before = sched.stats()["n_compactions"]
    n_new = 100 - prompt_len
    out, = _generate(sched, [TOKENS[:prompt_len]], [n_new])
    assert out["finish_reason"] == "length" and out["n_tokens"] == n_new
    seq = np.concatenate([TOKENS[:prompt_len],
                          np.asarray(out["tokens"], np.int32)])
    want = RE.logits(M, SEED, seq[:-1])
    # byte 0 is the prefill's: the argmax of the last prompt row
    assert out["tokens"][0] == int(want[prompt_len - 1, :320].argmax())
    assert len(seen) == n_new - 1
    for i, (pos, logits) in enumerate(seen):
        assert pos[0] == prompt_len + i
        assert np.abs(logits[0] - want[pos[0], :320]).max() < TOL, pos[0]
    # windows [0, 32), [32, 64), [64, 96) were each compacted once
    assert sched.stats()["n_compactions"] - before == 3
    assert sched.pages.n_free == dec.n_pages - 1


def test_a_window_filled_with_a_step_queued_is_compacted_in_between(
        params):
    """Both slots taken and greedy: the loop queues a step behind the
    one in flight, except where that one fills a slot's window. That
    pass only fetches, emits and compacts, the next dispatches alone,
    and every served byte is the reference's best at its position
    (ONE full forward over prompt + served bytes)."""
    from mmlspark_tpu.core.tracing import Tracer
    from mmlspark_tpu.serving.decode import pass_view
    dec = decoder_for(params, CFG, n_slots=2, max_len=128, page_size=4,
                      attn_impl="dense")
    tracer = Tracer()
    sched = DecodeScheduler(dec, tracer=tracer).start()
    prompts = [TOKENS[:20], TOKENS[40:50]]
    try:
        outs = _generate(sched, prompts, [30, 30])
        stats = sched.stats()
    finally:
        sched.stop()
    for prompt, out in zip(prompts, outs):
        seq = np.concatenate([prompt, np.asarray(out["tokens"], np.int32)])
        want = RE.logits(M, SEED, seq[:-1])[len(prompt) - 1:, :320]
        served = want[np.arange(30), out["tokens"]]
        assert (want.max(axis=-1) - served).max() < TOL
    views = [pass_view(sp.attrs["phases"])
             for sp in tracer.recorder.scan("decode.pass")]
    # positions 31 are written by steps 12 and 22: two compactions by
    # the loop, each in a pass that dispatched nothing, each followed
    # by a step that ran behind no other
    compacting = [i for i, v in enumerate(views)
                  if "compact" in v["phases_ms"]]
    assert len(compacting) == 2 and stats["n_compactions"] == 2
    for i in compacting:
        assert sorted(views[i]["phases_ms"]) == [
            "admit", "compact", "emit", "fetch", "prepare"]
        assert views[i + 1]["ahead"] is False
        assert "fetch" not in views[i + 1]["phases_ms"]
        assert views[i + 2]["ahead"] is True
    # behind no other: the first step and the one after each compaction
    assert stats["n_steps"] == 29 and stats["n_steps_ahead"] == 29 - 3
    assert stats["n_tokens_discarded"] == 0
    assert sched.pages.n_free == dec.n_pages - 1


def test_three_requests_together_give_what_each_gives_alone(served):
    dec, sched, _ = served
    prompts = [TOKENS[:7], TOKENS[10:55], TOKENS[20:110]]
    budgets = [60, 40, 12]
    alone = [_generate(sched, [p], [n])[0]["tokens"]
             for p, n in zip(prompts, budgets)]
    together = [o["tokens"] for o in _generate(sched, prompts, budgets)]
    assert together == alone
    assert sched.pages.n_free == dec.n_pages - 1
    assert sched.pool.n_free == 3


# ---------------------------------------------------------------------------
# the kernels in interpret mode against the XLA path


@pytest.mark.parametrize("tile,n_summary", [(8, 0), (16, 8), (32, 16),
                                            (32, 11)])
def test_prefill_kernel_interpret_matches_xla(tile, n_summary):
    """``eva_prefill_attention`` (the flash forward kernel over
    [summary rows | the tile], visibility carried by the positions)
    against ``eva_attention_dense``; streaming softmax in float32
    reassociates the sums."""
    rng = np.random.default_rng(tile + n_summary)
    q, k, v = (jnp.asarray(rng.normal(size=(tile, 4, 16)), jnp.float32)
               for _ in range(3))
    ks, vs = (jnp.asarray(rng.normal(size=(16, 4, 16)), jnp.float32)
              for _ in range(2))
    want = E.eva_attention_dense(q, k, v, ks, vs, n_summary, 0.25)
    got = eva_prefill_attention(q, k, v, ks, vs, jnp.int32(n_summary),
                                0.25, interpret=True)
    assert got.shape == want.shape
    assert np.abs(np.asarray(got - want)).max() < TOL


def test_decoder_on_interpreted_kernels_matches_xla(params):
    """Window prefill, compaction and steps on ``pallas_interpret``
    (``_flash_call`` + ``paged_decode_attention`` over summary pages
    then window pages) against the dense gather, on logits."""
    logits = {}
    for impl in ("dense", "pallas_interpret"):
        dec = decoder_for(params, CFG, n_slots=2, max_len=128, page_size=4,
                          attn_impl=impl)
        seen = _recording(dec)
        sched = DecodeScheduler(dec).start()
        try:
            out, = _generate(sched, [TOKENS[:45]], [25])
        finally:
            sched.stop()
        logits[impl] = (out["tokens"], np.stack([lg[0] for _, lg in seen]))
    assert logits["dense"][0] == logits["pallas_interpret"][0]
    assert np.abs(logits["dense"][1]
                  - logits["pallas_interpret"][1]).max() < TOL


# ---------------------------------------------------------------------------
# the kind, its rows, its precision


def test_the_configuration_picks_the_decoder(params):
    dec = decoder_for(params, CFG, n_slots=2, max_len=64, page_size=4,
                      attn_impl="dense")
    assert isinstance(dec, EvaByteDecoder) and dec.window == W
    t_cfg = T.TransformerConfig(vocab=64, d_model=16, n_heads=2, d_head=8,
                                d_ff=32, n_stages=1, layers_per_stage=1)
    soft = decoder_for(T.init_params(t_cfg, seed=0), t_cfg, n_slots=2,
                       max_len=16)
    assert isinstance(soft, TransformerDecoder) and soft.window is None


def test_prefix_cache_and_speculation_refuse(params):
    kw = dict(n_slots=2, max_len=64, page_size=4, attn_impl="dense")
    with pytest.raises(ValueError, match="prefix cache"):
        decoder_for(params, CFG, prefix_cache=True, **kw)
    with pytest.raises(ValueError, match="speculation"):
        decoder_for(params, CFG, draft_params=params, **kw)
    dec = decoder_for(params, CFG, **kw)
    assert not dec.has_prefix_prefill and not dec.has_draft
    with pytest.raises(ValueError, match="prefix_cache=True"):
        DecodeScheduler(dec, prefix_cache=True)
    assert DecodeScheduler(dec).prefix is None


@pytest.mark.parametrize("pos,rows,pages,prefill", [
    (0, (0, 1), (0, 1), (0, 1)),
    (3, (0, 4), (0, 1), (0, 1)),
    (4, (0, 5), (0, 2), (0, 2)),
    (31, (0, 32), (0, 8), (0, 8)),
    (32, (8, 1), (2, 1), (2, 8)),
    (70, (16, 7), (4, 2), (4, 8)),
    (126, (24, 31), (6, 8), (6, 8)),
])
def test_rows_and_pages_by_position(params, pos, rows, pages, prefill):
    """What the scheduler counts: at position ``pos`` a slot holds
    ``8 * (pos // 32)`` summary rows and ``pos % 32 + 1`` window rows;
    a prefill holds a finished window whole while it walks it. At
    16,384 positions of the real sizes that is 2,944 rows, not
    16,384."""
    dec = EvaByteDecoder(params, CFG, n_slots=1, max_len=128, page_size=4,
                         attn_impl="dense")
    assert dec.rows_at(pos) == rows
    assert dec.pages_for(pos) == pages
    assert dec.prefill_pages(pos) == prefill
    assert dec.pages_per_slot == 8 + 8 and dec.n_pages == 17
    big = EvaByteDecoder.__new__(EvaByteDecoder)
    big.window, big.cfg, big.page_size = 2048, E.EvaByteConfig(), 16
    assert sum(big.rows_at(16383)) == 2944


def test_programs_read_the_configurations_dtype():
    """bfloat16 weights and cache rows, float32 logits."""
    cfg = E.EvaByteConfig(vocab=320, d_model=64, n_heads=4, d_head=16,
                          d_ff=160, n_layers=2, window=W, chunk=C,
                          dtype="bfloat16")
    params = E.init_params(cfg, seed=1)
    assert params["blocks"][0]["wq"].dtype == jnp.bfloat16
    dec = decoder_for(params, cfg, n_slots=2, max_len=64, page_size=4,
                      attn_impl="dense")
    assert all(c.dtype == jnp.bfloat16 for c in dec.cache["k"])
    first, logits = dec.prefill_logits(0, TOKENS[:40])
    assert logits.dtype == jnp.float32 and logits.shape == (320,)
    _, step_logits = dec.step_logits(np.array([first, 0], np.int32),
                                     np.array([40, 0], np.int32))
    assert step_logits.dtype == jnp.float32
    # against its own plain forward in the same precision: bfloat16
    # operands round at 2^-9 relative, logits are of order 0.1
    want = E.forward_logits(params, jnp.asarray(TOKENS[:40]), cfg)[-1, :320]
    assert np.abs(np.asarray(logits - want)).max() < 2e-2
