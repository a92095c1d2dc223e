"""Granite-hybrid, the third block kind (models/granite_hybrid.py,
serving/hybrid_decode.py), at a small size on the CPU: d_model 64, four
layers (mamba, mamba, attention, mamba), 8 Mamba heads x 16 with state
16, chunks of 8, 4 query heads over 2 K/V heads, 4 of 8 experts held,
top 3, vocab 96. The yardstick is benchmark/reference_granite.py: plain
jax.numpy, weights from a seed, the recurrence a position at a time, one
full forward from a zero state.

Tolerances, and why. In float32 under ``default_matmul_precision
("highest")`` program and reference compute the same sums in another
order (the chunked scan, tiles from a cache, a grouped product); logits
are of order 0.02, the observed gap is 4e-8, and ``TOL`` leaves ten
times that. A fault of either mechanism moves logits by over 5e-3
(``test_faults_move_the_logits``), ten thousand times ``TOL``. In
bfloat16 the operands are rounded (2^-9 relative a product) and a
rounded router input can choose another third expert: the observed gap
is 3e-4 against logits of up to 0.02, and ``TOL_BF16`` leaves five
times that, a quarter of the 6e-3 a fault moves.
"""

import dataclasses
import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
import reference_granite as RG  # noqa: E402

from mmlspark_tpu.models import granite_hybrid as GH  # noqa: E402
from mmlspark_tpu.parallel.pallas_attention import (  # noqa: E402
    flash_attention, flash_prefill_attention, paged_decode_attention)
from mmlspark_tpu.serving.decode import decoder_for  # noqa: E402
from mmlspark_tpu.serving.hybrid_decode import HybridDecoder  # noqa: E402

TOL = 4e-7
TOL_BF16 = 1.5e-3
SEED = 5
M = RG.Model(
    vocab=96, d_model=64, n_heads=4, n_kv_heads=2, d_head=16,
    layer_types=("mamba", "mamba", "attention", "mamba"), n_experts=8,
    top_k=3, experts_held=(0, 1, 2, 3), d_expert=24, d_shared=48,
    ssm_heads=8, ssm_head_dim=16, ssm_state=16, ssm_conv=4, ssm_chunk=8,
    embedding_multiplier=12.0, residual_multiplier=0.22,
    attention_multiplier=0.0625, logits_scaling=16.0, norm_eps=1e-5,
    embed_std=0.01)
PS, TILE, N_SLOTS, MAX_LEN = 4, 16, 3, 64
TOKENS = np.random.default_rng(0).integers(0, 96, size=60).astype(np.int32)


def config(m=M, dtype="float32"):
    return GH.GraniteHybridConfig(**dataclasses.asdict(m), dtype=dtype)


CFG = config()


@pytest.fixture(scope="module", autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def params():
    return RG.make_params(M, SEED)


@pytest.fixture(scope="module")
def reference():
    return RG.logits(M, SEED, TOKENS)


def decoder(params, cfg=CFG, **kw):
    kw = dict(dict(n_slots=N_SLOTS, max_len=MAX_LEN, page_size=PS,
                   prefill_tile=TILE, attn_impl="dense"), **kw)
    return decoder_for(params, cfg, **kw)


def served_logits(dec, slot, tokens, prompt_len):
    """Prefill ``tokens[:prompt_len]`` into ``slot`` tile by tile, then
    feed the rest a step at a time: the logits at every position from
    ``prompt_len - 1`` on."""
    _, last = dec.prefill_logits(slot, tokens[:prompt_len])
    out = [np.asarray(last)]
    for pos in range(prompt_len, len(tokens)):
        tok = np.zeros(dec.n_slots, np.int32)
        at = np.zeros(dec.n_slots, np.int32)
        tok[slot], at[slot] = tokens[pos], pos
        _, logits = dec.step_logits(tok, at)
        out.append(np.asarray(logits[slot]))
    return np.stack(out)


# ---------------------------------------------------------------------------
# the model against the reference


def test_forward_matches_reference(params, reference):
    got = np.asarray(GH.forward_logits(params, jnp.asarray(TOKENS), CFG))
    assert np.abs(got - reference).max() < TOL


@pytest.mark.parametrize("fault", RG.FAULTS)
def test_faults_move_the_logits(reference, fault):
    """What the benchmark plants in the reference is far outside every
    tolerance here: the comparison sees the state and every routing."""
    moved = RG.logits(M, SEED, TOKENS, fault=fault, reset_every=16)
    assert np.abs(moved - reference).max() > 5e-3


@pytest.mark.parametrize("prompt_len", [11, 16, 37, 48],
                         ids=["inside_a_tile", "a_tiles_edge",
                              "past_two_tiles", "three_tiles_edge"])
def test_prefill_then_decode_matches_reference(params, reference,
                                               prompt_len):
    """The state carried from tile to tile and from step to step, the
    K/V rows appended to pages, against one forward from a zero
    state."""
    dec = decoder(params)
    got = served_logits(dec, 1, TOKENS, prompt_len)
    assert np.abs(got - reference[prompt_len - 1:]).max() < TOL
    assert dec.n_compiles() == 2


@pytest.mark.parametrize("prompt_len", [11, 16, 37])
def test_bfloat16_programs_stay_near_the_float32_reference(reference,
                                                           prompt_len):
    cfg = config(dtype="bfloat16")
    dec = decoder(RG.make_params(M, SEED, jnp.bfloat16), cfg)
    got = served_logits(dec, 0, TOKENS, prompt_len)
    gap = np.abs(got - reference[prompt_len - 1:]).max()
    assert 1e-5 < gap < TOL_BF16       # rounded, and not by much
    assert dec.cache["ssm"][0].dtype == jnp.float32
    assert dec.cache["k"][0].dtype == jnp.bfloat16
    assert dec.cache["k"][0].shape == (1 + N_SLOTS * MAX_LEN // PS, PS,
                                       M.n_kv_heads, M.d_head)


# ---------------------------------------------------------------------------
# the recurrence


def scan_recurrence(x, dt, a, b, c, state):
    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = (jnp.exp(dt_t * a)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return s, jnp.sum(s * c_t[None, None, :], axis=-1)
    state, y = jax.lax.scan(step, state, (x, dt, b, c))
    return y, state


@pytest.mark.parametrize("chunk", [4, 8, 16, 7, 5, 64],
                         ids=lambda c: f"chunk{c}")
def test_chunked_recurrence_is_the_scan(chunk):
    """Chunk sizes that divide the 40 positions, that do not, and one
    longer than all of them; from a state that is not zero."""
    rng = np.random.default_rng(chunk)
    t, h, p, n = 40, 3, 5, 6
    x = jnp.asarray(rng.normal(size=(t, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.5, size=(t, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 16, size=h), jnp.float32)
    b, c = (jnp.asarray(rng.normal(size=(t, n)), jnp.float32)
            for _ in range(2))
    s0 = jnp.asarray(rng.normal(size=(h, p, n)), jnp.float32)
    y, s = GH.ssm_chunked(x, dt, a, b, c, s0, chunk)
    y_ref, s_ref = scan_recurrence(x, dt, a, b, c, s0)
    # the same float32 sums in another order: sums of some tens of
    # terms of order one
    assert np.abs(np.asarray(y) - np.asarray(y_ref)).max() < 2e-5
    assert np.abs(np.asarray(s) - np.asarray(s_ref)).max() < 2e-5


def test_padded_positions_leave_state_and_tail_bit_identical(params):
    """A tile's padding neither decays nor feeds the state and shifts
    no row into the conv tail, whatever the padded tokens are; a tile
    with no real position leaves both as they were, bit for bit."""
    prefill = GH.build_hybrid_prefill(CFG, PS, donate=False)
    table = jnp.asarray(1 + np.arange(MAX_LEN // PS, dtype=np.int32))
    cache = GH.init_cache(CFG, N_SLOTS, 1 + N_SLOTS * MAX_LEN // PS, PS)
    tile = np.zeros(TILE, np.int32)
    tile[:9] = TOKENS[:9]
    other = tile.copy()
    other[9:] = TOKENS[20:27]

    def after(tokens, cache, pos0, length):
        out, _, _ = prefill(params, cache, jnp.asarray(tokens), table,
                            np.int32(1), np.int32(pos0), np.int32(length))
        return out

    a, b = after(tile, cache, 0, 9), after(other, cache, 0, 9)
    for name in ("ssm", "conv"):
        for x, y in zip(a[name], b[name]):
            assert np.array_equal(np.asarray(x), np.asarray(y))
            assert np.abs(np.asarray(x[1])).max() > 0
    # nothing real in the tile (pos0 > 0: the state is carried, not reset)
    c = after(other, a, TILE, 0)
    for name in ("ssm", "conv"):
        for x, y in zip(a[name], c[name]):
            assert np.array_equal(np.asarray(x), np.asarray(y))


def test_a_step_takes_the_tokens_of_the_step_before_on_the_device(
        params):
    """The step's one fetch packs counters behind its tokens; the
    tokens are an output of their own too, and a step dispatched on
    them before they are fetched gives what a step on the fetched
    tokens gives (two decoders, the same prompt in slot 1)."""
    a, b = decoder(params), decoder(params)
    tok = np.zeros(N_SLOTS, np.int32)
    at = np.zeros(N_SLOTS, np.int32)
    for dec in (a, b):
        tok[1] = dec.prefill(1, TOKENS[:9])
    at[1] = 9
    first = a.dispatch_step(tok, at)
    second = a.dispatch_step(first, at + (at > 0))      # not fetched yet
    assert (np.asarray(first.tokens)
            == np.asarray(first.fetched)[:N_SLOTS]).all()
    out1, _ = a.fetch_step(first)
    out2, logits2 = a.fetch_step(second)
    want1, _ = b.step_logits(tok, at)
    want2, want_logits2 = b.step_logits(want1, at + (at > 0))
    assert (out1 == want1).all() and (out2 == want2).all()
    assert np.abs(np.asarray(logits2) - np.asarray(want_logits2)).max() \
        == 0.0
    assert (a.expert_routings == b.expert_routings).all()
    assert (first.seq, second.seq) == (1, 2) and a.n_compiles() == 2


def test_a_reused_slot_starts_from_a_zero_state(params, reference):
    """A request's first tile resets whatever the slot's last request
    left: the second request in the slot reads as if it were alone."""
    dec = decoder(params)
    served_logits(dec, 2, TOKENS[::-1].copy(), 30)
    assert np.abs(np.asarray(dec.cache["ssm"][0][2])).max() > 0
    got = served_logits(dec, 2, TOKENS, 21)
    assert np.abs(got - reference[20:]).max() < TOL
    assert dec.n_state_resets == 2


# ---------------------------------------------------------------------------
# the expert layer holds a share


def _layer_input(seed=3, n=24):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(n, 64)),
                       jnp.float32)


def _moe_of(held, h, grouped, layer=0, router=None):
    m = RG.uncut(M) if held is None else RG.Model(
        **{**M.__dict__, "experts_held": tuple(held)})
    lp = RG.layer_params(m, SEED, layer)
    if router is not None:
        lp = dict(lp, router=router)
    out, routings, touched = GH.moe(lp, h, config(m), grouped=grouped)
    return np.asarray(out), np.asarray(routings), lp, m


@pytest.mark.parametrize("grouped", [True, False],
                         ids=["grouped", "masked"])
def test_the_shares_add_up(grouped):
    """Experts 0-3 on one chip and 4-7 on the other: the two routed
    parts and the shared expert counted ONCE are the uncut layer of the
    reference."""
    h = _layer_input()
    lo, r_lo, lp, m = _moe_of(range(0, 4), h, grouped)
    hi, r_hi, _, _ = _moe_of(range(4, 8), h, grouped)
    shared = np.asarray(GH._gated(GH._norm(h, lp["norm2"], CFG),
                                  lp["w_in_s"], lp["w_out_s"], jnp.float32))
    whole = RG.uncut(M)
    ref = np.asarray(RG._experts(RG.layer_params(whole, SEED, 0), h, whole,
                                 "highest", False))
    ref = (ref - np.asarray(h)) / M.residual_multiplier
    assert np.abs(lo + hi - shared - ref).max() < 1e-5
    # every routing of every token landed on exactly one of the chips
    assert r_lo.sum() + r_hi.sum() == h.shape[0] * M.top_k


@pytest.mark.parametrize("grouped", [True, False],
                         ids=["grouped", "masked"])
def test_no_routing_is_dropped_under_the_most_uneven_routing(grouped):
    """Every token to the same three experts, all held: the held
    experts receive every routing, and the layer is the reference's."""
    h = jnp.abs(_layer_input(seed=4, n=32))
    base = np.asarray(RG.layer_params(M, SEED, 1)["router"])
    router = 1e-3 * base
    # every input is positive, so a positive column scores every token
    # high: experts 0, 1, 2 in that order, whatever the token
    for e in (0, 1, 2):
        router[:, e] = 3.0 - e
    router = jnp.asarray(router)
    out, routings, lp, m = _moe_of(range(0, 4), h, grouped, layer=1,
                                   router=router)
    assert routings.tolist() == [32, 32, 32, 0]
    ref = np.asarray(RG._experts(lp, h, m, "highest", False))
    ref = (ref - np.asarray(h)) / M.residual_multiplier
    assert np.abs(out - ref).max() < 1e-5


# ---------------------------------------------------------------------------
# grouped-query heads in the shared kernels (interpret mode)


def _dense_repeated(q, k, v, q_pos, scale):
    """Dense causal attention with the K/V heads REPEATED to the query
    heads': what the kernels must equal without repeating anything."""
    g = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(x, g, axis=1) for x in (k, v))
    s = jnp.einsum("qhd,khd->hqk", q, k) * scale
    seen = q_pos[:, None] >= jnp.arange(k.shape[0])[None, :]
    p = jax.nn.softmax(jnp.where(seen[None], s, -1e30), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v)


@pytest.mark.parametrize("h,h_kv", [(8, 2), (4, 4), (6, 1)])
def test_paged_decode_attention_grouped_queries(h, h_kv):
    rng = np.random.default_rng(h * 10 + h_kv)
    n, d, ps, pps = 3, 16, 4, 6
    n_pages = 1 + n * pps
    q = jnp.asarray(rng.normal(size=(n, h, d)), jnp.float32)
    kp, vp = (jnp.asarray(rng.normal(size=(n_pages, ps, h_kv, d)),
                          jnp.float32) for _ in range(2))
    tables = jnp.asarray(1 + rng.permutation(n * pps).reshape(n, pps),
                         jnp.int32)
    pos = jnp.asarray([0, 9, 23], jnp.int32)
    got = paged_decode_attention(q, kp, vp, tables, pos, 0.25, ps,
                                 interpret=True)
    assert kp.shape[2] == h_kv             # the pool is never widened
    for i in range(n):
        lane_k = kp[tables[i]].reshape(-1, h_kv, d)
        lane_v = vp[tables[i]].reshape(-1, h_kv, d)
        want = _dense_repeated(q[i:i + 1], lane_k, lane_v, pos[i:i + 1],
                               0.25)
        assert np.abs(np.asarray(got[i]) - np.asarray(want[0])).max() < 2e-6


@pytest.mark.parametrize("h,h_kv,s,lane,offset", [
    (8, 2, 48, 48, None), (8, 2, 32, 160, 64), (4, 4, 40, 200, 128),
    (4, 1, 130, 130, None)])
def test_flash_prefill_attention_grouped_queries(h, h_kv, s, lane, offset):
    """A tile of queries at ``offset`` over a longer lane of K/V rows
    with fewer heads, against dense attention with K/V repeated."""
    rng = np.random.default_rng(s + lane)
    d = 16
    q = jnp.asarray(rng.normal(size=(1, s, h, d)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(1, lane, h_kv, d)), jnp.float32)
            for _ in range(2))
    got = flash_prefill_attention(
        q, k, v, 0.25, interpret=True,
        q_offset=None if offset is None else jnp.int32(offset))
    want = _dense_repeated(q[0], k[0], v[0], (offset or 0) + jnp.arange(s),
                           0.25)
    assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() < 2e-6


def test_equal_heads_give_the_parents_outputs():
    """With ``H_kv == H`` both kernels are the programs they always
    were: bit for bit the outputs the parent commit (e3868d6) gives for
    these inputs, in interpret mode (the digests were taken from its
    tree), and ``flash_prefill_attention`` is ``flash_attention``."""
    rng = np.random.default_rng(33)
    n, h, d, ps, pps, n_pages = 3, 4, 16, 4, 6, 19
    q = jnp.asarray(rng.normal(size=(n, h, d)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(n_pages, ps, h, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n_pages, ps, h, d)), jnp.float32)
    tables = jnp.asarray(1 + rng.permutation(n_pages - 1)[:n * pps]
                         .reshape(n, pps), jnp.int32)
    pos = jnp.asarray([0, 9, 23], jnp.int32)
    fq, fk, fv = (jnp.asarray(rng.normal(size=(1, 160, h, d)), jnp.float32)
                  for _ in range(3))
    paged = np.asarray(paged_decode_attention(q, kp, vp, tables, pos, 0.25,
                                              ps, interpret=True))
    flash = np.asarray(flash_prefill_attention(fq, fk, fv, interpret=True))
    assert hashlib.sha256(paged.tobytes()).hexdigest() == (
        "dec81cd857bd2c17f45fb8c60e0b78d87f6577eb713d9ca26cb37832e5df3502")
    assert hashlib.sha256(flash.tobytes()).hexdigest() == (
        "1949829fc5643d0cc69f0161996eefa28452aa01bcf7088e5bf3cdf53ad11b8e")
    assert np.array_equal(flash, np.asarray(
        flash_attention(fq, fk, fv, True, None, True)))


def test_decoder_on_interpreted_kernels_matches_xla(params, reference):
    """The two programs with the Pallas kernels interpreted (grouped
    queries over the paged lane, a tile over the lane so far) against
    the dense programs."""
    dec = decoder(params, attn_impl="pallas_interpret")
    got = served_logits(dec, 1, TOKENS[:44], 37)
    assert np.abs(got - reference[36:44]).max() < 2e-6


# ---------------------------------------------------------------------------
# the decoder's surface


def test_the_configuration_picks_the_decoder(params):
    dec = decoder(params)
    assert isinstance(dec, HybridDecoder)
    assert dec.warmup() == 2 and dec.n_state_resets == 0
    assert dec.rows_at(20) == (0, 21) and dec.pages_for(20) == (0, 6)
    assert dec.prefill_facts(37) == {"tiles": 3, "prompt_tokens": 37}
    assert dec.has_slot_state and not dec.has_draft \
        and not dec.has_prefix_prefill


def test_prefix_cache_and_speculation_refuse(params):
    for kw in ({"prefix_cache": True}, {"draft_params": params}):
        with pytest.raises(ValueError, match="snapshot"):
            decoder(params, **kw)


def test_config_from_hf_keys():
    import json
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "granite-4.0-h-small.json")) as f:
        hf = json.load(f)
    hf = dict(hf, num_local_experts=hf["num_routed_experts"])
    cfg = GH.GraniteHybridConfig.from_hf(
        hf, experts_held=range(*hf["experts_held"]))
    assert cfg.layer_types == ("mamba",) * 5 + ("attention",) \
        + ("mamba",) * 4
    assert (cfg.n_experts, cfg.top_k, len(cfg.experts_held)) == (72, 10, 36)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.d_head) == (32, 8, 128)
    assert (cfg.d_inner, cfg.d_conv_in, cfg.ssm_state) == (8192, 8448, 128)
    assert cfg.index_in_kind(5) == 0 and cfg.index_in_kind(6) == 5
    with pytest.raises(ValueError, match="experts_held"):
        GH.GraniteHybridConfig(experts_held=(0, 0))
