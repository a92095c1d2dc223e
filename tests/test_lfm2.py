"""LFM2's layer as a configuration of the hybrid block
(models/granite_hybrid.py, serving/hybrid_decode.py), at a small size on
the CPU: ``benchmark/configs/tiny-lfm2.json`` (d_model 64, six layers
conv conv attention conv conv attention, the first two dense FFN layers
of width 96, 4 query heads x 16 over 2 K/V heads with QK-norm and a
rotary of base 1e4, 8 experts of width 24, top 2 by sigmoid score plus a
selection bias, no shared expert, vocab 96). The yardstick is
benchmark/reference_lfm2.py: plain jax.numpy, weights from a seed, the
convolution a position at a time, one full forward from zeros.

Tolerances, and why. In float32 under ``default_matmul_precision
("highest")`` program and reference compute the same sums in another
order (tiles from a cache, a grouped product, a masked product); logits
are of order 0.2, the observed gap is under 1e-7, and ``TOL`` leaves
some ten times that. A fault of either mechanism moves logits by over
1e-2 (``test_faults_move_the_logits``), some ten thousand times
``TOL``.
"""

import dataclasses
import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
import reference_granite as RG  # noqa: E402
import reference_lfm2 as RL  # noqa: E402

from mmlspark_tpu.models import granite_hybrid as GH  # noqa: E402
from mmlspark_tpu.parallel.pallas_attention import (  # noqa: E402
    paged_decode_attention, paged_head_lanes, paged_walks)
from mmlspark_tpu.serving.decode import decoder_for  # noqa: E402
from mmlspark_tpu.serving.hybrid_decode import HybridDecoder  # noqa: E402

TOL = 1e-6
SEED = 7


def _config_file(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name)) as f:
        return json.load(f)


HF = _config_file("tiny-lfm2.json")
M = RL.Model.from_config(HF)
CFG = GH.GraniteHybridConfig.from_hf(HF, dtype="float32")
PS, TILE, N_SLOTS, MAX_LEN = 4, 16, 3, 64
TOKENS = np.random.default_rng(1).integers(0, 96, size=60).astype(np.int32)


def config(m=M):
    """The program's configuration of a reference model ``m``."""
    hf = dict(HF, num_key_value_heads=m.n_kv_heads,
              num_attention_heads=m.n_heads, head_dim=m.d_head)
    return GH.GraniteHybridConfig.from_hf(
        hf, experts_held=m.experts_held, dtype="float32")


@pytest.fixture(scope="module", autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def params():
    return RL.make_params(M, SEED)


@pytest.fixture(scope="module")
def reference():
    return RL.logits(M, SEED, TOKENS)


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
# the configuration


def test_config_from_hf_keys():
    """The cell's file read by the program's own ``from_hf``: LFM2's
    layer is a configuration of the hybrid block."""
    hf = _config_file("lfm2-24b-a2b.json")
    cfg = GH.GraniteHybridConfig.from_hf(hf)
    assert cfg.block_kind == "granite_hybrid"
    assert cfg.layer_types == ("conv", "conv", "attention", "conv", "conv",
                               "conv", "attention", "conv", "conv", "conv")
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.d_head) == (32, 8, 64)
    assert (cfg.n_experts, cfg.top_k, len(cfg.experts_held)) == (64, 4, 64)
    assert (cfg.d_expert, cfg.d_dense, cfg.n_dense_layers) == (1536, 11776, 2)
    assert (cfg.router, cfg.expert_bias, cfg.norm_topk) == ("sigmoid", True,
                                                            True)
    assert (cfg.d_shared, cfg.conv_width, cfg.rope_theta) == (0, 3, 1e6)
    assert cfg.qk_norm and cfg.attention_multiplier == 0.125
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling) == (1.0, 1.0, 1.0)
    assert cfg.index_in_kind(6) == 1 and cfg.index_in_kind(9) == 7
    # the granite defaults: a softmax router, a shared expert, no rotary
    g = GH.GraniteHybridConfig()
    assert (g.router, g.n_dense_layers, g.rope_theta, g.qk_norm) == (
        "softmax", 0, 0.0, False) and g.d_shared == 1536
    with pytest.raises(ValueError, match="router"):
        GH.GraniteHybridConfig(router="relu")
    with pytest.raises(ValueError, match="d_dense"):
        GH.GraniteHybridConfig(n_dense_layers=1)


def test_params_and_counts_at_the_cells_sizes():
    """The cut's parameters by hand (ISSUE 39): two dense conv layers
    of 89.1 M, two attention + MoE of 614.6 M, six conv + MoE of
    620.9 M, 5.13 B in the layers and 5.27 B with the tied embedding:
    10.53 GB in bfloat16; and the program's own layout at these sizes
    holds the same count."""
    m = RL.Model.from_config(_config_file("lfm2-24b-a2b.json"))
    d = 2048
    conv, attn = 4 * d * d + 3 * d, 2 * d * 40 * 64 + 2 * 64
    moe = d * 64 + 64 + 64 * 3 * d * 1536
    layers = (2 * (conv + 3 * d * 11776) + 2 * (attn + moe)
              + 6 * (conv + moe) + 10 * 2 * d)
    assert RL.n_params(m) == layers + 65536 * d + d
    assert 5.13e9 < layers < 5.14e9
    assert 10.52e9 < 2 * RL.n_params(m) < 10.54e9
    shapes = jax.eval_shape(lambda: GH.init_params(
        GH.GraniteHybridConfig.from_hf(_config_file("lfm2-24b-a2b.json")),
        0))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) \
        == RL.n_params(m)


def test_stacked_experts_are_the_experts_one_at_a_time():
    """The program's weights stack a layer's experts in one draw; the
    reference makes them an expert at a time: the same values."""
    full = RL.make_params(M, SEED)
    for e in (0, 5):
        w_in, w_out = RL.expert_params(M, SEED, 3, e)
        assert np.array_equal(np.asarray(full["blocks"][3]["w_in_e"][e]),
                              np.asarray(w_in))
        assert np.array_equal(np.asarray(full["blocks"][3]["w_out_e"][e]),
                              np.asarray(w_out))
    assert "router" not in full["blocks"][1] and "w_in_d" in full["blocks"][1]
    assert "w_in_s" not in full["blocks"][3]


# ---------------------------------------------------------------------------
# the model against the reference


def test_forward_matches_reference(params, reference):
    got = np.asarray(GH.forward_logits(params, jnp.asarray(TOKENS), CFG))
    assert np.abs(got - reference).max() < TOL
    assert np.abs(reference).max() > 0.05


@pytest.mark.parametrize("fault", RL.FAULTS)
def test_faults_move_the_logits(reference, fault):
    """What the benchmark plants in the reference is far outside
    ``TOL``: the comparison sees the conv tail and the bias's
    selections."""
    moved = RL.logits(M, SEED, TOKENS, fault=fault, reset_every=16)
    assert np.abs(moved - reference).max() > 1e-2


@pytest.mark.parametrize("prompt_len", [11, 16, 17, 37, 48],
                         ids=["inside_a_tile", "a_tiles_edge", "one_past",
                              "past_two_tiles", "three_tiles_edge"])
def test_prefill_then_decode_matches_reference(params, reference,
                                               prompt_len):
    """The conv tail carried from tile to tile (tiles of 16, smaller
    than the prompt) and from step to step, the K/V rows appended to
    pages with the rotary at their positions, against one forward from
    zeros."""
    dec = decoder(params)
    got = served_logits(dec, 1, TOKENS, prompt_len)
    assert np.abs(got - reference[prompt_len - 1:]).max() < TOL
    assert dec.n_compiles() == 2
    assert dec.cache["tail"][0].shape == (N_SLOTS, 2, 64)
    assert set(dec.cache) == {"ssm", "conv", "tail", "k", "v"}
    assert not dec.cache["ssm"] and not dec.cache["conv"]


def test_a_requests_first_tile_resets_the_tail(params, reference):
    """The second request in a slot reads as if it were alone, and the
    first really left a tail behind."""
    dec = decoder(params)
    served_logits(dec, 2, TOKENS[::-1].copy(), 30)
    assert np.abs(np.asarray(dec.cache["tail"][0][2])).max() > 0
    got = served_logits(dec, 2, TOKENS, 21)
    assert np.abs(got - reference[20:]).max() < TOL
    assert dec.n_state_resets == 2


def test_padded_positions_leave_the_tail_bit_identical(params):
    """A tile's padding shifts no row into the tail, whatever the
    padded tokens are; a tile with no real position leaves it as it
    was."""
    prefill = GH.build_hybrid_prefill(CFG, PS, donate=False)
    table = jnp.asarray(1 + np.arange(MAX_LEN // PS, dtype=np.int32))
    cache = GH.init_cache(CFG, N_SLOTS, 1 + N_SLOTS * MAX_LEN // PS, PS)
    tile = np.zeros(TILE, np.int32)
    tile[:9] = TOKENS[:9]
    other = tile.copy()
    other[9:] = TOKENS[20:27]

    def after(tokens, cache, pos0, length):
        return prefill(params, cache, jnp.asarray(tokens), table,
                       np.int32(1), np.int32(pos0), np.int32(length))[0]

    a, b = after(tile, cache, 0, 9), after(other, cache, 0, 9)
    for x, y in zip(a["tail"], b["tail"]):
        assert np.array_equal(np.asarray(x), np.asarray(y))
        assert np.abs(np.asarray(x[1])).max() > 0
    c = after(other, a, TILE, 0)
    for x, y in zip(a["tail"], c["tail"]):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_greedy_does_not_repeat_the_input_token(params):
    """With the tied head and ``embed_std`` 0.02 a position's own token
    is not its best next token (granite's collapse, its ``embed_std``)."""
    logits = np.asarray(GH.forward_logits(params, jnp.asarray(TOKENS), CFG))
    assert (logits.argmax(-1) == TOKENS).mean() < 0.2


# ---------------------------------------------------------------------------
# the router, the dense layers, QK-norm and the rotary


def _layer_input(seed=3, n=40):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(n, 64)),
                       jnp.float32)


def test_the_bias_chooses_but_does_not_weigh():
    """``top_k`` of ``s + b``, weighted by ``s`` over its chosen sum +
    1e-6: the bias changes some selections, and a chosen expert's
    weight is its unbiased score however it was chosen."""
    h = _layer_input()
    lp = RL.layer_params(M, SEED, 3)
    u = GH._norm(h, lp["norm2"], CFG)
    logits = u @ lp["router"]
    idx, w = GH.sigmoid_route(logits, lp["expert_bias"], CFG)
    idx0, w0 = GH.sigmoid_route(logits, None, CFG)
    idx, w, idx0, w0 = map(np.asarray, (idx, w, idx0, w0))
    s = np.asarray(jax.nn.sigmoid(logits))
    changed = (np.sort(idx, -1) != np.sort(idx0, -1)).any(-1)
    assert 0.05 < changed.mean() < 0.95
    want = np.take_along_axis(s, idx, -1)
    want = want / (want.sum(-1, keepdims=True) + 1e-6)
    assert np.abs(w - want).max() < 1e-7
    # where the choice is the same, so are the weights
    same = ~changed
    assert np.abs(np.sort(w[same], -1) - np.sort(w0[same], -1)).max() < 1e-7
    # the +1e-6: weights sum to a hair under one
    assert np.all(w.sum(-1) < 1.0) and np.all(w.sum(-1) > 1.0 - 1e-5)
    # and the reference agrees on ids and weights
    r_idx, r_w = RL.routes(M, SEED, 3, h)
    assert (np.sort(r_idx, -1) == np.sort(idx, -1)).all()
    assert np.abs(np.sort(r_w, -1) - np.sort(w, -1)).max() < 1e-7
    b_idx, _ = RL.routes(M, SEED, 3, h, ignore_bias=True)
    assert (np.sort(b_idx, -1) == np.sort(idx0, -1)).all()


def test_leading_dense_layers(params):
    """Layers 0 and 1 are one gated product of width 96 with no router;
    a step counts routings of the MoE layers only."""
    h = _layer_input()
    for layer in (0, 1):
        bp = params["blocks"][layer]
        out, r, t = GH._ffn(bp, layer, h, CFG, grouped=True)
        assert r is None and t is None
        want = RL._dense(bp, h, M, "highest") - h
        assert np.abs(np.asarray(out) - np.asarray(want)).max() < 1e-6
        assert bp["w_in_d"].shape == (64, 192)
    dec = decoder(params)
    tok = np.zeros(N_SLOTS, np.int32)
    tok[0] = dec.prefill(0, TOKENS[:9])
    at = np.zeros(N_SLOTS, np.int32)
    at[0] = 9
    dec.step_logits(tok, at)
    assert dec.expert_routings.sum() == 1 * M.top_k * M.n_moe == 8


def test_qk_norm_and_rotary(params):
    """The program's q and k are the reference's: normed per head with
    their own gains, then turned at their positions; the turn keeps a
    vector's length and makes q.k a function of the positions'
    difference only."""
    bp = params["blocks"][2]
    assert not np.allclose(np.asarray(bp["q_norm"]), 1.0)
    u = GH._norm(_layer_input(n=12), bp["norm1"], CFG)
    pos = jnp.arange(12) * 5
    q, k, _ = GH._qkv(bp, u, CFG, pos)
    q_ref = RL._rotary(RL._norm(jnp.einsum("td,dhk->thk", u, bp["wq"]),
                                bp["q_norm"], 1e-5), pos, 1e4)
    k_ref = RL._rotary(RL._norm(jnp.einsum("td,dhk->thk", u, bp["wk"]),
                                bp["k_norm"], 1e-5), pos, 1e4)
    assert np.abs(np.asarray(q) - np.asarray(q_ref)).max() < 1e-5
    assert np.abs(np.asarray(k) - np.asarray(k_ref)).max() < 1e-5
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 3, 16)),
                    jnp.float32)
    y = jnp.asarray(np.random.default_rng(3).normal(size=(1, 3, 16)),
                    jnp.float32)

    def dot(m, n):
        return np.asarray(jnp.sum(GH.rope(x, jnp.array([m]), 1e4)
                                  * GH.rope(y, jnp.array([n]), 1e4), -1))

    assert np.abs(dot(7, 3) - dot(104, 100)).max() < 1e-4
    assert np.abs(dot(7, 3) - dot(3, 7)).max() > 1e-3
    turned = GH.rope(x, jnp.array([50]), 1e4)
    assert np.allclose(np.linalg.norm(turned), np.linalg.norm(x), rtol=1e-6)


@pytest.mark.parametrize("grouped", [True, False],
                         ids=["grouped", "masked"])
def test_the_shares_add_up(grouped):
    """Experts 0-3 on one chip and 4-7 on the other: with no shared
    expert, the two routed parts are the uncut layer of the
    reference."""
    h = _layer_input()
    lp = RL.make_params(M, SEED)["blocks"][4]
    parts, counts = [], 0
    for held in (range(0, 4), range(4, 8)):
        m = dataclasses.replace(M, experts_held=tuple(held))
        share = dict(lp, w_in_e=lp["w_in_e"][np.array(list(held))],
                     w_out_e=lp["w_out_e"][np.array(list(held))])
        out, routings, _ = GH.moe(share, h, config(m), grouped=grouped)
        parts.append(np.asarray(out))
        counts += int(np.asarray(routings).sum())
    ref = RL._experts(M, SEED, 4, RL.layer_params(M, SEED, 4), h,
                      "highest", False)
    ref = np.asarray(ref - h)
    assert np.abs(parts[0] + parts[1] - ref).max() < 1e-5
    assert counts == h.shape[0] * M.top_k


# ---------------------------------------------------------------------------
# head_dim 64 in the paged kernel: the pool padded to 128 lanes


def test_paged_decode_attention_at_head_dim_64_walks_and_is_exact():
    """Eight K/V heads of 64 kept zero-padded to 128 lanes take the
    walk (not one grid step a table entry); the kernel (interpret mode)
    over the padded pool, its output's first 64 lanes, equals the dense
    gather of the unpadded rows over each slot's live walk."""
    assert paged_head_lanes(64, 8) == 128 and paged_walks(128, 8)
    assert not paged_walks(64, 8)
    assert paged_head_lanes(128, 8) == 128 and paged_head_lanes(16, 2) == 16
    rng = np.random.default_rng(64)
    n, h, h_kv, d, ps, pps = 3, 32, 8, 64, 16, 6
    n_pages = 1 + n * pps
    q = jnp.asarray(rng.normal(size=(n, h, d)), jnp.float32)
    kp, vp = (jnp.asarray(rng.normal(size=(n_pages, ps, h_kv, d)),
                          jnp.float32) for _ in range(2))
    tables = jnp.asarray(1 + rng.permutation(n * pps).reshape(n, pps),
                         jnp.int32)
    pos = jnp.asarray([0, 37, 90], jnp.int32)
    got = paged_decode_attention(GH._lanes(q, 128), GH._lanes(kp, 128),
                                 GH._lanes(vp, 128), tables, pos, 0.125,
                                 ps, interpret=True)
    assert got.shape == (n, h, 128)
    assert np.abs(np.asarray(got[..., 64:])).max() == 0.0
    for i in range(n):
        live = int(pos[i]) + 1
        lane_k = kp[tables[i]].reshape(-1, h_kv, d)[:live]
        lane_v = vp[tables[i]].reshape(-1, h_kv, d)[:live]
        want = GH.attention_dense(q[i:i + 1], lane_k, lane_v,
                                  pos[i:i + 1], 0.125)
        assert np.abs(np.asarray(got[i, :, :64])
                      - np.asarray(want[0])).max() < 2e-6


def test_decoder_on_interpreted_kernels_pads_the_pool_and_matches():
    """Eight K/V heads (heads of 8 lanes, padded to 128): the decoder
    keeps the pool padded, walks it in the step and reads it unpadded
    in the tile, and gives the dense programs' logits."""
    m = dataclasses.replace(M, n_heads=8, n_kv_heads=8, d_head=8)
    cfg = config(m)
    p = RL.make_params(m, SEED)
    want = RL.logits(m, SEED, TOKENS[:30])
    dec = decoder(p, cfg, attn_impl="pallas_interpret")
    assert dec.cache["k"][0].shape[-1] == 128
    got = served_logits(dec, 1, TOKENS[:30], 21)
    assert np.abs(got - want[20:30]).max() < 2e-6


# ---------------------------------------------------------------------------
# the decoder's surface, and granite unchanged


def test_the_configuration_picks_the_decoder(params):
    dec = decoder(params)
    assert isinstance(dec, HybridDecoder)
    assert dec.warmup() == 2 and dec.n_state_resets == 0
    assert dec.has_slot_state and not dec.has_prefix_prefill
    assert int(dec.expert_routings.sum()) == 0


GRANITE_DIGEST = (
    "130e8ede8c419d137af820776dfd1fc42656852cbdef8478388e415f27965936")


def test_granite_gives_the_parents_logits_bit_for_bit():
    """granite's tiny configuration through the plain forward, a tile
    walk and decode steps: bit for bit the logits the parent commit
    (ffebf75) gives (the digest was taken from its tree); retaken when
    the tile's grouped expert rows came to be summed a token at a time
    instead of scattered in expert order, which moved the logits by at
    most 5.6e-9 of 0.022."""
    m = dataclasses.replace(RG.Model(
        vocab=96, d_model=64, n_heads=4, n_kv_heads=2, d_head=16,
        layer_types=("mamba", "mamba", "attention", "mamba"), n_experts=8,
        top_k=3, experts_held=(0, 1, 2, 3), d_expert=24, d_shared=48,
        ssm_heads=8, ssm_head_dim=16, ssm_state=16, ssm_conv=4,
        ssm_chunk=8, embedding_multiplier=12.0, residual_multiplier=0.22,
        attention_multiplier=0.0625, logits_scaling=16.0, norm_eps=1e-5,
        embed_std=0.01))
    cfg = GH.GraniteHybridConfig(**dataclasses.asdict(m), dtype="float32")
    p = RG.make_params(m, 5)
    fwd = np.asarray(GH.forward_logits(p, jnp.asarray(TOKENS), cfg))
    dec = decoder(p, cfg)
    served = served_logits(dec, 1, TOKENS[:30], 21)
    digest = hashlib.sha256(fwd.tobytes() + served.tobytes()).hexdigest()
    assert digest == GRANITE_DIGEST
