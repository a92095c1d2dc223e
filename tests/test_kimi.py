"""Kimi-K2's language model (DeepSeek-V3's layer) as a configuration of
the hybrid block: every layer multi-head latent attention (``"mla"``)
over a paged latent cache (models/granite_hybrid.py, serving/
hybrid_decode.py, parallel/pallas_mla.py), at a small size on the CPU:
``benchmark/configs/tiny-kimi.json`` (d_model 256, 4 heads, q_lora 96,
kv_lora 64, d_nope 32, d_rope 16, d_v 32, YaRN as published, 32 routed
experts of which 8 are held, top 4 by sigmoid score plus a selection
bias, one shared expert, one dense layer of width 192 and two MoE
layers, vocab 512). The yardstick is benchmark/reference_kimi.py: plain
jax.numpy, weights from a seed, the attention expanded, one full forward.

Tolerances, and why. In float32 under ``default_matmul_precision
("highest")`` program and reference compute the same sums in another
order (tiles from a cache, the absorbed products, an online softmax, a
grouped product); logits are of order 4, the observed gap is under
2e-5, and ``TOL`` leaves some five times that. The planted faults move
logits by over 1e-1 (``test_faults_move_the_logits``), some thousand
times ``TOL``.
"""

import dataclasses
import json
import math
import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
import reference_kimi as RK  # noqa: E402

from mmlspark_tpu.models import granite_hybrid as GH  # noqa: E402
from mmlspark_tpu.parallel import pallas_mla as PM  # noqa: E402
from mmlspark_tpu.serving.decode import decoder_for  # noqa: E402
from mmlspark_tpu.serving.hybrid_decode import HybridDecoder  # noqa: E402

TOL = 1e-4
SEED = 11


def _config_file(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name)) as f:
        return json.load(f)


HF = _config_file("tiny-kimi.json")
M = RK.Model.from_config(HF)
PS, TILE, N_SLOTS, MAX_LEN = 4, 16, 3, 64
TOKENS = np.random.default_rng(1).integers(0, 512, size=60).astype(np.int32)


def config(m=M):
    """The program's configuration of a reference model ``m``."""
    return GH.GraniteHybridConfig.from_hf(
        RK.program_hf(HF), experts_held=m.experts_held, dtype="float32")


CFG = config()


@pytest.fixture(scope="module", autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def params():
    return RK.make_params(M, SEED)


@pytest.fixture(scope="module")
def reference():
    return RK.logits(M, SEED, TOKENS)


def decoder(params, **kw):
    kw = dict(dict(n_slots=N_SLOTS, max_len=MAX_LEN, page_size=PS,
                   prefill_tile=TILE, attn_impl="dense"), **kw)
    return decoder_for(params, CFG, **kw)


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
    """The cell's file read by the program's own ``from_hf``: Kimi-K2's
    layer is a configuration of the hybrid block, its widths as
    published, the router over all 384 experts and 12 of them held."""
    hf = _config_file("kimi-k2.6.json")
    m = RK.Model.from_config(hf)
    cfg = GH.GraniteHybridConfig.from_hf(RK.program_hf(hf),
                                         experts_held=m.experts_held)
    assert cfg.block_kind == "granite_hybrid"
    assert cfg.layer_types == ("mla",) * 6 and cfg.n_dense_layers == 1
    assert (cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank) == (64, 1536, 512)
    assert (cfg.d_nope, cfg.d_rope, cfg.d_v, cfg.latent_lanes) == (
        128, 64, 128, 576)
    assert (cfg.n_experts, cfg.top_k, cfg.d_expert, cfg.d_shared) == (
        384, 8, 2048, 2048)
    assert cfg.experts_held == tuple(range(0, 384, 32))
    assert (cfg.router, cfg.expert_bias, cfg.route_eps) == ("sigmoid", True,
                                                            1e-20)
    assert cfg.routed_scaling == 2.827 and not cfg.tied_head
    assert (cfg.vocab, cfg.d_model, cfg.d_dense) == (20480, 7168, 18432)
    assert hf["n_routed_experts"] == len(m.experts_held) == 12
    # the parent's kinds keep their normaliser
    assert GH.GraniteHybridConfig().route_eps == 1e-6
    with pytest.raises(ValueError, match="group"):
        GH.GraniteHybridConfig.from_hf(dict(RK.program_hf(hf), n_group=8))
    with pytest.raises(ValueError, match="mla"):
        GH.GraniteHybridConfig(layer_types=("mla",))


def test_params_at_the_cells_sizes():
    """The cut's parameters by hand: MLA 101.1 M a layer, an MoE layer's
    non-routed part 147.9 M and a routed expert 44.0 M, the dense layer
    497.5 M: 8.35 GB of bfloat16 weights with the eighth of the untied
    vocabulary; the program's own layout holds the same count."""
    hf = _config_file("kimi-k2.6.json")
    m = RK.Model.from_config(hf)
    d = 7168
    mla = (d * 1536 + 1536 + 1536 * 64 * 192 + d * 576 + 512
           + 512 * 64 * 256 + 64 * 128 * d)
    assert 101.0e6 < mla < 101.2e6
    moe = d * 384 + 384 + 3 * d * 2048 + 12 * 3 * d * 2048
    assert 147.8e6 < mla + d * 384 + 3 * d * 2048 < 148.0e6
    assert RK.n_params(m) == (2 * 20480 * d + d + 6 * (2 * d + mla)
                              + 3 * d * 18432 + 5 * moe)
    assert 8.34e9 < 2 * RK.n_params(m) < 8.36e9
    cfg = GH.GraniteHybridConfig.from_hf(RK.program_hf(hf),
                                         experts_held=m.experts_held)
    shapes = jax.eval_shape(lambda: GH.init_params(cfg, 0))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) \
        == RK.n_params(m)
    assert shapes["head"].shape == (d, 20480)


# ---------------------------------------------------------------------------
# (a) prefill tiles then decode steps against the reference's forward


def test_forward_matches_reference(params, reference):
    got = np.asarray(jax.jit(lambda p, t: GH.forward_logits(p, t, CFG))(
        params, jnp.asarray(TOKENS)))
    assert np.abs(got - reference).max() < TOL
    assert np.abs(reference).max() > 1.0


@pytest.mark.parametrize("impl", ["dense", "pallas_interpret"])
@pytest.mark.parametrize("prompt_len", [13, 37], ids=["one_tile",
                                                      "three_tiles"])
def test_prefill_then_decode_matches_reference(params, reference, impl,
                                               prompt_len):
    """Tiles of 16 expand the lane so far (a chunk a tile), the steps
    score the latent rows absorbed, under the dense twin and the kernel
    in interpret mode: the logits of the reference's one forward."""
    dec = decoder(params, attn_impl=impl)
    got = served_logits(dec, 1, TOKENS, prompt_len)
    assert np.abs(got - reference[prompt_len - 1:]).max() < TOL
    assert dec.n_compiles() == 2
    assert set(dec.cache) == {"ssm", "conv", "k", "v", "lat"}
    assert len(dec.cache["lat"]) == 3 and not dec.cache["k"]
    lanes = 128 if impl != "dense" else 80
    assert dec.cache["lat"][0].shape == (1 + N_SLOTS * MAX_LEN // PS, PS,
                                         lanes)


@pytest.mark.parametrize("fault", RK.FAULTS)
def test_faults_move_the_logits(reference, fault):
    """What the benchmark plants in the reference is far outside
    ``TOL``: the comparison sees the bias's selections and the shared
    expert."""
    moved = RK.logits(M, SEED, TOKENS, fault=fault)
    assert np.abs(moved - reference).max() > 1e-1


# ---------------------------------------------------------------------------
# (b) the absorbed and the expanded forms


def test_absorbed_equals_expanded(params):
    """One layer over a lane of 29 cached rows: the step's absorbed
    form (W_UK folded into the query, W_UV after the sum of latents)
    and the tile's expanded form (the lane up through W_UK / W_UV to
    per-head keys and values) give the same output for the query at
    the lane's end."""
    rng = np.random.default_rng(5)
    bp = params["blocks"][1]
    n_pos = 29
    u = jnp.asarray(rng.normal(size=(n_pos, 256)), jnp.float32)
    pos = jnp.arange(n_pos)
    rows = GH._mla_rows(bp, u, pos, CFG)
    pps = 16
    pool = jnp.zeros((1 + pps, PS, CFG.latent_lanes), jnp.float32)
    table = jnp.arange(1, 1 + pps, dtype=jnp.int32)
    pool = pool.at[table[:8]].set(jnp.pad(rows, ((0, 3), (0, 0))).reshape(
        8, PS, -1))
    expanded = jax.jit(lambda bp, u, lane: GH._mla_attend(
        bp, u, pos, lambda j: lane[:n_pos], 1, CFG))(
            bp, u, pool[table].reshape(-1, 80))[-1]
    absorbed, _ = jax.jit(lambda bp, u, pool: GH.mla_step(
        bp, u[-1:], pos[-1:], pool, table[(n_pos - 1) // PS][None],
        jnp.asarray([(n_pos - 1) % PS]), table[None], CFG, PS, "dense"))(
            bp, u, pool)
    assert np.abs(np.asarray(absorbed[0]) - np.asarray(expanded)).max() \
        < 1e-4
    assert np.abs(np.asarray(expanded)).max() > 0.1


@pytest.mark.parametrize("n_chunks", [1, 2, 4])
def test_tile_kernel_equals_the_xla_chunks(params, n_chunks):
    """A tile of 16 queries over its lane: ``latent_tile_attention``
    (interpret mode) expanding and scoring the chunks in one call gives
    the XLA chunk loop's output, for the tile at the lane's start, one
    chunk in, and three; the rows past the tile's own chunk hold NaN
    and are not read."""
    rng = np.random.default_rng(6 + n_chunks)
    bp = params["blocks"][1]
    u = jnp.asarray(rng.normal(size=(TILE, 256)), jnp.float32)
    q_pos = (n_chunks - 1) * TILE + jnp.arange(TILE)
    lane = rng.normal(size=(MAX_LEN, CFG.latent_lanes)).astype(np.float32)
    lane[n_chunks * TILE:] = np.nan
    lane = jnp.asarray(lane)
    xla = GH._mla_attend(
        bp, u, q_pos,
        lambda j: jax.lax.dynamic_slice_in_dim(lane, j * TILE, TILE),
        n_chunks, CFG)
    kernel = GH._mla_attend_kernel(bp, u, q_pos, lane, jnp.int32(n_chunks),
                                   CFG, True)
    assert np.isfinite(np.asarray(kernel)).all()
    assert np.abs(np.asarray(kernel) - np.asarray(xla)).max() < TOL
    assert np.abs(np.asarray(xla)).max() > 0.1


# ---------------------------------------------------------------------------
# (c) YaRN and the scale


def test_yarn_frequencies_and_scale_are_the_closed_forms():
    """At the cell's settings (d_rope 64, base 5e4, factor 64, 4096,
    beta 32 / 1): low 8, high 20, the ramp between, m = 0.1 ln 64 + 1 =
    1.41589 and the scale 192 ** -0.5 * m ** 2; the reference's
    frequencies are the program's."""
    hf = _config_file("kimi-k2.6.json")
    cfg = GH.GraniteHybridConfig.from_hf(RK.program_hf(hf))
    d, base = 64, 50000.0
    low = math.floor(d * math.log(4096 / (32 * 2 * math.pi))
                     / (2 * math.log(base)))
    high = math.ceil(d * math.log(4096 / (2 * math.pi))
                     / (2 * math.log(base)))
    assert (low, high) == (8, 20)
    want = []
    for i in range(32):
        extra = base ** (-2 * i / d)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(extra / 64 * ramp + extra * (1 - ramp))
    got = GH.yarn_inv_freq(cfg)
    assert np.allclose(got, want, rtol=1e-6)
    assert got[:9].tolist() == pytest.approx(
        [base ** (-2 * i / d) for i in range(9)], rel=1e-6)
    assert got[20:].tolist() == pytest.approx(
        [base ** (-2 * i / d) / 64 for i in range(20, 32)], rel=1e-6)
    ref = np.asarray(RK.inv_freq(RK.Model.from_config(hf)))
    assert np.allclose(ref, got, rtol=1e-6)
    m = 0.1 * math.log(64) + 1
    assert m == pytest.approx(1.41589, abs=1e-5)
    assert m * m == pytest.approx(2.00474, abs=1e-5)
    assert cfg.attention_multiplier == pytest.approx(192 ** -0.5 * m * m)
    assert RK.softmax_scale(RK.Model.from_config(hf)) == pytest.approx(
        cfg.attention_multiplier)
    assert GH.yarn_mscale(64, 1) / GH.yarn_mscale(64, 1) == 1.0


def test_rotary_layout_and_relative_positions():
    """The program's turn is the reference's (de-interleaved pairs,
    then rotate_half), keeps a vector's length, and makes a score a
    function of the positions' difference only. The program rounds its
    frequencies to float32 from float64, the reference computes them in
    float32: at position 12000 an angle differs by some 1e-4 radians."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(5, 3, 16)), jnp.float32)
    pos = jnp.asarray([0, 7, 100, 4000, 12000])
    got = GH.yarn_rope(x, pos, CFG)
    assert np.abs(np.asarray(got)
                  - np.asarray(RK.rope(x, pos, M))).max() < 2e-4
    assert np.allclose(np.linalg.norm(np.asarray(got), axis=-1),
                       np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5)
    a, b = x[:1], x[1:2]

    def dot(i, j):
        return np.asarray(jnp.sum(GH.yarn_rope(a, jnp.array([i]), CFG)
                                  * GH.yarn_rope(b, jnp.array([j]), CFG), -1))

    assert np.abs(dot(7, 3) - dot(504, 500)).max() < 1e-3
    assert np.abs(dot(7, 3) - dot(3, 7)).max() > 1e-3


# ---------------------------------------------------------------------------
# (d) the router


def _layer_input(seed=3, n=40):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(n, 256)),
                       jnp.float32)


def test_the_bias_chooses_but_does_not_weigh():
    """``top 4`` of ``s + b``, weighted by ``s`` over its chosen sum +
    1e-20, times 2.827: the bias changes some selections, and a chosen
    expert's weight is its unbiased score however it was chosen."""
    h = _layer_input()
    lp = RK.layer_params(M, SEED, 1)
    u = GH._norm(h, lp["norm2"], CFG)
    logits = u @ lp["router"]
    idx, w = GH.sigmoid_route(logits, lp["expert_bias"], CFG)
    idx0, w0 = GH.sigmoid_route(logits, None, CFG)
    idx, w, idx0, w0 = map(np.asarray, (idx, w, idx0, w0))
    s = np.asarray(jax.nn.sigmoid(logits))
    changed = (np.sort(idx, -1) != np.sort(idx0, -1)).any(-1)
    assert 0.05 < changed.mean()
    want = np.take_along_axis(s, idx, -1)
    want = want / (want.sum(-1, keepdims=True) + 1e-20) * 2.827
    assert np.abs(w - want).max() < 1e-6
    same = ~changed
    assert np.abs(np.sort(w[same], -1) - np.sort(w0[same], -1)).max() < 1e-6
    # no 1e-6 in the normaliser: the weights sum to 2.827
    assert np.abs(w.sum(-1) - 2.827).max() < 1e-5
    r_idx, r_w = RK.routes(M, SEED, 1, h)
    assert (np.sort(r_idx, -1) == np.sort(idx, -1)).all()
    held = np.isin(idx, M.experts_held)
    assert np.abs(np.sort(r_w, -1)
                  - np.sort(np.where(held, w, 0.0), -1)).max() < 1e-6
    b_idx, _ = RK.routes(M, SEED, 1, h, ignore_bias=True)
    assert (np.sort(b_idx, -1) == np.sort(idx0, -1)).all()


# ---------------------------------------------------------------------------
# (e) the shares add up


@pytest.mark.parametrize("grouped", [True, False], ids=["grouped", "masked"])
def test_the_shares_add_up(grouped):
    """Four chips holding 8 experts each (ids ``k, k + 4, ...``): their
    routed parts, with the shared expert counted once, are the
    reference's layer with all 32 held."""
    h = _layer_input()
    lp = RK.layer_params(M, SEED, 2)
    parts = []
    for k in range(4):
        held = tuple(range(k, 32, 4))
        m = dataclasses.replace(M, experts_held=held)
        bp = dict(lp, **RK._held_experts(m, RK._layer_key(SEED, 2),
                                         "float32"))
        cfg = dataclasses.replace(CFG, experts_held=held, d_shared=0)
        out, routings, _ = GH.moe(bp, h, cfg, grouped=grouped)
        parts.append(np.asarray(out))
    shared = GH._gated(GH._norm(h, lp["norm2"], CFG), lp["w_in_s"],
                       lp["w_out_s"], jnp.float32)
    whole = RK.layer_forward(M, SEED, 2, h, held=range(32),
                             with_mixer=False)
    assert np.abs(sum(parts) + np.asarray(shared)
                  - np.asarray(whole)).max() < 1e-5
    mine = RK.layer_forward(M, SEED, 2, h, with_mixer=False)
    assert np.abs(np.asarray(mine) - np.asarray(whole)).max() > 1e-2


# ---------------------------------------------------------------------------
# (f) the kernel against its dense twin


def _within(seconds, fn):
    """``fn()`` on a thread of its own, failing after ``seconds``: the
    TPU interpreter blocks for ever on a wait that no copy signals."""
    out = []

    def run():
        try:
            out.append((True, fn()))
        except Exception as e:  # noqa: BLE001 — raised below
            out.append((False, e))

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(seconds)
    assert out, f"no result in {seconds} s: a wait that no copy signals"
    ok, value = out[0]
    if not ok:
        raise value
    return value


@pytest.mark.parametrize("pos", [
    [0, 15, 16], [31, 32, 47], [63, 1, 48], [15, 31, 63], [16, 32, 48],
    [7, 0, 3], [15, 23, 47, 4, 31, 50, 0]],
    ids=["free_slot_and_a_page_edge", "a_fetch_edge", "the_last_row",
         "walks_end_on_fetch_edges", "one_row_past_a_fetch_edge",
         "one_page_and_a_free_slot", "last_fetches_full_and_partial_in_turn"])
def test_latent_kernel_equals_dense_twin(pos):
    """The walk, 2 pages a fetch of 8 rows, against the dense gather:
    at page and fetch boundaries, with a free slot on the scratch page,
    and with NaN in every row past each slot's position and in the
    unclaimed pages. A full fetch's copies go out as straight-line code
    and are waited for once, a partial one's a page at a time; the
    last case turns the buffers over with slots whose last fetch is
    full and partial in turn, so that the next slot's first fetch is
    started into either buffer, full or not. Run by the TPU
    interpreter, which makes a copy when it is waited for: a wait that
    covers fewer bytes than the copies leaves rows of NaN behind, and
    one on a semaphore that no copy signals never returns."""
    from jax.experimental.pallas import tpu as pltpu
    rng = np.random.default_rng(7)
    pos = np.asarray(pos, np.int32)
    n, h, lanes, d_lat, ps, pps = len(pos), 8, 256, 128, 8, 8
    n_pages = 1 + n * pps
    q = jnp.asarray(rng.normal(size=(n, h, lanes)), jnp.float32)
    pool = np.full((n_pages, ps, lanes), np.nan, np.float32)
    tables = (1 + rng.permutation(n * pps).reshape(n, pps)).astype(np.int32)
    tables[pos == 0] = 0
    for i in range(n):
        lane = rng.normal(size=(int(pos[i]) + 1, lanes))
        for r in range(int(pos[i]) + 1):
            pool[tables[i, r // ps], r % ps] = lane[r]
    args = (jnp.asarray(q), jnp.asarray(pool), jnp.asarray(tables),
            jnp.asarray(pos), 0.3)
    old = PM._LATENT_VMEM_BUDGET
    PM._LATENT_VMEM_BUDGET = 4 * ps * lanes * 4
    try:
        assert PM.latent_fetch_pages(pps, ps, lanes, jnp.float32) == 2
        got = _within(60, lambda: PM.paged_latent_decode_attention(
            *args, page_size=ps, d_lat=d_lat,
            interpret=pltpu.InterpretParams(detect_races=True)))
    finally:
        PM._LATENT_VMEM_BUDGET = old
    clean = np.nan_to_num(pool)
    want = PM.latent_decode_attention_dense(
        args[0], jnp.asarray(clean), args[2], args[3], 0.3, d_lat)
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    assert got.shape == (n, h, d_lat)


# ---------------------------------------------------------------------------
# (g) end to end through the scheduler


class _Pending:
    def __init__(self, payload, rid):
        self.payload, self.rid = payload, rid
        self.deadline = self.span = self.reply = None
        self.event = threading.Event()
        self.callbacks = []
        self.status = 200
        self.trace = rid


@pytest.fixture
def two_page_fetches(monkeypatch):
    """The latent walk copies 2 table entries (8 rows) at a time, so
    that a lane of 64 rows makes full and partial fetches; the kernel is
    traced afresh under it, and again after it."""
    monkeypatch.setattr(PM, "_LATENT_VMEM_BUDGET", 4 * PS * PM.LANE * 4)
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("impl", ["dense", "pallas_interpret"])
def test_the_scheduler_serves_it(params, impl, request):
    """``decoder_for`` gives a ``HybridDecoder`` with no state a slot;
    two requests through ``DecodeScheduler`` at once come back with
    tokens that are the reference's greedy choice at every position
    (the served token's reference logit is the best, within ``TOL``),
    and every page comes back. Where the latent kernel walks the
    pages, every step's ``decode.prepare`` counts its fetches a layer,
    and the sum over the steps is the hand count; the dense twin
    counts none."""
    from mmlspark_tpu.core.tracing import Tracer
    from mmlspark_tpu.serving.decode import DecodeScheduler, pass_view
    if impl == "pallas_interpret":
        request.getfixturevalue("two_page_fetches")
    dec = decoder(params, attn_impl=impl)
    assert isinstance(dec, HybridDecoder)
    assert not dec.has_slot_state and not dec.has_prefix_prefill
    assert dec.warmup() == 2
    assert dec.prefill_facts(37) == {"tiles": 3, "prompt_tokens": 37,
                                     "latent_rows_expanded": 96}
    tracer = Tracer()
    sched = DecodeScheduler(dec, tracer=tracer).start()
    prompts, news = [TOKENS[:21], TOKENS[30:39]], [9, 12]
    pend = [_Pending({"prompt": [int(t) for t in p], "max_new_tokens": n},
                     f"r{i}") for i, (p, n) in enumerate(zip(prompts, news))]
    try:
        for p in pend:
            sched.submit(p)
        for p in pend:
            assert p.event.wait(120)
        stats = sched.stats()
    finally:
        sched.stop()
    for prompt, n, p in zip(prompts, news, pend):
        out = json.loads(p.reply)["tokens"]
        assert len(out) == n
        seq = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
        want = RK.logits(M, SEED, seq)[len(prompt) - 1:]
        served = want[np.arange(n), out]
        assert (want.max(axis=-1) - served).max() < TOL
    assert stats["slots_free"] == N_SLOTS
    assert stats["pages"]["free"] == dec.n_pages - 1
    assert int(dec.expert_routings.sum()) > 0
    # a request of prompt P and n tokens steps at positions P .. P+n-2,
    # reading pos // 4 + 1 entries, 2 a fetch: 21..28 read 6,6,6,7,7,7,
    # 7,8 (29 fetches, 25 full); 9..19 read 3,3,3,4,4,4,4,5,5,5,5 (26,
    # 19). With a slot free, no step runs ahead.
    steps = [v for v in (pass_view(sp.attrs["phases"])
                         for sp in tracer.recorder.scan("decode.pass"))
             if "dispatch" in v["phases_ms"]]
    assert len(steps) >= max(news) - 1
    fetches = [(v.get("latent_fetches"), v.get("latent_fetches_full"))
               for v in steps]
    if impl == "dense":
        assert set(fetches) == {(None, None)}
    else:
        assert [sum(f) for f in zip(*fetches)] == [29 + 26, 25 + 19]
