"""A looped softmax stack on the decode plane (``TransformerConfig.recipe``
with ``n_loops`` > 1: models/transformer.py ``_decode_layers``,
serving/decode.py ``TransformerDecoder``), at a small size on the CPU:
2 layers x 3 passes, d_model 64, 4 heads x 16, gated SiLU FFN 96,
sandwich norms, half-split rotary at base 1e6, vocab 128, pages of 4
rows. The yardstick is benchmark/reference_ouro.py: plain jax.numpy,
weights from a seed, one full forward with no cache.

Tolerances, and why. In float32 under ``default_matmul_precision
("highest")`` program and reference compute the same sums in another
order (rows from a cache, a ``fori_loop`` over the passes); logits are
of unit size, the observed gap is 1.9e-6, and ``TOL`` leaves five times
that. A fault of the mechanism moves a logit by over 0.5
(``test_faults_of_the_mechanism_read_as_a_mismatch``). In bfloat16 the
operands, the weights and the K/V rows are rounded (2^-9 relative a
product, through 12 sublayers): the observed gap is 0.023 at the worst
logit (0.006 rms), ``TOL_BF16`` leaves 2.6 times that, and the reference
with every operand rounded to 8 bits (the benchmark's control) lies
three times past it, at 0.19 (0.039 rms)
(``test_the_int8_control_fails_the_bfloat16_tolerance``).
"""

import http.client
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
import reference_ouro as RO  # noqa: E402

from mmlspark_tpu.core.stage import Transformer  # noqa: E402
from mmlspark_tpu.core.tracing import TRACER  # noqa: E402
from mmlspark_tpu.models import transformer as T  # noqa: E402
from mmlspark_tpu.serving import (  # noqa: E402
    DecodeScheduler, ServingServer, TransformerDecoder)
from mmlspark_tpu.serving.decode import decoder_for, pass_view  # noqa: E402

TOL = 1e-5
TOL_BF16 = 0.06
SEED = 7
HF = {"model_type": "ouro", "vocab_size": 128, "hidden_size": 64,
      "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
      "intermediate_size": 96, "num_hidden_layers": 2,
      "total_ut_steps": 3, "early_exit_threshold": 1,
      "rope_theta": 1000000, "rms_norm_eps": 1e-6, "hidden_act": "silu",
      "rope_scaling": None, "sliding_window": None,
      "tie_word_embeddings": False,
      "init": {"embed_std": 1.0, "post_norm_gain": 0.3}}
M = RO.Model.from_config(HF)
CFG = T.TransformerConfig.from_hf(HF, dtype="float32")
PS, N_SLOTS, MAX_LEN = 4, 3, 48
TOKENS = np.random.default_rng(0).integers(0, 128, size=40).astype(np.int32)


@pytest.fixture(scope="module", autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def params():
    return RO.make_params(M, SEED)


@pytest.fixture(scope="module")
def reference():
    """``(logits [S, vocab], gates [passes, S])`` of the full forward."""
    return RO.logits_and_gates(M, SEED, TOKENS)


def decoder(params, cfg=CFG, **kw):
    kw = dict(dict(n_slots=N_SLOTS, max_len=MAX_LEN, page_size=PS,
                   attn_impl="dense"), **kw)
    return decoder_for(params, cfg, **kw)


def served(dec, slot, tokens, prompt_len):
    """Prefill ``tokens[:prompt_len]`` into ``slot`` by its bucket, then
    feed the rest a step at a time through the (pass, layer) cache:
    the logits and the expected exit pass at every position from
    ``prompt_len - 1`` on."""
    _, last = dec.prefill_logits(slot, tokens[:prompt_len])
    logits, exits = [np.asarray(last)], [dec.prefill_exit_pass]
    for pos in range(prompt_len, len(tokens)):
        tok = np.zeros(dec.n_slots, np.int32)
        at = np.zeros(dec.n_slots, np.int32)
        tok[slot], at[slot] = tokens[pos], pos
        _, lg = dec.step_logits(tok, at)
        logits.append(np.asarray(lg[slot]))
        exits.append(float(dec.step_exit_pass[slot]))
    return np.stack(logits), np.asarray(exits)


# ---------------------------------------------------------------------------
# (a) prefill by bucket, then decoding through the paged cache


@pytest.mark.parametrize("prompt_len", [3, 4, 9, 16, 23],
                         ids=["inside_a_page", "a_pages_edge",
                              "past_two_pages", "a_buckets_edge",
                              "past_five_pages"])
def test_prefill_then_decode_matches_reference(params, reference,
                                               prompt_len):
    dec = decoder(params)
    assert isinstance(dec, TransformerDecoder)
    got, exits = served(dec, 1, TOKENS, prompt_len)
    want, lam = reference
    assert np.abs(got - want[prompt_len - 1:]).max() < TOL
    # the gate is part of the forward: prefill's and every step's
    # expected exit pass are the reference's
    p = RO.exit_distribution(lam[:, prompt_len - 1:])
    expect = (np.arange(1, M.n_loops + 1)[:, None] * p).sum(axis=0)
    assert np.abs(exits - expect).max() < 1e-4
    assert 1.0 < exits.min() and exits.max() < M.n_loops


@pytest.mark.parametrize("prompt_len", [3, 9, 16])
def test_bfloat16_programs_stay_near_the_float32_reference(reference,
                                                           prompt_len):
    cfg = T.TransformerConfig.from_hf(HF, dtype="bfloat16")
    dec = decoder(RO.make_params(M, SEED, jnp.bfloat16), cfg)
    got, _ = served(dec, 0, TOKENS, prompt_len)
    gap = np.abs(got - reference[0][prompt_len - 1:]).max()
    assert 1e-3 < gap < TOL_BF16        # rounded, and not by much
    assert got.dtype == np.float32      # the logits stay float32
    assert {x.dtype for x in dec.cache["k"] + dec.cache["v"]} \
        == {jnp.dtype(jnp.bfloat16)}


def test_the_int8_control_fails_the_bfloat16_tolerance(reference):
    """What the benchmark's control computes (every matmul operand
    rounded to 8 bits) lies outside the tolerance bfloat16 is held
    to."""
    low, _ = RO.logits_and_gates(M, SEED, TOKENS, "int8")
    assert np.abs(low - reference[0]).max() > 2 * TOL_BF16
    bf16, _ = RO.logits_and_gates(M, SEED, TOKENS, "bfloat16")
    assert np.abs(bf16 - reference[0]).max() < TOL_BF16


def test_decoder_on_interpreted_kernels_matches_xla():
    """The flash prefill and the paged decode kernel (head_dim 128:
    the form that walks a slot's live table entries) under page tables
    shifted a pass, against the dense gather."""
    hf = dict(HF, num_attention_heads=1, num_key_value_heads=1,
              head_dim=128, total_ut_steps=2)
    m, cfg = RO.Model.from_config(hf), T.TransformerConfig.from_hf(
        hf, dtype="float32")
    prm = RO.make_params(m, SEED)
    got = {impl: served(decoder(prm, cfg, n_slots=2, attn_impl=impl), 1,
                        TOKENS[:14], 6)[0]
           for impl in ("dense", "pallas_interpret")}
    assert np.abs(got["dense"] - got["pallas_interpret"]).max() < TOL
    want, _ = RO.logits_and_gates(m, SEED, TOKENS[:14])
    assert np.abs(got["pallas_interpret"] - want[5:]).max() < TOL


# ---------------------------------------------------------------------------
# (b) the exit gate


def test_exit_distribution_sums_to_one_and_is_the_references(reference):
    lam = reference[1]
    p = np.asarray(T.exit_distribution(jnp.asarray(lam)))
    assert np.abs(p.sum(axis=0) - 1.0).max() < 1e-6
    assert np.abs(p - RO.exit_distribution(lam)).max() < 1e-6
    assert (p > 0).all() and 0.02 < lam.min() and lam.max() < 0.98


def test_threshold_one_takes_the_last_pass(reference):
    lam = reference[1]
    assert (RO.exit_pass(lam, 1.0) == M.n_loops).all()
    assert (RO.exit_pass(lam, 0.5) < M.n_loops).any()


@pytest.mark.parametrize("build", [
    lambda cfg: T.init_paged_kv_cache(cfg, 9, PS),
    lambda cfg: T.build_paged_decode_step(cfg, 2, PS, 4),
    lambda cfg: T.build_paged_prefill(cfg, PS, 4)],
    ids=["pool", "step", "prefill"])
def test_a_threshold_under_one_refuses(build):
    cfg = T.TransformerConfig.from_hf(dict(HF, early_exit_threshold=0.9),
                                      dtype="float32")
    with pytest.raises(NotImplementedError, match="ROADMAP B12"):
        build(cfg)


# ---------------------------------------------------------------------------
# (c) one pass of the default recipe is today's program; what is not
# built refuses by name


def test_a_step_takes_the_tokens_of_the_step_before_on_the_device(
        params):
    """A looped step's one fetch packs the exit passes behind its
    tokens; the tokens are an output of their own too, and a step
    dispatched on them before they are fetched gives what a step on
    the fetched tokens gives."""
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
    assert (a.step_exit_pass == b.step_exit_pass).all()
    assert a._step._cache_size() == 1


def test_one_loop_with_the_default_recipe_is_the_unlooped_program():
    cfg = T.TransformerConfig(vocab=64, d_model=16, n_heads=2, d_head=8,
                              d_ff=32, n_stages=1, layers_per_stage=1)
    assert cfg.recipe == T.BlockRecipe() and cfg.recipe.n_loops == 1
    dec = decoder_for(T.init_params(cfg, seed=0), cfg, n_slots=2,
                      max_len=16, page_size=4)
    assert dec._step.__name__ == "step" and dec.n_loops == 1
    out, _ = dec.step_logits(np.zeros(2, np.int32), np.zeros(2, np.int32))
    assert out.shape == (2,) and dec.step_exit_pass is None
    assert dec.cache["k"][0].shape[0] == dec.n_pages
    assert dec.cache["k"][0].dtype == jnp.float32
    looped = decoder(RO.make_params(M, SEED))
    assert looped._step.__name__ == "looped_step"
    assert looped._prefill.__name__ == "looped_prefill"


@pytest.mark.parametrize("what,build", [
    ("unpaged", lambda: T.build_decode_step(CFG, 2, 16)),
    ("unpaged", lambda: T.build_prefill(CFG)),
    ("unpaged", lambda: T.init_kv_cache(CFG, 2, 16)),
    ("unpaged", lambda: T.build_draft_propose(CFG, 2, 16, 2)),
    ("unpaged", lambda: T.build_paged_verify_step(CFG, 2, 2, PS, 4)),
    ("default block recipe", lambda: T.decode_param_specs(
        CFG, jax.sharding.Mesh(np.array(jax.devices()[:1]), ("model",)))),
    ("default block recipe", lambda: T.build_spmd_train_step(
        CFG, jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",)))),
    ("dense ReLU-MLP", lambda: T.quantize_decode_ffn({}, CFG)),
], ids=["step", "prefill", "lanes", "draft", "verify", "tensor_parallel",
        "train", "int8_ffn"])
def test_what_the_recipe_does_not_reach_refuses_by_name(what, build):
    with pytest.raises(NotImplementedError, match=what):
        build()


def test_speculation_refuses_and_unknown_recipes_do(params):
    with pytest.raises(ValueError, match="ROADMAP B12"):
        decoder(params, draft_params=params, draft_cfg=CFG)
    with pytest.raises(ValueError, match="recipe ffn"):
        T.BlockRecipe(ffn="gelu")
    with pytest.raises(ValueError, match="no recipe for model_type"):
        T.TransformerConfig.from_hf(dict(HF, model_type="gpt_neox"))
    with pytest.raises(ValueError, match="prompt_buckets"):
        decoder(params, prompt_buckets=[6, 16])


@pytest.mark.parametrize("layout,base", [
    ("half", 1e6), ("half", 1e4), ("interleaved", 1e4),
    ("interleaved", 1e6)])
def test_rotary_by_the_recipe(layout, base):
    """The recipe's rotary (layout, base) against the pairs written
    out: column ``i`` pairs with ``i + Dh / 2`` (half) or ``i + 1``
    (interleaved, even ``i``), over the whole head."""
    rng = np.random.default_rng(3)
    x = rng.normal(size=(5, 2, 16)).astype(np.float32)
    pos = np.array([0, 1, 7, 300, 4000])
    got = np.asarray(T._rope_at(jnp.asarray(x), jnp.asarray(pos),
                                T.BlockRecipe(rope_layout=layout,
                                              rope_base=base)))
    want = x.astype(np.float64).copy()
    for i in range(8):
        a, b = (i, i + 8) if layout == "half" else (2 * i, 2 * i + 1)
        ang = pos[:, None] * base ** (-2.0 * i / 16)
        want[..., a] = x[..., a] * np.cos(ang) - x[..., b] * np.sin(ang)
        want[..., b] = x[..., b] * np.cos(ang) + x[..., a] * np.sin(ang)
    assert np.abs(got - want).max() < 2e-4      # float32 angles to 4000


# ---------------------------------------------------------------------------
# (d) the cache is keyed by pass


@pytest.mark.parametrize("fault", RO.FAULTS)
def test_faults_of_the_mechanism_read_as_a_mismatch(params, reference,
                                                    fault):
    """What the benchmark plants in the reference (one pass fewer; every
    pass attending the first pass's rows) is far outside the tolerance
    the program is held to: the comparison sees every pass, and the
    rows each pass keeps for itself."""
    moved, _ = RO.logits_and_gates(M, SEED, TOKENS, fault=fault)
    got, _ = served(decoder(params), 2, TOKENS, 9)
    assert np.abs(got - moved[8:]).max() > 0.5
    assert np.abs(got - reference[0][8:]).max() < TOL


def test_each_pass_writes_rows_of_its_own(params):
    """After a prompt of 9 tokens the slot's three pages hold rows in
    every pass's part of a layer's pool, no two alike."""
    dec = decoder(params)
    dec.prefill(0, TOKENS[:9])
    pool = np.asarray(dec.cache["k"][0])
    assert pool.shape[0] == M.n_loops * dec.n_pages
    first = dec._identity_tables[0][0]
    rows = [pool[first + t * dec.n_pages] for t in range(M.n_loops)]
    assert all(np.abs(r).max() > 0 for r in rows)
    assert np.abs(rows[0] - rows[1]).max() > 1e-3
    assert np.abs(rows[1] - rows[2]).max() > 1e-3


# ---------------------------------------------------------------------------
# (e) through DecodeScheduler and POST /generate?stream=1


class Identity(Transformer):
    def transform(self, df):
        return df


def _stream(host, port, prompt, n_new):
    conn = http.client.HTTPConnection(host, port, timeout=60)
    conn.request("POST", "/generate?stream=1", json.dumps(
        {"prompt": [int(t) for t in prompt], "max_new_tokens": n_new}),
        {"Content-Type": "application/json"})
    resp = conn.getresponse()
    body = resp.read()
    conn.close()
    events = [json.loads(e.split(b"data: ", 1)[1])
              for e in body.split(b"\n\n") if e.strip()]
    return resp.status, events


def _post(host, port, prompt):
    """A plain POST: the status and the body as sent."""
    conn = http.client.HTTPConnection(host, port, timeout=60)
    conn.request("POST", "/generate?stream=1", json.dumps(
        {"prompt": [int(t) for t in prompt], "max_new_tokens": 2}),
        {"Content-Type": "application/json"})
    resp = conn.getresponse()
    body = resp.read()
    conn.close()
    return resp.status, body


def _greedy(prompt, n_new):
    """The reference's greedy continuation, a full forward a token."""
    seq = list(prompt)
    for _ in range(n_new):
        lg, _ = RO.logits_and_gates(M, SEED, np.asarray(seq, np.int32))
        seq.append(int(lg[-1].argmax()))
    return seq[len(prompt):]


@pytest.fixture(scope="module")
def generated(params):
    """A prompt past the prefill ladder, then two requests of unlike
    lengths at once through the server, and everything the cases below
    look at."""
    dec = decoder(params, prompt_buckets=[4, 8, 16], prefix_cache=False)
    # on a thread of its own, as the loop's is: the module's matmul
    # precision is this thread's alone and is part of a program's key
    warmed = []
    t = threading.Thread(target=lambda: warmed.append(dec.warmup()))
    t.start()
    t.join()
    warm, = warmed
    sched = DecodeScheduler(dec, max_new_tokens_default=8)
    claimed = []
    claim = sched._claim_pages
    sched._claim_pages = lambda n: claimed.append(n) or claim(n)
    t_from = TRACER.recorder.scan("decode.pass", 0.0, 1e18)
    requests = [(TOKENS[:5], 14), (TOKENS[10:23], 6)]
    replies = [None, None]
    with ServingServer(Identity(), port=0, decoder=sched,
                       max_latency_ms=1.0,
                       verify_checkpoints=False) as srv:
        # 20 tokens: inside max_len (48), past the ladder's top (16)
        refused = _post(srv.host, srv.port, TOKENS[:20])

        def go(i):
            replies[i] = _stream(srv.host, srv.port, *requests[i])
        threads = [threading.Thread(target=go, args=(i,)) for i in (0, 1)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        stats = sched.stats()
    passes = [pass_view(sp.attrs["phases"]) for sp in
              TRACER.recorder.scan("decode.pass", 0.0, 1e18)[len(t_from):]]
    return dict(dec=dec, sched=sched, warm=warm, claimed=claimed,
                requests=requests, replies=replies, stats=stats,
                passes=passes, refused=refused)


@pytest.mark.parametrize("i", [0, 1])
def test_streamed_tokens_are_the_references_greedy_tokens(generated, i):
    status, events = generated["replies"][i]
    prompt, n_new = generated["requests"][i]
    final = [e for e in events if e.get("done")][0]
    assert status == 200 and final["finish_reason"] == "length"
    assert [e["token"] for e in events if "done" not in e] \
        == final["tokens"] == _greedy(prompt, n_new)


def test_a_prompt_past_the_ladder_is_a_400_and_the_loop_serves_on(
        generated):
    """``max_len`` 48 under a ladder that ends at 16: a prompt of 20
    tokens is refused at the edge, with the limit named, and claims
    nothing; the two requests after it are served (above)."""
    status, body = generated["refused"]
    assert status == 400
    assert b"prompt length 20 > 16" in body
    assert generated["stats"]["max_prompt"] == generated["dec"].max_prompt \
        == 16
    assert generated["stats"]["n_requests"] == 2
    assert generated["stats"]["pages"]["high_water"] > 0   # they ran


@pytest.mark.parametrize("what", ["bucket_of", "prefill_facts"])
def test_a_refusal_inside_a_prefill_fails_the_request_not_the_loop(
        params, what):
    """What the decoder says of a prefill is read inside the prefill's
    own guard: a refusal there is that request's 500, its slot and
    pages come back, and the next request is served."""
    dec = decoder(params, prefix_cache=False)
    real, calls = getattr(dec, what), []

    def once(*args):
        calls.append(args)
        if len(calls) == 1:
            raise ValueError("refused here")
        return real(*args)
    setattr(dec, what, once)
    sched = DecodeScheduler(dec, max_new_tokens_default=2).start()
    try:
        done = []
        for _ in range(2):
            p = _pending({"prompt": [int(t) for t in TOKENS[:5]]})
            sched.submit(p)
            assert p.event.wait(120)
            done.append((p.status, json.loads(p.reply)))
        assert done[0][0] == 500 and "refused here" in done[0][1]["error"]
        assert done[1][0] == 200 and done[1][1]["tokens"] \
            == _greedy(TOKENS[:5], 2)
        assert sched._thread.is_alive()
        assert sched.pool.n_free == N_SLOTS
        assert sched.pages.n_free == sched.pages.n_pages - 1
    finally:
        sched.stop()


def test_pages_count_a_position_once_whatever_the_loops(generated):
    """A request claims ceil(rows / page_size) pages; the pool's bytes
    are ``n_loops`` times a single pass's; every page comes back."""
    dec, sched = generated["dec"], generated["sched"]
    per_request = sorted(-(-(len(p) + n) // PS)
                         for p, n in generated["requests"])
    got = generated["claimed"]
    # a prefill claims the prompt's pages and the first row's; growth
    # claims one page at a time
    assert sum(got) == sum(per_request)
    assert generated["stats"]["pages"]["high_water"] <= sum(per_request)
    assert sched.pages.n_free == sched.pages.n_pages - 1
    assert sched.pages.n_pages == dec.n_pages == 1 + N_SLOTS * MAX_LEN // PS
    once = T.init_paged_kv_cache(
        T.TransformerConfig.from_hf(dict(HF, total_ut_steps=1),
                                    dtype="float32"), dec.n_pages, PS)
    nbytes = lambda c: sum(x.nbytes for x in c["k"] + c["v"])  # noqa: E731
    assert generated["stats"]["pages"]["pool_bytes"] == nbytes(dec.cache) \
        == M.n_loops * nbytes(once)


def test_compiles_stay_flat_after_warmup(generated):
    assert generated["warm"] == 4           # the step and three buckets
    assert generated["stats"]["n_compiles"] == generated["warm"]
    assert generated["stats"]["n_step_faults"] == 0


def test_stats_say_the_loops_and_what_a_position_costs(generated):
    stats = generated["stats"]
    assert stats["n_loops"] == M.n_loops
    assert stats["kv_bytes_per_position"] \
        == M.n_loops * M.n_layers * 2 * M.n_heads * M.d_head * 4
    # the unlooped float32 block reads a layer's K and V row once
    cfg = T.TransformerConfig(vocab=64, d_model=16, n_heads=2, d_head=8,
                              d_ff=32, n_stages=1, layers_per_stage=3)
    dec = decoder_for(T.init_params(cfg, seed=0), cfg, n_slots=2,
                      max_len=16, page_size=4)
    assert dec.kv_bytes_per_position == 3 * 2 * 2 * 8 * 4


def test_spans_carry_the_loops_the_exit_pass_and_the_bucket(generated):
    passes = generated["passes"]
    steps = [p for p in passes if "exit_pass_mean" in p]
    assert len(steps) >= 13
    for p in steps:
        assert p["loops"] == M.n_loops and p["window_rows"] > 0
        assert 1.0 < p["exit_pass_mean"] < M.n_loops
    prefills = [q for p in passes for q in p["prefills"]]
    assert sorted((q["prompt_tokens"], q["bucket"], q["loops"])
                  for q in prefills) == [(5, 8, 3), (13, 16, 3)]


def test_a_shared_prefix_is_shared_in_every_pass(params):
    """The prefix cache under a looped stack: a page id names a
    position's rows in every pass, so the second request attends the
    first's rows pass by pass and decodes what a cold prefill would."""
    dec = decoder(params)
    sched = DecodeScheduler(dec, prefix_cache=True,
                            max_new_tokens_default=4).start()
    try:
        outs = []
        for tail in (TOKENS[20:23], TOKENS[30:35]):
            prompt = [int(t) for t in np.concatenate([TOKENS[:12], tail])]
            p = _pending({"prompt": prompt, "max_new_tokens": 5})
            sched.submit(p)
            assert p.event.wait(120)
            outs.append((prompt, json.loads(p.reply)))
        cache = sched.stats()["prefix_cache"]
        assert cache["hits"] == 1 and cache["hit_tokens"] == 12
        assert cache["ledger_clean"]
        for prompt, out in outs:
            assert out["tokens"] == _greedy(prompt, 5)
    finally:
        sched.stop()


def _pending(payload, rid="looped"):
    class Pending:
        """The slice of the server's pending request the standalone
        scheduler touches."""
        def __init__(self):
            self.payload, self.rid, self.trace = payload, rid, rid
            self.deadline = self.reply = self.span = None
            self.event, self.callbacks = threading.Event(), []
            self.status = 200
    return Pending()
