"""The decode step's walk over the touched experts
(``parallel/pallas_moe.py`` ``touched_experts``) against the masked form
it stands in for (``models/granite_hybrid.py`` ``masked_experts``), in
interpret mode on the CPU, at the expert shapes of the two tiny hybrid
configurations (LFM2's: 8 experts of width 24, all held, no shared
expert; granite's: 4 of 8 held, width 24, a shared expert of 48), and
at a shape whose experts take two copies each.

Tolerances, and why. Kernel and masked form take the same operands in
the same precision and differ only in the order of the float32 sums
(an expert and a chunk at a time against one product over all held
experts): outputs of order one, sums of up to 2,048 products, agree to
some 2e-6, and ``TOL`` leaves five times that. A dropped or doubled
expert moves them by order one.
"""

import dataclasses
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

from mmlspark_tpu.core.profiling import collect  # noqa: E402
from mmlspark_tpu.models import granite_hybrid as GH  # noqa: E402
from mmlspark_tpu.parallel.pallas_moe import (  # noqa: E402
    expert_chunk, touched_experts)
from mmlspark_tpu.serving.decode import decoder_for  # noqa: E402

TOL = 1e-5

# (slots, d_model, expert width, experts held, chunk)
SHAPES = {"lfm2": (3, 64, 24, 8, None), "granite": (3, 64, 24, 4, None),
          "two_chunks": (8, 128, 256, 4, 128)}


@pytest.fixture(scope="module", autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _touched(case: str, e: int):
    return sorted({"none": set(), "one": {e // 2},
                   "some": {0, e // 2 + 1, e - 1},
                   "all": set(range(e))}[case])


def _operands(shape, touched, seed=0, dtype=jnp.float32):
    """Weights and inputs at ``shape``; slot 1 is free (a zero row of
    ``weight``), every other slot routed to every expert of
    ``touched``; ``ids`` lists the touched experts first, as ``moe``
    does."""
    t, d, f, e, _ = shape
    rng = np.random.default_rng(seed)
    u = jnp.asarray(rng.normal(size=(t, d)), dtype)
    w_in = jnp.asarray(rng.normal(size=(e, d, 2 * f)) * d ** -0.5, dtype)
    w_out = jnp.asarray(rng.normal(size=(e, f, d)) * f ** -0.5, dtype)
    weight = np.zeros((t, e), np.float32)
    weight[:, touched] = rng.uniform(0.05, 1.0, size=(t, len(touched)))
    weight[1] = 0.0
    routings = (weight > 0).sum(0)
    ids = jnp.argsort(jnp.asarray(routings) == 0, stable=True)
    return u, jnp.asarray(weight), w_in, w_out, ids, len(touched)


@pytest.mark.parametrize("case", ["none", "one", "some", "all"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_walk_matches_the_masked_form(shape, case):
    """0, 1, some and all held experts touched, with a free slot: the
    walk gives the masked form's output; a free slot's row is zero."""
    s = SHAPES[shape]
    u, weight, w_in, w_out, ids, n = _operands(s, _touched(case, s[3]))
    got = np.asarray(touched_experts(u, weight, w_in, w_out, ids,
                                     jnp.int32(n), interpret=True,
                                     chunk=s[4]))
    want = np.asarray(GH.masked_experts(u, weight, w_in, w_out))
    assert got.shape == want.shape and got.dtype == np.float32
    assert np.abs(got - want).max() < TOL
    assert np.abs(got[1]).max() == 0.0
    assert (np.abs(got).max() > 0.1) == (n > 0)


@pytest.mark.parametrize("shape", list(SHAPES))
def test_untouched_experts_are_never_read(shape):
    """Untouched experts' weights full of NaN leave the walk's output
    finite and the masked form's over clean weights; the masked form
    itself turns NaN (so the NaN was there to be read)."""
    s = SHAPES[shape]
    touched = _touched("some", s[3])
    u, weight, w_in, w_out, ids, n = _operands(s, touched, seed=1)
    away = np.setdiff1d(np.arange(s[3]), touched)
    nan_in = w_in.at[away].set(jnp.nan)
    nan_out = w_out.at[away].set(jnp.nan)
    got = np.asarray(touched_experts(u, weight, nan_in, nan_out, ids,
                                     jnp.int32(n), interpret=True,
                                     chunk=s[4]))
    want = np.asarray(GH.masked_experts(u, weight, w_in, w_out))
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < TOL
    assert np.isnan(np.asarray(
        GH.masked_experts(u, weight, nan_in, nan_out))).any()


def test_bfloat16_operands_round_where_the_masked_form_does():
    """In bfloat16 the walk rounds what the masked form rounds (u, the
    weights, the weighted gate before ``W_out``): the two agree to the
    rounding of that gate, a bfloat16 step of 2^-8 relative."""
    s = SHAPES["two_chunks"]
    u, weight, w_in, w_out, ids, n = _operands(
        s, _touched("some", s[3]), seed=2, dtype=jnp.bfloat16)
    got = np.asarray(touched_experts(u, weight, w_in, w_out, ids,
                                     jnp.int32(n), interpret=True,
                                     chunk=s[4]))
    want = np.asarray(GH.masked_experts(u, weight, w_in, w_out))
    scale = np.abs(want).max()
    assert np.abs(got - want).max() < 1e-2 * scale


def test_chunks_at_the_cells_shapes():
    """Three copies of 3.1 MB a chunk at both cells' expert shapes
    (LFM2 D 2048 F 1536; granite D 4096 F 768; bfloat16); a width that
    is not whole lane registers is one chunk."""
    assert expert_chunk(2048, 1536, 2) == 768
    assert expert_chunk(4096, 768, 2) == 384
    assert expert_chunk(64, 24, 4) == 24
    with pytest.raises(ValueError, match="divide"):
        touched_experts(*_operands(SHAPES["two_chunks"], [0])[:5],
                        jnp.int32(1), interpret=True, chunk=96)


# ---------------------------------------------------------------------------
# the expert layer and the decoder on the walk


def _lfm2():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "tiny-lfm2.json")) as f:
        hf = json.load(f)
    params = RL.make_params(RL.Model.from_config(hf), 7)
    return (GH.GraniteHybridConfig.from_hf(hf, dtype="float32"), params,
            params["blocks"][3])


def _granite():
    m = RG.Model(
        vocab=96, d_model=64, n_heads=4, n_kv_heads=2, d_head=16,
        layer_types=("mamba", "mamba", "attention", "mamba"), n_experts=8,
        top_k=3, experts_held=(0, 1, 2, 3), d_expert=24, d_shared=48,
        ssm_heads=8, ssm_head_dim=16, ssm_state=16, ssm_conv=4,
        ssm_chunk=8, embedding_multiplier=12.0, residual_multiplier=0.22,
        attention_multiplier=0.0625, logits_scaling=16.0, norm_eps=1e-5,
        embed_std=0.01)
    params = RG.make_params(m, 5)
    return (GH.GraniteHybridConfig(**dataclasses.asdict(m), dtype="float32"),
            params, params["blocks"][1])


CONFIGS = {"lfm2": _lfm2, "granite": _granite}


@pytest.mark.parametrize("live", ["none", "two", "all"])
@pytest.mark.parametrize("config", list(CONFIGS))
def test_moe_step_walks_the_touched_experts(config, live):
    """``moe(grouped=False)`` under ``pallas_interpret`` against
    ``dense``: the same output, routings and touched count, with free
    slots among the live (granite: a held share and a shared expert)."""
    cfg, _, lp = CONFIGS[config]()
    h = jnp.asarray(np.random.default_rng(4).normal(size=(6, 64)),
                    jnp.float32)
    mask = {"none": np.zeros(6, bool), "two": np.eye(6, dtype=bool)[[1, 4]]
            .any(0), "all": np.ones(6, bool)}[live]
    out = {impl: GH.moe(lp, h, cfg, grouped=False, live=jnp.asarray(mask),
                        impl=impl)
           for impl in ("dense", "pallas_interpret")}
    (o_d, r_d, t_d), (o_k, r_k, t_k) = out["dense"], out["pallas_interpret"]
    assert np.abs(np.asarray(o_k) - np.asarray(o_d)).max() < TOL
    assert (np.asarray(r_k) == np.asarray(r_d)).all() and int(t_k) == int(t_d)
    assert (int(t_k) == 0) == (live == "none")


def _steps(dec, tokens):
    """Two live slots of three at different positions, four steps on
    fixed tokens: the live slots' logits a step."""
    n = dec.n_slots
    dec.prefill(0, tokens[:9])
    dec.prefill(2, tokens[10:23])
    out = []
    for i in range(4):
        tok, at = np.zeros(n, np.int32), np.zeros(n, np.int32)
        tok[[0, 2]] = tokens[30 + i], tokens[40 + i]
        at[[0, 2]] = 9 + i, 13 + i
        _, logits = dec.step_logits(tok, at)
        out.append(np.asarray(logits)[[0, 2]])
    return np.stack(out)


@pytest.mark.parametrize("config", list(CONFIGS))
def test_decoder_on_the_walk_serves_the_dense_logits(config):
    """``HybridDecoder(attn_impl="pallas_interpret")`` gives the dense
    path's logits, and its step's ``decode.fetch`` says it read exactly
    the pairs it touched; the dense path says it read every held expert
    of every MoE layer."""
    cfg, params, _ = CONFIGS[config]()
    tokens = np.random.default_rng(9).integers(0, 96, 50).astype(np.int32)
    fetches = {}
    logits = {}
    for impl in ("dense", "pallas_interpret"):
        dec = decoder_for(params, cfg, n_slots=3, max_len=64, page_size=4,
                          prefill_tile=16, attn_impl=impl)
        with collect() as spans:
            logits[impl] = _steps(dec, tokens)
        fetches[impl] = [a for name, _, _, a in spans
                         if name == "decode.fetch"]
    assert np.abs(logits["pallas_interpret"] - logits["dense"]).max() < 2e-6
    pairs = (cfg.n_layers - cfg.n_dense_layers) * len(cfg.experts_held)
    walked, masked = fetches["pallas_interpret"], fetches["dense"]
    assert len(walked) == len(masked) == 4
    for a, b in zip(walked, masked):
        assert 0 < a["experts_read"] == a["experts_touched"] < pairs
        assert b["experts_read"] == pairs
        assert a["experts_touched"] == b["experts_touched"]
