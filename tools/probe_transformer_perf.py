"""Where does the transformer train step spend its time? (dev-chip probe)

Times single-device step VARIANTS with the dependent-chain slope method
(bench.py:_chain_slope_seconds) to attribute ms/step to: attention
softmax traffic, the 32k-vocab CE, the optimizer update, and dispatch.

    python tools/probe_transformer_perf.py [variant ...]

Each variant prints one JSON line {variant, ms_per_step, mfu?}.
"""

import functools
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax                                    # noqa: E402
import jax.numpy as jnp                       # noqa: E402

from mmlspark_tpu.models import transformer as T          # noqa: E402
from mmlspark_tpu.parallel.ring_attention import dense_attention  # noqa: E402

CFG = T.TransformerConfig(vocab=32768, d_model=512, n_heads=8,
                          d_head=64, d_ff=2048, n_stages=1,
                          layers_per_stage=8, dtype="bfloat16")
AX = T._Axes(None, None, None, None, None)
PEAK = 197e12


def flops_per_step(cfg, batch, seq):
    L = cfg.n_stages * cfg.layers_per_stage
    d_attn = cfg.n_heads * cfg.d_head
    n_matmul = (cfg.d_model * cfg.vocab
                + L * (4 * cfg.d_model * d_attn + 2 * cfg.d_model * cfg.d_ff))
    return 6.0 * n_matmul * batch * seq + 12.0 * L * batch * seq * seq * d_attn


def chain_slope(run_chain, n_short=2, n_long=10, repeats=3):
    times = {}
    for n in (n_short, n_long):
        run_chain(n)
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            run_chain(n)
            best = min(best, time.perf_counter() - t0)
        times[n] = best
    slope = (times[n_long] - times[n_short]) / (n_long - n_short)
    return slope if slope > 0 else times[n_long] / n_long


def body_forward(params, tokens, cfg, attn_mode):
    """Embed + blocks (+ optionally attention) + final norm -> h."""
    x = params["embed"][tokens]
    pos = jnp.arange(tokens.shape[1])
    dt = T._compute_dtype(cfg)
    for bp_all in params["blocks"]:
        bp = {k: v[0] for k, v in bp_all.items()}
        if attn_mode != "none":
            h = T._rmsnorm(x, bp["ln1"]).astype(dt)
            q = jnp.einsum("bsd,dhk->bshk", h, bp["wq"].astype(dt)
                           ).astype(jnp.float32)
            k = jnp.einsum("bsd,dhk->bshk", h, bp["wk"].astype(dt)
                           ).astype(jnp.float32)
            v = jnp.einsum("bsd,dhk->bshk", h, bp["wv"].astype(dt)
                           ).astype(jnp.float32)
            q, k = T._rope(q, pos), T._rope(k, pos)
            if attn_mode == "folded":
                from mmlspark_tpu.parallel.pallas_attention import (
                    flash_attention_folded)
                a = flash_attention_folded(q.astype(dt), k.astype(dt),
                                           v.astype(dt), True)
            elif attn_mode == "flash":
                from mmlspark_tpu.parallel.pallas_attention import (
                    flash_attention)
                a = flash_attention(q.astype(dt), k.astype(dt), v.astype(dt),
                                    True)
            elif attn_mode == "bf16p":
                dh = q.shape[-1]
                s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(dt), k.astype(dt),
                               preferred_element_type=jnp.float32) * dh ** -0.5
                sq = q.shape[1]
                mask = jnp.arange(sq)[:, None] >= jnp.arange(sq)[None, :]
                s = jnp.where(mask[None, None], s, -1e30)
                p = jax.nn.softmax(s, axis=-1).astype(dt)   # bf16 stored p
                a = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(dt),
                               preferred_element_type=jnp.float32)
            else:
                a = dense_attention(q, k, v, causal=True, compute_dtype=dt)
            o = jnp.einsum("bshk,hkd->bsd", a.astype(dt), bp["wo"].astype(dt)
                           ).astype(jnp.float32)
            x = x + o
        x = x + T._mlp(bp, x, AX, cfg)
    return T._rmsnorm(x, params["final_norm"])


def ce_loss(params, h, labels, mask, cfg, mode):
    dt = T._compute_dtype(cfg)
    if mode == "none":
        return jnp.sum(h * h) * 1e-6
    if mode.startswith("chunked"):
        C = int(mode.split(":")[1]) if ":" in mode else 128
        b, s, d = h.shape
        n = s // C
        W = params["head"].astype(dt)
        hs = jnp.swapaxes(h.reshape(b, n, C, d), 0, 1)
        ls = jnp.swapaxes(labels.reshape(b, n, C), 0, 1)
        ms = jnp.swapaxes(mask.reshape(b, n, C), 0, 1)

        @jax.checkpoint
        def body(carry, args):
            hc, lc, mc = args
            logits = jnp.einsum("bcd,dv->bcv", hc.astype(dt), W,
                                preferred_element_type=jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, lc[..., None],
                                       axis=-1)[..., 0]
            return carry + jnp.sum((lse - gold) * mc), None
        total, _ = jax.lax.scan(body, jnp.float32(0.0), (hs, ls, ms))
        return total / jnp.maximum(jnp.sum(mask), 1.0)
    logits = jnp.einsum("bsd,dv->bsv", h.astype(dt),
                        params["head"].astype(dt),
                        preferred_element_type=jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum((lse - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def make_step(cfg, attn_mode="dense", ce_mode="full", fwd_only=False,
              opt=True, lr=0.01, momentum=0.9):
    def loss_fn(params, tokens, labels, mask):
        h = body_forward(params, tokens, cfg, attn_mode)
        return ce_loss(params, h, labels, mask, cfg, ce_mode)

    if fwd_only:
        @jax.jit
        def step(params, velocity, tokens, labels, mask):
            return params, velocity, loss_fn(params, tokens, labels, mask)
        return step

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, velocity, tokens, labels, mask):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, labels,
                                                  mask)
        if opt:
            velocity = jax.tree.map(lambda v, g: momentum * v + g,
                                    velocity, grads)
            params = jax.tree.map(lambda p, v: p - lr * v, params, velocity)
        else:
            params = jax.tree.map(lambda p, g: p - lr * g * 0, params, grads)
        return params, velocity, loss
    return step


def run_variant(name, batch=8, seq=1024, **kw):
    seq = int(seq)
    params = T.init_params(CFG, seed=0)
    params = jax.device_put(params)
    velocity = jax.tree.map(lambda p: p * 0.0, params)
    rng = np.random.default_rng(0)
    tokens, labels, mask = T.make_batch(rng, CFG, batch, seq)
    step = make_step(CFG, **kw)
    state = {"p": params, "v": velocity}

    def run_chain(n):
        for _ in range(n):
            state["p"], state["v"], loss = step(state["p"], state["v"],
                                                tokens, labels, mask)
        float(loss)

    sec = chain_slope(run_chain)
    out = {"variant": name, "batch": batch, "ms_per_step": round(sec * 1e3, 2)}
    if kw.get("attn_mode") != "none" and kw.get("ce_mode") != "none" \
            and not kw.get("fwd_only"):
        mfu = flops_per_step(CFG, batch, seq) / sec / PEAK
        out["mfu"] = round(mfu, 4)
    print(json.dumps(out), flush=True)


VARIANTS = {
    "full": dict(),
    "bf16p": dict(attn_mode="bf16p"),
    "no_ce": dict(ce_mode="none"),
    "no_attn": dict(attn_mode="none"),
    "fwd_only": dict(fwd_only=True),
    "no_opt": dict(opt=False),
    "full_b16": dict(batch=16),
    "bf16p_b16": dict(attn_mode="bf16p", batch=16),
    "full_b32": dict(batch=32),
    "flash": dict(attn_mode="flash"),
    "flash_b16": dict(attn_mode="flash", batch=16),
    "folded": dict(attn_mode="folded"),
    "folded_b16": dict(attn_mode="folded", batch=16),
    "folded_noopt": dict(attn_mode="folded", opt=False),
    "folded_s512": dict(attn_mode="folded", batch=16, seq=512),
    "full_s512": dict(batch=16, seq=512),
    "folded_s256": dict(attn_mode="folded", batch=32, seq=256),
    "full_s256": dict(batch=32, seq=256),
    "folded_noce": dict(attn_mode="folded", ce_mode="none"),
    "folded_ce128": dict(attn_mode="folded", ce_mode="chunked:128"),
    "folded_ce256": dict(attn_mode="folded", ce_mode="chunked:256"),
    "folded_ce512": dict(attn_mode="folded", ce_mode="chunked:512"),
    "folded_s4096_b2": dict(attn_mode="folded", batch=2, seq=4096),
    "full_s4096_b2": dict(batch=2, seq=4096),
    "flash_s4096_b2": dict(attn_mode="flash", batch=2, seq=4096),
}


def main():
    names = sys.argv[1:] or list(VARIANTS)
    print(json.dumps({"devices": [str(d) for d in jax.devices()],
                      "backend": jax.default_backend()}), flush=True)
    for n in names:
        kw = dict(VARIANTS[n])
        batch = kw.pop("batch", 8)
        run_variant(n, batch=batch, **kw)


if __name__ == "__main__":
    main()
