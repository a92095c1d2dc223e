"""Operations and bytes Kimi-K2's language model (``reference_kimi.Model``
``m``) needs on this chip, from shapes alone, as ``flops_lfm2.py`` has
them for LFM2's layer. A token's routed work is the held experts it was
routed to in each MoE layer (``held``: ``top_k * 12 / 384`` a layer under
a uniform router). The embedding is a gather and costs no FLOPs; the
head is the untied ``[D, vocab]`` matrix. Bytes are the LEAST a program
must move: a latent row at its unpadded 576 lanes (the program keeps it
at 640, and a share under 100% shows that).

The latent attention is counted in the form the program runs it:
absorbed in a decode step (``W_UK`` on the query and ``W_UV`` on the sum
of latents, the same products a token as the expanded layer's, plus the
scores and sums over the latent rows), expanded in a prompt's tile (every
lane row the tile reads goes up through ``W_UK`` / ``W_UV``, then the
per-head scores and sums)."""

from __future__ import annotations


def mla_params(m) -> int:
    """One latent attention layer's matrices."""
    d, h = m.d_model, m.n_heads
    return (d * m.q_lora + m.q_lora * h * (m.d_nope + m.d_rope)
            + d * (m.kv_lora + m.d_rope) + m.kv_lora * h * (m.d_nope + m.d_v)
            + h * m.d_v * d)


def dense_ffn_params(m) -> int:
    return 3 * m.d_model * m.d_dense


def expert_params(m) -> int:
    """One routed expert's three matrices."""
    return 3 * m.d_model * m.d_expert


def shared_params(m) -> int:
    return 3 * m.d_model * m.d_expert * m.n_shared


def router_params(m) -> int:
    return m.d_model * m.n_experts


def n_dense(m) -> int:
    return min(m.n_dense_layers, m.n_layers)


def dense_matmul_params(m) -> int:
    """Weights outside the routed experts that take part in a matrix
    product for every token, the head included."""
    return (m.n_layers * mla_params(m) + n_dense(m) * dense_ffn_params(m)
            + m.n_moe * (shared_params(m) + router_params(m))
            + m.vocab * m.d_model)


def token_flops(m, held: float) -> float:
    """One token through the layers, the attention over the rows apart."""
    return (2.0 * (dense_matmul_params(m) - m.vocab * m.d_model)
            + 2.0 * m.n_moe * held * expert_params(m))


def latent_row_bytes(m, itemsize: int = 2) -> int:
    """One position's latent row in one layer, unpadded: 1,152 B."""
    return (m.kv_lora + m.d_rope) * itemsize


def kernel_flops_per_row(m) -> int:
    """What the absorbed step does with one latent row in one layer:
    every head's score over ``kv_lora + d_rope`` lanes and its weighted
    sum of the ``kv_lora`` latent lanes."""
    return 2 * m.n_heads * (2 * m.kv_lora + m.d_rope)


def kernel_least_s(m, rows: float, peak) -> float:
    """The least time of one call of ``paged_latent_decode_attention``
    over ``rows`` live latent rows: the larger of their unpadded bytes at
    the HBM peak and their FLOPs at the bf16 peak."""
    return max(rows * latent_row_bytes(m) / peak["hbm_bytes_per_s"],
               rows * kernel_flops_per_row(m) / peak["bf16_flops_per_s"])


def expand_flops_per_row(m) -> int:
    """One lane row up through ``W_UK`` / ``W_UV`` in one layer."""
    return 2 * m.kv_lora * m.n_heads * (m.d_nope + m.d_v)


def pair_flops(m) -> int:
    """One expanded query-key pair in one layer: the score over ``d_nope
    + d_rope`` lanes a head and the weighted ``d_v`` value."""
    return 2 * m.n_heads * (m.d_nope + m.d_rope + m.d_v)


def prefill_flops(m, prompt_len: int, held: float) -> float:
    """One prompt through the layers in the least expanded form: every
    position's row expanded once, ``P (P + 1) / 2`` causal pairs, the
    head at the last position only."""
    p = float(prompt_len)
    return (token_flops(m, held) * p + 2.0 * m.vocab * m.d_model
            + m.n_layers * (expand_flops_per_row(m) * p
                            + pair_flops(m) * p * (p + 1) / 2))


def decode_flops(m, n_tokens: int, rows: float, held: float) -> float:
    """``n_tokens`` decode steps' worth of tokens whose queries read
    ``rows`` latent rows in all (each its own row included)."""
    return ((token_flops(m, held) + 2.0 * m.vocab * m.d_model) * n_tokens
            + m.n_layers * kernel_flops_per_row(m) * rows)


def weight_bytes(m, itemsize: int = 2) -> float:
    """Every weight outside the routed experts once: the matrices in
    ``itemsize``, the routers and the selection biases float32."""
    return (dense_matmul_params(m) * itemsize
            + m.n_moe * (router_params(m) * (4 - itemsize)
                         + 4 * m.n_experts))


def step_bytes(m, experts_touched: float, rows: float,
               itemsize: int = 2) -> float:
    """What one decode step must move: :func:`weight_bytes`, the
    ``experts_touched`` (layer, held expert) pairs that received a
    routing, and the live latent rows of every layer unpadded."""
    return (weight_bytes(m, itemsize)
            + experts_touched * expert_params(m) * itemsize
            + m.n_layers * latent_row_bytes(m, itemsize) * rows)


def expected_touched(m, tokens: float) -> float:
    """(layer, held expert) pairs ``tokens`` tokens touch under a
    uniform router: ``1 - (1 - k / E) ** T`` of the held ones a MoE
    layer."""
    return (m.n_moe * len(m.experts_held)
            * (1.0 - (1.0 - m.top_k / float(m.n_experts)) ** float(tokens)))


def tile_least_s(m, pos0: int, real: int, last: bool, held: float,
                 peak) -> float:
    """The least time of one prompt tile: ``real`` tokens at ``pos0``
    (the head once, on the prompt's ``last`` tile) — the larger of its
    FLOPs at the bf16 peak (the tokens, the ``pos0 + real`` lane rows
    expanded, the causal pairs) and its bytes at the HBM peak (the
    weights outside the experts once, the experts its tokens touch, the
    lane's latent rows read and the tile's written)."""
    pairs = real * pos0 + real * (real + 1) / 2.0
    flops = (token_flops(m, held) * real
             + m.n_layers * (expand_flops_per_row(m) * (pos0 + real)
                             + pair_flops(m) * pairs)
             + (2.0 * m.vocab * m.d_model if last else 0.0))
    moved = (weight_bytes(m) + expected_touched(m, real) * expert_params(m)
             * 2 + m.n_layers * latent_row_bytes(m) * (pos0 + 2 * real))
    return max(flops / peak["bf16_flops_per_s"],
               moved / peak["hbm_bytes_per_s"])


def prompt_least_s(m, prompt_len: int, tile: int, held: float,
                   peak) -> float:
    """:func:`tile_least_s` over the tiles a prompt is walked in."""
    return sum(tile_least_s(m, pos0, min(tile, prompt_len - pos0),
                            pos0 + tile >= prompt_len, held, peak)
               for pos0 in range(0, int(prompt_len), int(tile)))
