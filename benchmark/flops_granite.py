"""Operations and bytes the Granite-hybrid block's ALGORITHM needs on
THIS chip, from shapes alone, as ``flops.py`` has them for the softmax
block. ``m`` is a ``reference_granite.Model``. The chip holds
``len(m.experts_held)`` of the routed experts: a token's routed work
here is the held experts it was routed to (``held`` a token and layer:
on average ``top_k * held / n_experts``, measured where the program
counts its routings), not ``top_k``. The embedding is a gather and
costs no FLOPs; the head is the tied embedding's ``vocab`` rows."""

from __future__ import annotations


def mamba_matmul_params(m) -> int:
    return (m.d_model * (2 * m.d_inner + 2 * m.ssm_state + m.ssm_heads)
            + m.d_inner * m.d_model)


def attention_matmul_params(m) -> int:
    return 2 * m.d_model * (m.n_heads + m.n_kv_heads) * m.d_head


def shared_params(m) -> int:
    return 3 * m.d_model * m.d_shared


def expert_params(m) -> int:
    """One routed expert's three matrices."""
    return 3 * m.d_model * m.d_expert


def router_params(m) -> int:
    return m.d_model * m.n_experts


def n_mamba(m) -> int:
    return sum(k == "mamba" for k in m.layer_types)


def dense_matmul_params(m) -> int:
    """Weights outside the routed experts that take part in a matrix
    product for every token, the head included."""
    n_m = n_mamba(m)
    return (n_m * mamba_matmul_params(m)
            + (m.n_layers - n_m) * attention_matmul_params(m)
            + m.n_layers * (shared_params(m) + router_params(m))
            + m.vocab * m.d_model)


def expected_held(m) -> float:
    """Held experts a token is routed to in one layer, a uniform
    router's mean."""
    return m.top_k * len(m.experts_held) / float(m.n_experts)


def state_elems(m) -> int:
    """One slot's recurrent state in one Mamba layer."""
    return m.ssm_heads * m.ssm_head_dim * m.ssm_state


def token_flops(m, held: float) -> float:
    """One token through the layers, the attention's pairs apart: the
    matrix products, and the recurrence once a Mamba layer (decay,
    outer product, add: three operations a state element; the readout
    two)."""
    return (2.0 * (dense_matmul_params(m) - m.vocab * m.d_model)
            + 2.0 * m.n_layers * held * expert_params(m)
            + 5.0 * n_mamba(m) * state_elems(m))


def prefill_flops(m, prompt_len: int, held: float) -> float:
    """One prompt through the layers, the head at its last position
    only, causal attention over ``P (P + 1) / 2`` query-key pairs in
    each attention layer."""
    p = float(prompt_len)
    n_attn = m.n_layers - n_mamba(m)
    return (token_flops(m, held) * p + 2.0 * m.vocab * m.d_model
            + 4.0 * n_attn * m.n_heads * m.d_head * p * (p + 1) / 2)


def decode_flops(m, n_tokens: int, rows: float, held: float) -> float:
    """``n_tokens`` decode steps' worth of tokens whose queries read
    ``rows`` K/V rows in all (each its own row included)."""
    n_attn = m.n_layers - n_mamba(m)
    return ((token_flops(m, held) + 2.0 * m.vocab * m.d_model) * n_tokens
            + 4.0 * n_attn * m.n_heads * m.d_head * rows)


def kv_row_bytes(m, itemsize: int = 2) -> int:
    """One position's K and V rows in one attention layer."""
    return 2 * m.n_kv_heads * m.d_head * itemsize


def step_bytes(m, experts_touched: float, live_slots: float, rows: float,
               itemsize: int = 2) -> float:
    """What one decode step must move: every weight outside the routed
    experts once (the router float32), the ``experts_touched`` (layer,
    held expert) pairs that received a routing, the live slots'
    recurrent state read and written (float32, the conv tail with it),
    and the live K/V rows."""
    tail = (m.ssm_conv - 1) * m.d_conv_in
    return (dense_matmul_params(m) * itemsize
            + m.n_layers * router_params(m) * (4 - itemsize)
            + experts_touched * expert_params(m) * itemsize
            + 2.0 * live_slots * n_mamba(m) * (state_elems(m) + tail) * 4
            + (m.n_layers - n_mamba(m)) * kv_row_bytes(m, itemsize) * rows)
