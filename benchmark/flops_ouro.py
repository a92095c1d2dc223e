"""Operations and bytes the looped softmax stack's ALGORITHM needs, from
shapes alone, as ``flops.py`` has them for the unlooped block. ``m`` is
a ``reference_ouro.Model``: ``n_loops`` passes over the same
``n_layers`` layers, a K/V row a (pass, layer, position). The embedding
is a gather and costs no FLOPs; the head is read once, after the last
pass."""

from __future__ import annotations


def layer_matmul_params(m) -> int:
    """One layer's matrices: q, k, v, o and the gated FFN's three."""
    return 4 * m.d_model * m.n_heads * m.d_head + 3 * m.d_model * m.d_ff


def token_flops(m) -> float:
    """One token through every pass of every layer, the attention's
    pairs and the head apart: each matrix is used ``n_loops`` times."""
    return 2.0 * m.n_loops * m.n_layers * layer_matmul_params(m)


def pair_flops(m) -> float:
    """One query-key pair in every (pass, layer): q.k and p.v."""
    return 4.0 * m.n_loops * m.n_layers * m.n_heads * m.d_head


def prefill_flops(m, prompt_len: int) -> float:
    """One prompt through the passes, the head at its last position
    only, causal attention over ``P (P + 1) / 2`` pairs."""
    p = float(prompt_len)
    return (token_flops(m) * p + 2.0 * m.vocab * m.d_model
            + pair_flops(m) * p * (p + 1) / 2)


def decode_flops(m, n_tokens: int, rows: float) -> float:
    """``n_tokens`` decode steps' worth of tokens whose queries read
    ``rows`` positions in all (each its own included)."""
    return ((token_flops(m) + 2.0 * m.vocab * m.d_model) * n_tokens
            + pair_flops(m) * rows)


def kv_row_bytes(m, itemsize: int = 2) -> int:
    """One position's K and V rows in ONE (pass, layer): what one call
    of the decode attention kernel reads a live row."""
    return 2 * m.n_heads * m.d_head * itemsize


def kv_bytes_per_position(m, itemsize: int = 2) -> int:
    """One position's rows in every pass and layer: what the pool holds
    a position."""
    return m.n_loops * m.n_layers * kv_row_bytes(m, itemsize)


def step_bytes(m, live_slots: float, rows: float, itemsize: int = 2
               ) -> float:
    """What one decode step must move: every layer's matrices once A
    PASS (the same weights, read again), the four float32 norm gains a
    layer with them, the head once, the live positions' K/V rows of
    every (pass, layer) read and the live slots' new rows written."""
    a_pass = m.n_layers * (layer_matmul_params(m) * itemsize
                           + 4 * m.d_model * 4)
    return (m.n_loops * a_pass + m.vocab * m.d_model * itemsize
            + (rows + live_slots) * kv_bytes_per_position(m, itemsize))
