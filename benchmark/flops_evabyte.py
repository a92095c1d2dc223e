"""Operations and bytes the EvaByte block's ALGORITHM needs, from shapes
alone, as ``flops.py`` has them for the softmax block. ``m`` is a
``reference_evabyte.Model``. A position ``n`` sits in window ``n // W``;
its query reads ``n % W + 1`` exact rows of its own window and ``(n //
W) * W / C`` summary rows. The embedding is a gather and costs no
FLOPs; the head is all ``n_pred_heads * vocab`` columns, as both the
reference and the program compute them."""

from __future__ import annotations

from typing import Dict, Tuple


def layer_matmul_params(m) -> int:
    return 4 * m.d_model * m.n_heads * m.d_head + 3 * m.d_model * m.d_ff


def matmul_params(m) -> int:
    """Weights that take part in a matrix product for every byte."""
    return (m.n_layers * layer_matmul_params(m)
            + m.d_model * m.n_pred_heads * m.vocab)


def rows_at(m, pos: int) -> Tuple[int, int]:
    """``(summary_rows, window_rows)`` the query at ``pos`` reads."""
    return (pos // m.window) * (m.window // m.chunk), pos % m.window + 1


def row_bytes(m, itemsize: int = 2) -> int:
    """One cache row of one layer: K and V, all heads."""
    return 2 * m.n_heads * m.d_head * itemsize


def summaries(m, itemsize: int = 2) -> Dict[str, float]:
    """One layer's summaries of ONE finished window: ``phi . k`` for
    every row, the two pooled sums, and the least bytes (read the
    window's rows, write its summaries)."""
    hd = m.n_heads * m.d_head
    return {"flops": 6.0 * m.window * hd,
            "bytes": float((m.window + m.window // m.chunk)
                           * row_bytes(m, itemsize))}


def prefill_attention(m, tile: int, n_summary: int, itemsize: int = 2
                      ) -> Dict[str, float]:
    """One layer's attention of one window's tile of ``tile`` real
    positions that sees ``n_summary`` summary rows: QK^T and PV over the
    causal pairs of the tile and over every (query, summary) pair, and
    the least bytes (read q, k, v and the summaries, write o)."""
    hd = m.n_heads * m.d_head
    pairs = tile * (tile + 1) / 2.0 + float(tile) * n_summary
    return {"flops": 4.0 * hd * pairs,
            "bytes": float((4 * tile + 2 * n_summary) * hd * itemsize)}


def prompt_windows(m, prompt_len: int):
    """``(tile, n_summary)`` of every window a prompt is prefilled in."""
    full, rest = divmod(int(prompt_len), m.window)
    per = m.window // m.chunk
    out = [(m.window, w * per) for w in range(full)]
    if rest:
        out.append((rest, full * per))
    return out


def prefill_flops(m, prompt_len: int) -> float:
    """One prompt through the layers window by window, the head at its
    last position only, the summaries of every window it finishes."""
    attn = sum(prefill_attention(m, t, s)["flops"]
               for t, s in prompt_windows(m, prompt_len))
    n_full = int(prompt_len) // m.window
    return (2.0 * m.n_layers * layer_matmul_params(m) * prompt_len
            + 2.0 * m.d_model * m.n_pred_heads * m.vocab
            + m.n_layers * (attn + n_full * summaries(m)["flops"]))


def decode_flops(m, n_tokens: int, rows: float) -> float:
    """``n_tokens`` decode steps' worth of bytes whose queries read
    ``rows`` cache rows in all (both kinds, each its own row
    included)."""
    return (2.0 * matmul_params(m) * n_tokens
            + 4.0 * m.n_layers * m.n_heads * m.d_head * rows)


def decode_attention_bytes(m, rows: float, itemsize: int = 2) -> float:
    """The cache rows of every layer that queries reading ``rows`` rows
    must fetch."""
    return float(m.n_layers * row_bytes(m, itemsize)) * rows


def step_bytes(m, rows: float, itemsize: int = 2) -> float:
    """What one decode step must read: every matmul weight once, and the
    live rows of both kinds."""
    return (matmul_params(m) * itemsize
            + decode_attention_bytes(m, rows, itemsize))
