"""Operations and bytes the ALGORITHM needs, from shapes alone (never
from ``cost_analysis``), so that a number reads the same work whatever
implements it. ``m`` is a ``reference.Model``. Recomputed operations do
not count; the embedding is a gather and costs no FLOPs."""

from __future__ import annotations

import json
import os
from typing import Any, Dict

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of ``device_kind``; a device that is not in
    ``peaks.json`` is an error, not a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise LookupError(f"no published peaks for device_kind "
                          f"{device_kind!r} in benchmark/peaks.json")
    return table[device_kind]


def layer_matmul_params(m) -> int:
    return 4 * m.d_model * m.n_heads * m.d_head + 2 * m.d_model * m.d_ff


def matmul_params(m) -> int:
    """Weights that take part in a matrix product for every token: the
    layers' projections and FFN, and the output head."""
    return m.n_layers * layer_matmul_params(m) + m.d_model * m.vocab


def train_flops_per_token(m, seq: int) -> float:
    """Forward + backward: 6 x matmul weights, plus causal attention
    (QK^T and PV forward, four products backward, half masked):
    ``6 * S * H*Dh`` per layer."""
    return (6.0 * matmul_params(m)
            + 6.0 * seq * m.n_heads * m.d_head * m.n_layers)


def prefill_flops(m, prompt_len: int) -> float:
    """One prompt through the layers, the head at its last position
    only, causal attention over ``P*(P+1)/2`` query-key pairs."""
    p = float(prompt_len)
    return (2.0 * m.n_layers * layer_matmul_params(m) * p
            + 2.0 * m.d_model * m.vocab
            + 4.0 * m.n_layers * m.n_heads * m.d_head * p * (p + 1) / 2)


def decode_flops(m, n_tokens: int, positions: float) -> float:
    """``n_tokens`` decode steps' worth of tokens that attend over
    ``positions`` cached rows in total (each counts its own row)."""
    return (2.0 * matmul_params(m) * n_tokens
            + 4.0 * m.n_layers * m.n_heads * m.d_head * positions)


def flash_attention_fwd(m, batch: int, seq: int,
                        itemsize: int = 2) -> Dict[str, float]:
    """One layer's causal attention forward for a batch: FLOPs (QK^T and
    PV, ``2 * b*H*S*S*Dh`` multiply-adds each, half of them masked) and
    the least bytes (read q, k, v, write o)."""
    elems = batch * seq * m.n_heads * m.d_head
    return {"flops": 2.0 * batch * m.n_heads * seq * seq * m.d_head,
            "bytes": 4.0 * elems * itemsize}


def kv_bytes(m, positions: float, itemsize: int = 4) -> float:
    """K and V rows of every layer for ``positions`` cached rows."""
    return 2.0 * m.n_layers * m.n_heads * m.d_head * itemsize * positions


def decode_step_bytes(m, positions: float, itemsize: int = 4) -> float:
    """What one decode step must read: every matmul weight once, and
    the K/V rows of the live lengths."""
    return matmul_params(m) * itemsize + kv_bytes(m, positions, itemsize)


def roofline(flops: float, nbytes: float, peak: Dict[str, Any]
             ) -> Dict[str, Any]:
    """The least time the chip could take, and which peak bounds it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_c, t_m),
            "bound": "compute" if t_c >= t_m else "memory"}
