"""The one general traffic generator. A traffic mix is a data file,
``traffic/<name>.json``; everything here is driven by its parameters and
by ``--seed``. The same seed gives the same inputs, and every seed gives
the same SET of sizes in another order, so that runs differ in order and
in token ids only, never in the amount of work.

``kind: "token_batches"``  training batches: ``batch`` rows of ``seq + 1``
    token ids, uniform over the vocabulary, every row different.
``kind: "closed_loop"``  requests for ``clients`` closed-loop clients.
    Lengths come in blocks of ``sizes_per_block``: the block's prompt
    lengths are the quantiles of the stated distribution (so every block
    holds its whole shape, tail included), its output budgets likewise,
    paired by a permutation fixed by ``sizes_seed``; ``total_max`` (if
    given) then caps each pair's budget at ``total_max - prompt_len``,
    as a server's context limit does. ``--seed`` permutes each block
    and draws the token ids. ``shared_prefix`` tokens (0 =
    none) at the head of every prompt are the same within a run.
"""

from __future__ import annotations

import json
import os
from statistics import NormalDist
from typing import Any, Dict, Iterator, List, Tuple

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> Dict[str, Any]:
    with open(os.path.join(_HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def _quantiles(dist: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` quantiles, at ``(i + 0.5) / n``, of a clipped distribution."""
    if dist["dist"] == "fixed":
        return np.full(n, int(dist["value"]), np.int64)
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    v = np.exp(np.log(float(dist["median"])) + float(dist["sigma"]) * z)
    return np.clip(np.rint(v), dist["min"], dist["max"]).astype(np.int64)


def block_sizes(spec: Dict[str, Any]) -> List[Tuple[int, int]]:
    """The ``(prompt_len, max_new_tokens)`` pairs every block holds."""
    n = int(spec["sizes_per_block"])
    prompts = _quantiles(spec["prompt_len"], n)
    news = _quantiles(spec["max_new_tokens"], n)
    pair = np.random.default_rng(int(spec.get("sizes_seed", 0))).permutation(n)
    cap = int(spec.get("total_max", 1 << 62))
    return [(int(prompts[i]), max(min(int(news[pair[i]]),
                                      cap - int(prompts[i])), 1))
            for i in range(n)]


def warm_request(spec: Dict[str, Any], vocab: int, seed: int
                 ) -> Dict[str, Any]:
    """One request that is in no plan: the block's median prompt
    length and its smallest budget, with token ids of its own."""
    sizes = block_sizes(spec)
    p_len = sorted(p for p, _ in sizes)[len(sizes) // 2]
    n_new = min(n for _, n in sizes)
    rng = np.random.default_rng([int(seed), 1])
    return {"prompt": rng.integers(0, vocab, size=p_len).astype(np.int32),
            "max_new_tokens": n_new}


def requests(spec: Dict[str, Any], vocab: int, seed: int, n_blocks: int
             ) -> List[Dict[str, Any]]:
    """``n_blocks`` blocks of requests in sending order:
    ``{"prompt": int32[P], "max_new_tokens": n}``."""
    rng = np.random.default_rng(int(seed))
    sizes = block_sizes(spec)
    shared = rng.integers(0, vocab, size=int(spec.get("shared_prefix", 0)))
    out = []
    for _ in range(n_blocks):
        for i in rng.permutation(len(sizes)):
            p_len, n_new = sizes[i]
            prompt = rng.integers(0, vocab, size=p_len)
            k = min(len(shared), p_len - 1)
            prompt[:k] = shared[:k]
            out.append({"prompt": prompt.astype(np.int32),
                        "max_new_tokens": n_new})
    return out


def token_batches(spec: Dict[str, Any], vocab: int, seed: int
                  ) -> Iterator[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """An endless feed of ``(tokens, labels, mask)``: next-token batches
    whose rows all differ."""
    rng = np.random.default_rng(int(seed))
    b, s = int(spec["batch"]), int(spec["seq"])
    mask = np.ones((b, s), np.float32)
    while True:
        toks = rng.integers(0, vocab, size=(b, s + 1), dtype=np.int64)
        yield (toks[:, :-1].astype(np.int32), toks[:, 1:].astype(np.int32),
               mask)
