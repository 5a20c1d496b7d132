"""The one traffic generator.  A mix is a data file of parameters; this
module turns it and ``--seed`` into host batches or requests.

Every seed gets the same work.  A mix of requests is one fixed sequence:
lengths and inter-arrival gaps are the quantiles of their distribution over
a block of ``block`` requests, shuffled block by block by a generator that
does not see ``--seed``; the seed draws the token ids (and, elsewhere, the
weights).  With the sizes in another order for every seed, the 270 to 340
requests of a window were a different sample of the mix each time, and the
runs of one cell spread by 2 to 4 % in tokens per second on that alone (my
chip runs, PR 23).  So the spread between runs is the system's, not the
draw's.
"""

from __future__ import annotations

import json
import math
import statistics
from dataclasses import dataclass
from typing import Dict, Iterator, List

import numpy as np

FIRST_TOKEN_ID = 2      # 0 is pad / BOS, 1 is the usual EOS
ORDER_STREAM = 20260927  # fixes the order of sizes and gaps for every seed


def load(path: str) -> Dict:
    with open(path, encoding="utf-8") as f:
        spec = json.load(f)
    if spec.get("kind") not in ("train_batches", "requests"):
        raise ValueError(f"{path}: unknown traffic kind {spec.get('kind')!r}")
    return spec


def quantiles(dist: Dict, n: int) -> List[float]:
    """The ``n`` mid-point quantiles of a distribution given as data."""
    us = [(i + 0.5) / n for i in range(n)]
    kind = dist["dist"]
    if kind == "uniform_int":
        lo, hi = int(dist["low"]), int(dist["high"])
        return [float(min(hi, lo + int(u * (hi - lo + 1)))) for u in us]
    if kind == "lognormal_int":
        normal = statistics.NormalDist()
        lo, hi = int(dist["low"]), int(dist["high"])
        mu, sigma = math.log(float(dist["median"])), float(dist["sigma"])
        return [
            float(min(hi, max(lo, round(math.exp(
                mu + sigma * normal.inv_cdf(u))))))
            for u in us
        ]
    if kind == "exponential":
        mean = float(dist["mean"])
        return [-mean * math.log(1.0 - u) for u in us]
    if kind == "constant":
        return [float(dist["value"])] * n
    raise ValueError(f"unknown distribution {kind!r}")


@dataclass
class Request:
    index: int
    prompt: np.ndarray      # int32 [prompt_len]
    max_new_tokens: int
    gap_s: float            # time since the previous request was due
    greedy: bool = True


def requests(spec: Dict, seed: int, vocab_size: int) -> Iterator[Request]:
    """Endless stream of requests for a ``requests`` mix."""
    block = int(spec["block"])
    prompt_q = quantiles(spec["prompt_len"], block)
    output_q = quantiles(spec["output_len"], block)
    if spec["loop"] == "open":
        gap_q = quantiles(
            {"dist": "exponential", "mean": 1.0 / float(spec["rate_rps"])},
            block,
        )
    else:
        gap_q = [0.0] * block
    index = 0
    b = 0
    while True:
        order = np.random.default_rng([ORDER_STREAM, b])
        p_order, o_order, g_order = (
            order.permutation(block) for _ in range(3)
        )
        rng = np.random.default_rng([int(seed), b])
        for i in range(block):
            n = int(prompt_q[p_order[i]])
            yield Request(
                index=index,
                prompt=rng.integers(
                    FIRST_TOKEN_ID, vocab_size, size=n
                ).astype(np.int32),
                max_new_tokens=int(output_q[o_order[i]]),
                gap_s=float(gap_q[g_order[i]]),
            )
            index += 1
        b += 1


def train_pool(spec: Dict, seed: int, vocab_size: int) -> List[Dict]:
    """``pool`` distinct host batches: ids, a mask for the drawn lengths
    and a label that depends on the ids.  ``label_positive_share`` of the
    rows are labelled 1: with balanced labels and seeded weights the
    rows' gradients all but cancel, and what is left is so small on some
    seeds that the comparison with the reference measures rounding."""
    rng = np.random.default_rng([int(seed), 0])
    batch, seq = int(spec["batch_size"]), int(spec["seq_len"])
    lens_q = np.asarray(quantiles(spec["length"], batch), np.int64)
    positive = round(100 * float(spec["label_positive_share"]))
    pool = []
    for _ in range(int(spec["pool"])):
        ids = rng.integers(
            FIRST_TOKEN_ID, vocab_size, size=(batch, seq), dtype=np.int64
        )
        lens = lens_q[rng.permutation(batch)]
        mask = (np.arange(seq)[None, :] < lens[:, None]).astype(np.int32)
        pool.append({
            "input_ids": (ids * mask).astype(np.int32),
            "attention_mask": mask,
            "label": (ids[:, 0] % 100 < positive).astype(np.int32),
        })
    return pool
