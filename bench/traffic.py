"""The one request generator: a traffic file's parameters -> requests.

A traffic file fixes the engine geometry (``engine``), the sets of prompt
and output lengths, the number of image-patch blocks (``patch_pool``) and
the number of request blocks in one window (``blocks``, chosen so that
the window lasts about the benchmark's ``run_seconds``). The stream is
made of blocks; each block holds every (prompt length, output length) pair
once, in the files' order (prompt length major). The seed draws the token
ids and nothing else, so every seed gives the same work in the same order:
with few slots, the order decides how admissions overlap decode, and so
the gaps between tokens.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import List, Optional

import numpy as np


@dataclass
class RequestSpec:
    prompt: np.ndarray          # (L,) int32 token ids
    max_new_tokens: int
    patch: Optional[int]        # index into the patch pool, or None


def block_size(traffic: dict) -> int:
    return len(traffic["prompt_lens"]) * len(traffic["output_lens"])


def window_requests(traffic: dict) -> int:
    """Requests in one window: ``blocks`` whole blocks, fixed work."""
    return block_size(traffic) * traffic["blocks"]


def _rng(seed: int, stream: int):
    return np.random.default_rng([stream, seed])


def request_stream(traffic: dict, vocab: int, seed: int,
                   n: int) -> List[RequestSpec]:
    """The first ``n`` requests of the seeded stream."""
    rng = _rng(seed, 0)
    pairs = list(itertools.product(traffic["prompt_lens"],
                                   traffic["output_lens"]))
    pool = traffic.get("patch_pool", 0)
    out: List[RequestSpec] = []
    while len(out) < n:
        for plen, olen in pairs:
            if len(out) == n:
                break
            out.append(RequestSpec(
                prompt=rng.integers(0, vocab, plen, dtype=np.int64).astype(
                    np.int32),
                max_new_tokens=int(olen),
                patch=len(out) % pool if pool else None))
    return out


def warmup_batches(traffic: dict, vocab: int, seed: int,
                   update_segment: int) -> List[List[RequestSpec]]:
    """Queues that, served one after another on the cell's engine, compile
    every program the window can run: ``k`` equal prompts admitted together
    for every ``k`` up to the slot count (their first tokens are sampled in
    one batch of ``k``), every prompt length once (one finalize program per
    length), and, where an output is long enough to reach the decode-time
    index update, one request that runs through it."""
    rng = _rng(seed, 1)
    lens = list(traffic["prompt_lens"])
    slots = traffic["engine"]["slots"]
    pool = traffic.get("patch_pool", 0)

    def req(plen, olen):
        return RequestSpec(prompt=rng.integers(0, vocab, plen).astype(np.int32),
                           max_new_tokens=olen, patch=0 if pool else None)

    batches = []
    for k in range(1, slots + 1):
        batches.append([req(lens[(k - 1) % len(lens)], 2) for _ in range(k)])
    for plen in lens[slots:]:
        batches.append([req(plen, 2)])
    if max(traffic["output_lens"]) - 1 >= update_segment:
        batches[0][0].max_new_tokens = update_segment + 2
    return batches
