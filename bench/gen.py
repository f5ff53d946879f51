"""Seeded generators of the benchmark's inputs.

Traffic files under ``traffic/`` hold only parameters; these functions turn
them into inputs.  Sizes are the deterministic quantiles of the stated
distribution, so every seed gives the same set of sizes and the seed only
changes their order and the token ids: runs on different seeds do the same
amount of work.
"""
from __future__ import annotations

from statistics import NormalDist

import numpy as np


def lm_batch(seed: int, step: int, *, vocab: int, seq_len: int, batch: int,
             eos_id: int = 0, mean_doc_len: int = 512,
             zipf_a: float = 1.2) -> dict[str, np.ndarray]:
    """One packed language-model batch: Zipfian unigrams with a per-document
    offset and EOS at document ends, deterministic in ``(seed, step)``.

    The same stream as the program's ``repro.data.SyntheticLM.batch_at``,
    kept here so that the reference reads its inputs from the benchmark's
    own code.
    """
    rng = np.random.default_rng(np.random.SeedSequence([seed, step]))
    n = batch * (seq_len + 1)
    toks = rng.zipf(zipf_a, size=n).astype(np.int64)
    toks = (toks % (vocab - 1)) + 1
    doc_len = np.maximum(8, rng.poisson(mean_doc_len, size=n // 8 + 2))
    bounds = np.cumsum(doc_len)
    bounds = bounds[bounds < n]
    offsets = np.zeros(n, np.int64)
    if len(bounds):
        drift = rng.integers(0, vocab // 4, size=len(bounds) + 1)
        offsets = drift[np.searchsorted(bounds, np.arange(n), side="right")]
    toks = ((toks + offsets) % (vocab - 1)) + 1
    toks[bounds] = eos_id
    toks = toks.reshape(batch, seq_len + 1).astype(np.int32)
    return {"tokens": toks[:, :-1], "targets": toks[:, 1:]}


def lognormal_sizes(n: int, dist: dict) -> list[int]:
    """The ``n`` mid-quantiles of a lognormal with ``median`` and ``sigma``,
    rounded and clipped to ``[min, max]``, in increasing order."""
    nd = NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        v = round(dist["median"] * float(np.exp(dist["sigma"] * z)))
        out.append(int(min(dist["max"], max(dist["min"], v))))
    return out


def mean_lognormal_size(dist: dict, n: int = 4096) -> float:
    return float(np.mean(lognormal_sizes(n, dist)))


def serve_requests(traffic: dict, n: int, vocab: int,
                   seed: int) -> list[tuple[np.ndarray, int]]:
    """``n`` requests ``(prompt [1, s] int32, max_new)`` of an offline batch,
    in submission order.

    Output lengths go longest first, so the batch ends with short requests
    and the lanes drain evenly whatever the seed; the seed pairs prompt
    lengths with output lengths and draws the token ids (never 0).
    """
    rng = np.random.default_rng(seed)
    outs = sorted(lognormal_sizes(n, traffic["output"]), reverse=True)
    prompts = lognormal_sizes(n, traffic["prompt"])
    prompts = [prompts[i] for i in rng.permutation(n)]
    return [(rng.integers(1, vocab, size=(1, p), dtype=np.int32), o)
            for p, o in zip(prompts, outs)]


def pow2_bucket(length: int, minimum: int) -> int:
    """The prefill bucket a prompt of ``length`` is padded to."""
    b = max(1, int(minimum))
    while b < length:
        b *= 2
    return b
