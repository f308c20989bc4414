"""The one general traffic generator: every mix is a data file under
``bench/traffic/`` that names its kind and parameters.

Every seed gets the same work.  Sizes and arrival gaps are fixed sets,
read off each distribution at the quantiles (i + 0.5) / n, and the seed
only chooses their order, the prompt tokens and which request goes to which
tenant.  So runs with different seeds differ in content and order, not in
how much there is to do.
"""
from __future__ import annotations

import json
import math
import os
from statistics import NormalDist

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent numpy stream ``stream`` of a (possibly > 32-bit) seed."""
    return np.random.default_rng([int(seed), stream])


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def zipf_counts(n: int, k: int, s: float) -> np.ndarray:
    """How many of ``n`` requests each of ``k`` tenants gets under Zipf(s),
    by largest remainder, so the counts sum to ``n``."""
    p = 1.0 / np.arange(1, k + 1) ** s
    exact = n * p / p.sum()
    counts = np.floor(exact).astype(int)
    extra = n - counts.sum()
    counts[np.argsort(-(exact - counts))[:extra]] += 1
    return counts


def output_lengths(spec: dict, n: int) -> np.ndarray:
    """The fixed set of output lengths: lognormal(median, sigma) clipped."""
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    raw = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(int)


def _tail_last(values: np.ndarray, k: int, rng) -> np.ndarray:
    """``values`` (sorted) in the seed's order, except that an evenly spaced
    subset of ``k`` of them comes last in an order that no seed changes."""
    pick = np.zeros(len(values), bool)
    pick[((np.arange(k) + 0.5) * len(values) / k).astype(int)] = True
    tail = values[pick][np.random.default_rng(0).permutation(k)]
    return np.concatenate([rng.permutation(values[~pick]), tail])


def serve_requests(mix: dict, seconds: float, seed: int, vocab: int):
    """Open-loop Poisson arrivals at ``mix["rate"]`` requests/s over
    ``seconds``.  Returns a list of dicts: arrival (s from the window's
    start), prompt (int32 array), steps (tokens to serve), tenant.

    The requests of the last ``fixed_tail_s`` seconds have the same arrival
    gaps and output lengths for every seed (their prompts and tenants still
    vary): the last completion, which ends the window of a rate, is then
    the same work whatever the seed."""
    n = max(1, int(round(mix["rate"] * seconds)))
    k = min(n - 1, int(round(mix["rate"] * mix["fixed_tail_s"])))
    # n - 1 gaps between n arrivals, so the last arrival is at the same
    # time for every seed
    gaps = -np.log1p(-_quantiles(n - 1)) / mix["rate"]
    lengths = output_lengths(mix["output"], n)
    tenants = np.repeat(np.arange(mix["tenants"]),
                        zipf_counts(n, mix["tenants"], mix["zipf"]))
    rng = rng_for(seed, 1)
    gaps, lengths, tenants = (_tail_last(gaps, k, rng),
                              _tail_last(lengths, k, rng),
                              rng.permutation(tenants))
    arrivals = np.concatenate([[0.0], np.cumsum(gaps)])
    prompts = rng.integers(0, vocab, (n, mix["prompt_len"]), dtype=np.int32)
    return [{"arrival": float(arrivals[i]), "prompt": prompts[i],
             "steps": int(lengths[i]), "tenant": int(tenants[i])}
            for i in range(n)]


class FederatedData:
    """Per-client token stream for a federated job: each client draws each
    local step's rows from one of ``topics`` Markov chains (every token
    prefers ``branch`` successors, with a share ``noise`` of uniform
    tokens), choosing the topic from its own Dirichlet(``dirichlet_alpha``)
    mixture — the non-IID partition of the paper's heterogeneity runs.

    ``round_batch(local_steps)`` -> (clients, local_steps, batch, seq) int32,
    the interface the federated trainer stages from.  Every call draws fresh
    rows."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        rng = rng_for(seed, 2)
        self.n, self.b, self.s = (mix["clients"], mix["batch_per_client"],
                                  mix["seq_len"])
        self.vocab, self.noise = vocab, mix["noise"]
        self.succ = rng.integers(0, vocab, (mix["topics"], vocab,
                                            mix["branch"]), dtype=np.int32)
        self.mix = rng.dirichlet(np.full(mix["topics"],
                                         mix["dirichlet_alpha"]), size=self.n)
        self.rng = rng

    def round_batch(self, local_steps: int = 1) -> np.ndarray:
        rng = self.rng
        n, b, s = self.n, self.b, self.s
        topic = np.stack([rng.choice(len(self.mix[i]), size=local_steps,
                                     p=self.mix[i]) for i in range(n)])
        topic = np.repeat(topic.reshape(-1), b)
        rows = topic.shape[0]
        toks = np.empty((rows, s), np.int32)
        toks[:, 0] = rng.integers(0, self.vocab, rows)
        branch = self.succ.shape[2]
        for t in range(1, s):
            nxt = self.succ[topic, toks[:, t - 1],
                            rng.integers(0, branch, rows)]
            noisy = rng.random(rows) < self.noise
            toks[:, t] = np.where(noisy, rng.integers(0, self.vocab, rows),
                                  nxt)
        return toks.reshape(n, local_steps, b, s)


def sfedlora_gamma(mix: dict) -> float:
    """The paper's scaling factor alpha * sqrt(N / r) for a federated mix."""
    return mix["alpha"] * math.sqrt(mix["clients"] / mix["rank"])
