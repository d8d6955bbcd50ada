"""The one traffic generator. A mix is a data file `traffic/<mix>.json`; this
reads its parameters and yields the work, a pure function of (mix, seed).

Every seed gets the SAME request sizes in the SAME order: the sizes are the
mix's own quantiles (no random draw), and their order is drawn from the MIX's
seed, a fresh shuffle of the whole set each time it is used up. The run's
seed draws the token ids (and, in the benchmark, the weights). A tail such as
a 95th-percentile time to first token follows the clumps of long prompts in
the queue. With the order drawn from the run's seed it swung by a tenth
between seeds; with one short cycle repeated, the window held four copies of
each value and the percentile sat on the edge between two of them, 3.5 %
apart (PERF.md, Findings). One long fixed order has neither fault.
"""

from __future__ import annotations

import json
import os
import statistics

import numpy as np

from benchmark import find_data

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str, root: str = os.path.dirname(HERE),
             paths: tuple = ("benchmark",)) -> dict:
    f = find_data(root, list(paths), "traffic", f"{name}.json")
    if f is None:
        raise FileNotFoundError(f"no traffic/{name}.json under {paths}")
    with open(f) as fh:
        mix = json.load(fh)
    mix["name"] = name
    return mix


def lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                        hi: int) -> np.ndarray:
    """n whole numbers at the (i + 0.5) / n quantiles of a log-normal,
    clipped to [lo, hi]."""
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(int)


def request_sizes(mix: dict) -> list[tuple[int, int]]:
    """The mix's set of (prompt tokens, output tokens), the same for every
    seed: the quantiles of both lengths, outputs paired with prompts by a
    fixed shuffle (long prompts do not always get long outputs)."""
    n = int(mix["distinct_sizes"])
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    prompts = lognormal_quantiles(n, p["median"], p["sigma"], p["min"], p["max"])
    outputs = lognormal_quantiles(n, o["median"], o["sigma"], o["min"], o["max"])
    pairing = np.random.default_rng(int(mix["pairing_seed"])).permutation(n)
    return [(int(a), int(b)) for a, b in zip(prompts, outputs[pairing])]


class RequestSource:
    """An endless stream of (prompt ids, max_new_tokens): the mix's set of
    sizes in an order drawn from the MIX's seed, shuffled anew each time the
    set is used up, so every run sends the same sizes in the same order. The
    ids are the run's seed's, uniform over the vocabulary: no two prompts
    share a prefix except by chance."""

    def __init__(self, mix: dict, seed: int, vocab_size: int):
        self.sizes = request_sizes(mix)
        self.order_rng = np.random.default_rng([int(mix["pairing_seed"]), 0x0DE2])
        self.rng = np.random.default_rng([int(seed), 0x5EED])
        self.vocab = int(vocab_size)
        self._order: list = []
        self.issued = 0

    def next(self) -> tuple[list, int]:
        if not self._order:
            self._order = self.order_rng.permutation(len(self.sizes)).tolist()
        n_prompt, n_out = self.sizes[self._order.pop()]
        ids = self.rng.integers(0, self.vocab, n_prompt).tolist()
        self.issued += 1
        return ids, n_out


def train_batch_ids(mix: dict, seed: int, step: int, vocab_size: int) -> np.ndarray:
    """(sequences, tokens) of uniform ids for one optimizer step: every row
    of every step differs."""
    rng = np.random.default_rng([int(seed), 0x7EA1, int(step)])
    return rng.integers(
        0, vocab_size, (int(mix["sequences_per_step"]), int(mix["seq_len"])),
        dtype=np.int32,
    )
