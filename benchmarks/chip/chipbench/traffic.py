"""The one traffic generator: reads a mix's parameters, makes requests.

``closed_loop``: ``clients`` clients, each with one request in flight; a
client sends its next request the moment its last one finished.  Every
request asks for ``output_len`` tokens.  Prompt lengths are the mix's
``prompt_lens``, shared evenly among the clients: every seed sends the
same set of lengths, so every seed compiles the same shapes and does the
same work, and the seed only decides which client gets which length and
what the tokens are.  A client's k-th request takes the next length of
the set after its previous one, so the set in flight stays the same.
Tokens are uniform over the published vocabulary.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np


def prompt_lengths(traffic: dict) -> List[int]:
    """One length per client: the mix's ``prompt_lens``, each sent by as
    many clients as the count allows."""
    lens = [int(x) for x in traffic["prompt_lens"]]
    n = int(traffic["clients"])
    if n % len(lens):
        raise ValueError(f"{len(lens)} prompt lengths do not share {n} "
                         "clients evenly")
    return sorted(lens * (n // len(lens)))


class ClosedLoop:
    def __init__(self, traffic: dict, vocab_size: int, seed: int):
        if traffic["kind"] != "closed_loop":
            raise ValueError(f"unknown traffic kind {traffic['kind']!r}")
        self.n = int(traffic["clients"])
        self.output_len = int(traffic["output_len"])
        self.lengths = prompt_lengths(traffic)
        self.vocab = int(vocab_size)
        self.seed = int(seed)
        order = np.random.default_rng([self.seed, 0]).permutation(self.n)
        self._slot = [int(s) for s in order]     # client -> index in lengths
        self._sent = [0] * self.n                # requests sent per client

    @property
    def max_prompt(self) -> int:
        return max(self.lengths)

    def next_request(self, client: int) -> Tuple[np.ndarray, int]:
        """The client's next (prompt tokens, output length)."""
        k = self._sent[client]
        length = self.lengths[(self._slot[client] + k) % self.n]
        rng = np.random.default_rng([self.seed, 1, client, k])
        self._sent[client] += 1
        return (rng.integers(0, self.vocab, length).astype(np.int32),
                self.output_len)

    def first_requests(self) -> List[Tuple[int, np.ndarray, int]]:
        return [(c, *self.next_request(c)) for c in range(self.n)]
