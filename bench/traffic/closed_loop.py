"""Closed-loop traffic: a fixed number of clients, each sending its next
request the moment its previous one ends (the paper's evaluation harness:
parallel clients, each waiting for its answer).

Mix parameters (``bench/mixes/<mix>.json``, ``"kind": "closed_loop"``):

* ``clients``            clients, one request in flight each;
* ``prompt_tokens``      [lo, hi] context tokens of a request;
* ``new_tokens``         [lo, hi] tokens to generate;
* ``grid``               sizes are drawn from a grid of this many evenly
                         spaced points over each range;
* ``sampling``           null (greedy) or {"temperature", "top_p"};
* ``greedy_clients``     with ``sampling``, this many clients stay greedy.

Every seed gets the same sizes in another order, so the seed changes the
order of the work and the token ids, not its amount:

* the initial sessions stand for requests already in flight: client i's
  context is the i-th of ``clients`` evenly spaced points of
  ``prompt_tokens``, and its remaining tokens the i-th of ``clients``
  evenly spaced points of (0, ``new_tokens[1]``], the two assignments
  permuted by the seed;
* each later request of a client takes the next entry of the client's own
  seeded permutation of the grid, for the prompt and for the new tokens.

Prompt token ids are uniform over the vocabulary.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np


def _points(lo: int, hi: int, n: int) -> List[int]:
    return [int(lo + (hi - lo) * (i + 0.5) / n) for i in range(n)]


class Traffic:
    def __init__(self, mix: Dict[str, Any], seed: int, vocab: int):
        self.mix = mix
        self.vocab = vocab
        self.rng = np.random.default_rng(seed)
        self.clients = int(mix["clients"])
        lo_p, hi_p = mix["prompt_tokens"]
        lo_n, hi_n = mix["new_tokens"]
        grid = int(mix["grid"])
        self.prompt_grid = _points(lo_p, hi_p + 1, grid)
        self.new_grid = _points(lo_n, hi_n + 1, grid)
        self.order = [(self.rng.permutation(grid), self.rng.permutation(grid))
                      for _ in range(self.clients)]
        self.count = [0] * self.clients
        self.sampling = mix.get("sampling")
        self.greedy_clients = int(mix.get("greedy_clients", self.clients)
                                  if self.sampling else self.clients)
        self.next_rid = 0
        self.queue: List[Dict[str, Any]] = []   # due, not yet handed over

    def _request(self, client: int, prompt_len: int, new: int
                 ) -> Dict[str, Any]:
        rid = self.next_rid
        self.next_rid += 1
        greedy = client < self.greedy_clients
        return {"rid": rid, "client": client, "greedy": greedy,
                "tokens": self.rng.integers(0, self.vocab, size=prompt_len,
                                            dtype=np.int32),
                "max_new_tokens": int(new),
                "sampling": None if greedy else self.sampling}

    def initial(self) -> List[Dict[str, Any]]:
        """The sessions in flight when the run starts."""
        lo_p, hi_p = self.mix["prompt_tokens"]
        prompts = self.rng.permutation(_points(lo_p, hi_p + 1, self.clients))
        hi_n = self.mix["new_tokens"][1]
        left = self.rng.permutation(
            [max(1, int(np.ceil(hi_n * (i + 0.5) / self.clients)))
             for i in range(self.clients)])
        return [self._request(c, int(prompts[c]), int(left[c]))
                for c in range(self.clients)]

    def finished(self, client: int, t: float) -> None:
        """The client's request ended at ``t``: its next one is due then."""
        p_ord, n_ord = self.order[client]
        j = self.count[client] % len(p_ord)
        self.count[client] += 1
        req = self._request(client, self.prompt_grid[p_ord[j]],
                            self.new_grid[n_ord[j]])
        req["due"] = t
        self.queue.append(req)

    def due(self, now: float) -> List[Dict[str, Any]]:
        out = [r for r in self.queue if r["due"] <= now]
        self.queue = [r for r in self.queue if r["due"] > now]
        return out

    def max_lifetime_tokens(self) -> int:
        return int(self.mix["prompt_tokens"][1] + self.mix["new_tokens"][1])

    def prompt_lengths(self) -> List[int]:
        """Every prompt length the traffic can send (for the warm-up)."""
        lo_p, hi_p = self.mix["prompt_tokens"]
        return sorted(set(self.prompt_grid)
                      | set(_points(lo_p, hi_p + 1, self.clients)))

    def sampled(self) -> Optional[Dict[str, Any]]:
        return self.sampling if self.greedy_clients < self.clients else None
