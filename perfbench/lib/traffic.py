"""One general traffic generator. A traffic mix is a data file of
parameters under ``perfbench/traffic/``; this module turns such a file and
a seed into inputs. The rule that keeps cells steady: a file fixes the
multiset of sizes, and the seed decides only their order and the token
ids. Nothing is drawn afresh, so every seed does the same work."""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List, Sequence, Tuple

import numpy as np

#: token ids below this are reserved (pad, bos, eos) in the program's
#: tokenizers; generated ids stay clear of them and of the last id
FIRST_TOKEN_ID = 3


def lognormal_grid(n: int, median: float, sigma: float,
                   lo: int, hi: int) -> List[int]:
    """The n-point quantile grid of a lognormal, clipped to [lo, hi]:
    the value at probability (i + 0.5) / n for i in 0..n-1, rounded to a
    whole number. A grid, not a draw: the same n values every time."""
    if n < 1 or lo > hi or median <= 0 or sigma < 0:
        raise ValueError(f"bad grid: n={n} median={median} sigma={sigma} "
                         f"clip={lo}..{hi}")
    normal = NormalDist()
    out = []
    for i in range(n):
        z = normal.inv_cdf((i + 0.5) / n)
        out.append(int(min(hi, max(lo, round(median * math.exp(sigma * z))))))
    return out


def sizes(spec: Dict, n: int) -> List[int]:
    """A traffic file's size block -> n whole numbers, ascending."""
    dist = spec.get("dist")
    if dist == "lognormal_grid":
        return lognormal_grid(n, float(spec["median"]), float(spec["sigma"]),
                              int(spec["min"]), int(spec["max"]))
    if dist == "list":
        values = [int(v) for v in spec["values"]]
        if len(values) != n:
            raise ValueError(f"list of {len(values)} sizes, {n} wanted")
        return sorted(values)
    raise ValueError(f"unknown size distribution {dist!r}")


def _rng(seed: int, *stream: int) -> np.random.Generator:
    # SeedSequence takes whole numbers of any size, so the driver's
    # seeds above 2**31 need no folding
    return np.random.default_rng([int(seed), *[int(s) for s in stream]])


def token_ids(seed: int, stream: Sequence[int], n: int, vocab: int
              ) -> np.ndarray:
    return _rng(seed, *stream).integers(
        FIRST_TOKEN_ID, vocab - 1, size=n, dtype=np.int32)


class ClosedLoopTraffic:
    """``clients`` callers, each sending its next request when the last
    has finished. The file fixes a grid of (prompt length, output length)
    pairs, one per client: the quantiles of the two marginals, paired by
    the permutation in the file. A client's k-th request is one element
    of the k-th pass through the grid; the seed decides which client gets
    which element in each pass, and the token ids."""

    def __init__(self, spec: Dict, seed: int, vocab: int):
        self.n = int(spec["clients"])
        prompts = sizes(spec["prompt"], self.n)
        outputs = sizes(spec["output"], self.n)
        pairing = [int(i) for i in spec["pairing"]]
        if sorted(pairing) != list(range(self.n)):
            raise ValueError("pairing is not a permutation of the clients")
        self.grid: List[Tuple[int, int]] = [
            (prompts[i], outputs[pairing[i]]) for i in range(self.n)]
        self.seed = int(seed)
        self.vocab = int(vocab)
        self._passes: Dict[int, np.ndarray] = {}

    def element(self, client: int, k: int) -> int:
        if k not in self._passes:
            self._passes[k] = _rng(self.seed, 1, k).permutation(self.n)
        return int(self._passes[k][client])

    def request(self, client: int, k: int) -> Tuple[List[int], int]:
        """(prompt token ids, output length) of a client's k-th request."""
        plen, olen = self.grid[self.element(client, k)]
        ids = token_ids(self.seed, (2, k, client), plen, self.vocab)
        return [int(t) for t in ids], olen


def document_lengths(spec: Dict, seed: int) -> np.ndarray:
    """The fixed grid of document lengths of a packed-training file,
    shuffled by the seed (the order decides the packing, not the set)."""
    n = int(spec["documents"])
    lengths = np.asarray(sizes(spec["length"], n), np.int32)
    return lengths[_rng(seed, 3).permutation(n)]


class _Pad:
    pad_token_id = 0


class SyntheticDocuments:
    """Tokenized instruction examples in the protocol the program's
    ``PackedInstructionDataset`` takes (``tokenizer.pad_token_id``,
    ``__len__``, ``__getitem__`` -> input_ids / attention_mask / labels):
    document i has the i-th shuffled grid length, token ids from the
    seed, and its first ``prompt_share`` masked out of the loss as the
    prompt of an instruction pair would be."""

    IGNORE_INDEX = -100
    tokenizer = _Pad()

    def __init__(self, spec: Dict, seed: int, vocab: int):
        self.lengths = document_lengths(spec, seed)
        self.prompt_share = float(spec["prompt_share"])
        self.seed = int(seed)
        self.vocab = int(vocab)

    def __len__(self) -> int:
        return len(self.lengths)

    def __getitem__(self, i: int) -> Dict[str, np.ndarray]:
        n = int(self.lengths[i])
        ids = token_ids(self.seed, (4, i), n, self.vocab)
        labels = ids.copy()
        labels[:int(n * self.prompt_share)] = self.IGNORE_INDEX
        return {"input_ids": ids, "attention_mask": np.ones(n, np.int32),
                "labels": labels}
