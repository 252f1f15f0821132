"""The one traffic generator: turns a mix's data file and a seed into
documents and request segments.

A mix (``traffic/<name>.json``) gives sizes, not code; a heavier model
that fits fewer lanes on a chip says so in its configuration's
``engine.lanes``, which the harness merges over the mix's, and keeps the
mix's traffic:

* ``generator: "documents"`` — a fixed library of ``documents.count``
  documents asked again and again. Their lengths are the quantiles of a
  log-normal (``median``, ``sigma``), clipped and rounded to
  ``multiple``; popularity is Zipf(``popularity.zipf_a``) over ranks.
* ``generator: "unique"`` — every request brings a prompt of its own,
  with lengths cycling through ``prompt_tokens.values``.

Every seed gets the same work: the lengths, the requests each document
receives in a segment (Zipf frequencies by largest remainder), the
question and answer lengths and the inter-arrival gaps (quantiles of the
exponential at ``rate_hz``) are fixed by the mix, and so is their order
within segment k (``_order``, drawn from k alone); the seed chooses token
content. The order is not the seed's because the engine serves a
segment's events one after another on the host: which document comes
first sets how much of the others' work lies inside each request's wall
TTFT, so an order drawn from the seed would change the work. Popularity
rank r always holds the same length slot (``interleave``), so the hottest
documents mix short and long. Arrival times are simulated seconds for the
engine's event clock.

Token content copies the structure of the program's QA generator
(``[SEP key value value]`` facts, questions naming a key), so that no
operation meets an id outside the vocabulary.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Tuple

import numpy as np

SEP, ASK = 5, 6


@dataclasses.dataclass
class Doc:
    key: str
    tokens: np.ndarray


@dataclasses.dataclass
class Req:
    req_id: int
    doc_key: str
    question: np.ndarray
    answer_tokens: int
    arrival_s: float


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2 ** 63 - 1), *stream])


def _order(k: int) -> np.random.Generator:
    """The order of segment ``k``'s requests: the same for every seed."""
    return np.random.default_rng([7, k])


def lognormal_lengths(n: int, median: float, sigma: float, lo: int, hi: int,
                      multiple: int) -> List[int]:
    """``n`` quantiles of a log-normal, clipped to [lo, hi] and rounded to
    ``multiple``; ascending."""
    from statistics import NormalDist
    nd = NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        x = median * math.exp(sigma * z)
        x = min(max(x, lo), hi)
        out.append(int(max(lo, min(hi, round(x / multiple) * multiple))))
    return sorted(out)


def zipf_counts(n_items: int, n_requests: int, a: float) -> List[int]:
    """Requests per rank for one segment: Zipf(a) shares by largest
    remainder, so every segment holds the same counts."""
    w = np.array([1.0 / (r + 1) ** a for r in range(n_items)])
    share = w / w.sum() * n_requests
    base = np.floor(share).astype(int)
    rest = n_requests - int(base.sum())
    order = np.argsort(-(share - base), kind="stable")
    base[order[:rest]] += 1
    return base.tolist()


def spread(lo: int, hi: int, n: int) -> List[int]:
    """``n`` whole numbers spread evenly over [lo, hi]."""
    if n <= 1 or lo == hi:
        return [int(lo)] * n
    return [int(round(lo + (hi - lo) * i / (n - 1))) for i in range(n)]


def interleave(n: int) -> List[int]:
    """A fixed order of ``n`` ascending length slots for popularity ranks
    0..n-1 that mixes short and long among the hottest: rank r takes slot
    (r * s) mod n for the stride s nearest n/2 that is coprime with n."""
    s = next(c for c in sorted(range(1, n + 1), key=lambda c: abs(c - n / 2))
             if math.gcd(c, n) == 1) if n > 1 else 1
    return [(r * s) % n for r in range(n)]


def exp_gaps(n: int, rate_hz: float) -> List[float]:
    """``n`` quantiles of the exponential inter-arrival time."""
    return [-math.log(1.0 - (i + 0.5) / n) / rate_hz for i in range(n)]


def qa_tokens(rng: np.random.Generator, vocab: int, length: int
              ) -> Tuple[np.ndarray, np.ndarray]:
    n = -(-length // 4)
    keys = rng.integers(vocab // 4, vocab // 2, n)
    vals = rng.integers(vocab // 2, vocab - 8, (n, 2))
    toks = np.stack([np.full(n, SEP), keys, vals[:, 0], vals[:, 1]],
                    axis=1).reshape(-1)[:length]
    return toks.astype(np.int32), keys


def question(rng: np.random.Generator, keys: np.ndarray, vocab: int,
             length: int) -> np.ndarray:
    q = np.empty(length, np.int32)
    q[0] = ASK
    q[1:] = rng.choice(keys, length - 1) if len(keys) else \
        rng.integers(8, vocab - 8, length - 1)
    return q


class Traffic:
    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix = mix
        self.seed = seed
        self.vocab = vocab
        self.kind = mix["generator"]
        if self.kind not in ("documents", "unique"):
            raise ValueError(f"unknown generator {self.kind!r}")
        self.n_seg = int(mix["segment_requests"])
        self.rate = float(mix["rate_hz"])
        self.docs: List[Doc] = []
        self._doc_keys: List[np.ndarray] = []
        # everything handed to the engine, for the check afterwards
        self.prompts: Dict[str, np.ndarray] = {}
        self.asked: Dict[int, Req] = {}
        if self.kind == "documents":
            d = mix["documents"]
            ln = d["length"]
            lengths = lognormal_lengths(d["count"], ln["median"], ln["sigma"],
                                        ln["min"], ln["max"], ln["multiple"])
            rng = _rng(seed, 0)
            slots = interleave(len(lengths))
            for i in range(d["count"]):
                toks, keys = qa_tokens(rng, vocab, lengths[slots[i]])
                self.docs.append(Doc(f"doc-{i}", toks))
                self._doc_keys.append(keys)
                self.prompts[f"doc-{i}"] = toks

    def _note(self, docs: List[Doc], reqs: List[Req]) -> None:
        for d in docs:
            self.prompts[d.key] = d.tokens
        for r in reqs:
            self.asked[r.req_id] = r

    # -- segments ----------------------------------------------------------
    def fill_requests(self, start_id: int) -> List[Req]:
        """One request per document, arriving together: what set-up sends
        to prefill and store every document through the engine."""
        rng = _rng(self.seed, 1)
        qmin, _ = self._qlen()
        reqs = [Req(start_id + i, d.key,
                    question(rng, self._doc_keys[i], self.vocab, qmin),
                    self._answers()[0], 0.0)
                for i, d in enumerate(self.docs)]
        self._note([], reqs)
        return reqs

    def warm_requests(self, start_id: int) -> Tuple[List[Doc], List[Req]]:
        """For ``unique``: one fresh prompt at every prompt length, with the
        longest question and answer, arriving together."""
        rng = _rng(self.seed, 4)
        _, qmax = self._qlen()
        docs, reqs = [], []
        for i, n in enumerate(self.prompt_lengths()):
            toks, keys = qa_tokens(rng, self.vocab, n)
            docs.append(Doc(f"warm-{i}", toks))
            reqs.append(Req(start_id + i, docs[-1].key,
                            question(rng, keys, self.vocab, qmax),
                            max(self._answers()), 0.0))
        self._note(docs, reqs)
        return docs, reqs

    def _qlen(self) -> Tuple[int, int]:
        q = self.mix["question_tokens"]
        return int(q["min"]), int(q["max"])

    def _answers(self) -> List[int]:
        return [int(a) for a in self.mix["answer_tokens"]]

    def segment(self, k: int, t0: float) -> Tuple[List[Doc], List[Req]]:
        """Segment ``k`` (0-based), arrivals from simulated time ``t0``.
        Returns the documents it introduces (all of them for
        ``unique``, none for ``documents``) and its requests."""
        n = self.n_seg
        rng, order = _rng(self.seed, 2, k), _order(k)
        qmin, qmax = self._qlen()
        qlens = order.permutation(spread(qmin, qmax, n))
        ans = self._answers()
        alens = order.permutation([ans[i % len(ans)] for i in range(n)])
        gaps = order.permutation(exp_gaps(n, self.rate))
        arrivals = t0 + np.cumsum(gaps)
        base = (k + 1) * 1_000_000
        new_docs: List[Doc] = []
        reqs: List[Req] = []
        if self.kind == "documents":
            counts = zipf_counts(len(self.docs), n,
                                 float(self.mix["popularity"]["zipf_a"]))
            targets = order.permutation(
                [r for r, c in enumerate(counts) for _ in range(c)])
            for i in range(n):
                di = int(targets[i])
                reqs.append(Req(base + i, self.docs[di].key,
                                question(rng, self._doc_keys[di], self.vocab,
                                         int(qlens[i])),
                                int(alens[i]), float(arrivals[i])))
        else:
            vals = [int(v) for v in self.mix["prompt_tokens"]["values"]]
            plens = order.permutation([vals[i % len(vals)]
                                       for i in range(n)])
            for i in range(n):
                toks, keys = qa_tokens(rng, self.vocab, int(plens[i]))
                doc = Doc(f"u{k}-{i}", toks)
                new_docs.append(doc)
                reqs.append(Req(base + i, doc.key,
                                question(rng, keys, self.vocab,
                                         int(qlens[i])),
                                int(alens[i]), float(arrivals[i])))
        self._note(new_docs, reqs)
        return new_docs, reqs

    def longest_sequence(self) -> int:
        """Context, question and answer tokens of the longest request the
        mix can send."""
        return (max(self.prompt_lengths()) + self._qlen()[1]
                + max(self._answers()))

    def prompt_lengths(self) -> List[int]:
        """Every context length this mix sends (the shapes to warm)."""
        if self.kind == "documents":
            return sorted({len(d.tokens) for d in self.docs})
        return sorted({int(v) for v in self.mix["prompt_tokens"]["values"]})
