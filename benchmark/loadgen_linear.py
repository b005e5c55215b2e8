"""The load generator's child for a reasoning mix with a tail of documents
(traffic ``kind`` ``linear-closed``): ``benchmark/loadgen.py`` as it is,
with the requests drawn by this file's rule. No JAX.

The pool is ``clients * pool_per_client`` requests. One in
``documents.every`` carries a document in front of its question; such a
request stands at every ``every``-th place of the pool (0, ``every``, ...)
for every ``--seed``:

- the question, document and output LENGTHS, and which questions carry
  which document, come from the file's ``shape_seed``: the pool is the same
  multiset of (prompt tokens, output tokens) for every seed;
- the run's seed permutes the document requests among their places and the
  plain requests among theirs, and draws the token ids.

``python -m benchmark.loadgen_linear`` takes ``benchmark.loadgen``'s
arguments; the runner (``runners/linear.py``) starts it where the serving
runner starts ``benchmark.loadgen``.
"""

from __future__ import annotations

import sys

import numpy as np

from benchmark import loadgen
from benchmark.traffic import _FIRST_PLAIN_ID, describe, draw_lengths, load

__all__ = ["describe", "load", "requests", "shapes"]


def shapes(traffic: dict) -> tuple[list, list]:
    """([(document tokens, question tokens, output tokens)] of the document
    requests, [(question tokens, output tokens)] of the plain ones), from
    ``shape_seed``."""
    n = int(traffic["clients"]) * int(traffic.get("pool_per_client", 1))
    every = int(traffic["documents"]["every"])
    if n % every:
        raise ValueError("the pool must be whole groups of documents.every")
    rng = np.random.default_rng(int(traffic.get("shape_seed", 0)))
    q_len = draw_lengths(traffic["question_tokens"], n, rng)
    o_len = draw_lengths(traffic["output_tokens"], n, rng)
    d_len = draw_lengths(traffic["documents"]["tokens"], n // every, rng)
    docs = [(int(d), int(q_len[i]), int(o_len[i]))
            for i, d in enumerate(d_len)]
    plain = [(int(q), int(o)) for q, o in zip(q_len[len(docs):],
                                              o_len[len(docs):])]
    return docs, plain


def requests(traffic: dict, seed: int, horizon_s: float, vocab: int) -> list:
    """The pool of one run, ``{"due": None, "prompt", "max_tokens"}`` in
    sending order (closed loop: the callers take them in order)."""
    if traffic["kind"].split("-", 1)[1] != "closed":
        raise ValueError(f"a reasoning mix is closed-loop: {traffic['kind']!r}")
    docs, plain = shapes(traffic)
    every = int(traffic["documents"]["every"])
    order = np.random.default_rng(int(seed))
    docs = [docs[i] for i in order.permutation(len(docs))]
    plain = [plain[i] for i in order.permutation(len(plain))]
    reqs = []
    for place in range(len(docs) + len(plain)):
        if place % every == 0:
            d, q, o = docs[place // every]
        else:
            (q, o), d = plain[place - place // every - 1], 0
        reqs.append({"due": None, "max_tokens": o, "prompt": order.integers(
            _FIRST_PLAIN_ID, vocab, size=d + q).tolist()})
    return reqs


if __name__ == "__main__":
    # loadgen.main() reads the file, draws the requests and describes them
    # through its ``traffic_mod``: this module stands there
    loadgen.traffic_mod = sys.modules[__name__]
    sys.exit(loadgen.main())
