"""The load generator's child for a document question-answering mix
(traffic ``kind`` ``latent-closed``): ``benchmark/loadgen.py`` as it is, with
the requests drawn by DOCUMENT. No JAX.

``benchmark/traffic.py`` knows one shared prefix; this mix has
``documents.count`` of them. A request is a document then a question:

- the documents' lengths and token ids come from the file's ``shape_seed``
  (the same documents for every ``--seed``);
- ``questions_per_document`` questions a document make the pool; question
  and output LENGTHS come from ``shape_seed`` too, so every seed does the
  same work;
- the run's seed draws the question ids and puts the pool in its order.

``python -m benchmark.loadgen_docqa`` takes ``benchmark.loadgen``'s
arguments; the runner (``runners/latent.py``) starts it where the serving
runner starts ``benchmark.loadgen``.
"""

from __future__ import annotations

import sys

import numpy as np

from benchmark import loadgen
from benchmark.traffic import _FIRST_PLAIN_ID, describe, draw_lengths, load

__all__ = ["describe", "documents", "load", "requests"]


def documents(traffic: dict, vocab: int) -> list:
    """The mix's documents as lists of token ids, from ``shape_seed``."""
    spec = traffic["documents"]
    rng = np.random.default_rng([int(traffic.get("shape_seed", 0)), 1])
    lengths = draw_lengths(spec["tokens"], int(spec["count"]), rng)
    return [rng.integers(_FIRST_PLAIN_ID, vocab, size=int(n)).tolist()
            for n in lengths]


def requests(traffic: dict, seed: int, horizon_s: float, vocab: int) -> list:
    """The pool of one run, ``{"due": None, "prompt", "max_tokens"}`` in
    sending order (closed loop: the callers take them in order)."""
    if traffic["kind"].split("-", 1)[1] != "closed":
        raise ValueError(f"a document mix is closed-loop: {traffic['kind']!r}")
    docs = documents(traffic, vocab)
    per_doc = int(traffic["questions_per_document"])
    n = len(docs) * per_doc
    if n != int(traffic["clients"]) * int(traffic.get("pool_per_client", 1)):
        raise ValueError("documents x questions must be the callers' pool")
    shape = np.random.default_rng(int(traffic.get("shape_seed", 0)))
    order = np.random.default_rng(int(seed))
    q_len = draw_lengths(traffic["question_tokens"], n, shape)
    o_len = draw_lengths(traffic["output_tokens"], n, shape)
    reqs = []
    for i in order.permutation(n):
        question = order.integers(_FIRST_PLAIN_ID, vocab,
                                  size=int(q_len[i])).tolist()
        reqs.append({"due": None, "prompt": docs[i // per_doc] + question,
                     "max_tokens": int(o_len[i])})
    return reqs


if __name__ == "__main__":
    # loadgen.main() reads the file, draws the requests and describes them
    # through its ``traffic_mod``: this module stands there
    loadgen.traffic_mod = sys.modules[__name__]
    sys.exit(loadgen.main())
