"""What the serving-engine and model test files share: prompts, weights made
once a process, ONE set of engine shapes for the ``*-test`` presets, the
greedy reference loop, one jitted program a helper and the catalog's rows (the ``short_kda_chunks``
fixture is in ``tests/conftest.py``). Not a test module: nothing here is
collected.

The engine shapes are one set on purpose. An engine's programs are lowered
from its shapes, so ``gpt-test`` at these shapes is the SAME cold, suffix,
chunk and decode program in every file, and the run's compile cache
(``tests/conftest.py``) hands it to whichever worker asks second. A file
overrides a shape only where its property needs it, and says why there; a
new model's test file needs its reference, its config row and its
properties, not another harness."""

import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_training_and_inference_system_tpu.config import (
    get_model_config)
from distributed_llm_training_and_inference_system_tpu.config.schema import (
    ServeConfig)
from distributed_llm_training_and_inference_system_tpu.models import gpt, init
from distributed_llm_training_and_inference_system_tpu.serve import (
    InferenceEngine)

# slots, max_seq_len, page, prefill bucket, steps a dispatch: 32 pages a
# slot, a riding piece of 16 rows (one chunk of nemotron-h-test's scan)
SLOTS, SPAN, PS, BUCKET, STEPS = 4, 256, 8, 32, 4
LINEAR = "kimi-linear-test"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")

_FRESH = np.random.default_rng(36)
_PARAMS, _PROGRAMS = {}, {}


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(3, 250, n).tolist()


def fresh_tokens(n):
    """``n`` token ids off one stream of the process: a prompt no earlier
    case can have left in a prefix cache."""
    return _FRESH.integers(3, 250, n).tolist()


def _config(name_or_cfg):
    return (get_model_config(name_or_cfg) if isinstance(name_or_cfg, str)
            else name_or_cfg)


def params_of(name_or_cfg, seed=0):
    """``init(cfg, PRNGKey(seed))`` as ONE program, made once a process for
    each config and seed; a caller gets containers of its own (a file's
    ``seeded`` writes other leaves into them) around the shared arrays."""
    cfg = _config(name_or_cfg)
    key = (repr(cfg), seed)
    if key not in _PARAMS:
        _PARAMS[key] = jax.jit(functools.partial(init, cfg))(
            jax.random.PRNGKey(seed))
    return jax.tree_util.tree_map(lambda leaf: leaf, _PARAMS[key])


def serve_config(name, **over):
    opts = dict(model=name, max_batch_size=SLOTS, max_seq_len=SPAN,
                kv_block_size=PS, prefill_chunk=BUCKET, dtype="float32",
                decode_steps_per_dispatch=STEPS)
    if name == LINEAR:
        # its engines prefill a prompt over a piece's rows chunk by chunk: a
        # riding piece is held to the CHUNK programs, which read and write
        # the slot's state as it does
        opts["chunked_prefill_tokens"] = InferenceEngine.RIDE_PAGES * PS
    return ServeConfig(**{**opts, **over})


def engine(name_or_cfg, params=None, **over):
    """An engine of the shared shapes on ``params`` (default: ``params_of``
    the config), ``over`` laid over them."""
    cfg = _config(name_or_cfg)
    return InferenceEngine(
        cfg, serve_config(cfg.name, **over),
        params=params_of(cfg) if params is None else params, seed=0)


def program(fn, cfg):
    """``fn(..., cfg=cfg)`` jitted, once a process for each function and
    config: a test's window through the model as ONE compiled program, as an
    engine runs it, where op by op it is a thousand one-op programs (a
    compile each, most of a paged case's time)."""
    key = (fn, repr(cfg))
    if key not in _PROGRAMS:
        _PROGRAMS[key] = jax.jit(functools.partial(fn, cfg=cfg))
    return _PROGRAMS[key]


def _forward(params, tokens, *, cfg):
    return gpt.forward(params, tokens, cfg)


def forward(params, tokens, cfg):
    """The model's whole forward over ``tokens`` ([B, T]) as one program."""
    return program(_forward, cfg)(params, jnp.asarray(tokens))


def idle(eng):
    """A shared engine as a case must find and leave it: every request
    ended, no slot seated, no page held by a slot."""
    assert eng._reserved_pages == 0 and not eng._riding
    assert not eng._req_slot and not eng.active.any()
    assert all(r is None for r in eng.scheduler.slots)
    # every page is free or kept for a prefix hit: none is held by a slot
    assert eng.kv.free_pages == eng.kv.num_pages - 1


def greedy(last_logits, prompt, n):
    """``n`` tokens by argmax of ``last_logits(context)``, the reference's
    logits at the context's last position."""
    out = []
    for _ in range(n):
        out.append(int(np.asarray(last_logits(prompt + out)).argmax()))
    return out


def gaps(logits, params, prompt, served):
    """How far under the reference's best logit each served token lies
    (``logits(params, tokens)`` is a file's reference, [T, V]): 0 where it
    is the argmax."""
    lg = logits(params, prompt + served[:-1])[len(prompt) - 1:]
    return lg.max(-1) - lg[np.arange(len(served)), served]


def catalog_row(name):
    """The published ``config.json`` of ``name`` in the model-configs
    catalog; skips where the catalog or the row is not there."""
    if not CATALOG.exists():
        pytest.skip("no model-configs catalog here")
    for line in CATALOG.read_text().splitlines():
        row = json.loads(line)
        if row["name"] == name:
            return row["config"]
    pytest.skip(f"the catalog has no {name} row")
