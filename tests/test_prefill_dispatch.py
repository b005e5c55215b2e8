"""Between the last decode dispatch and the prefill program the engine
thread runs NO device program and waits on NO device value (PERF.md 6, PR
32): one prefill is one XLA program. Counted without a chip: after a
warm-up every cache of compiled programs is dropped (``jax.clear_caches``),
so every program the engine thread then runs is compiled anew and JAX logs
its name; a one-operation program (``jnp.float32(x)``,
``jax.random.PRNGKey(seed)``, ``jax.random.fold_in``) is a program like any
other. Before PR 32 the list read ``convert_element_type`` (four times),
``_threefry_seed``, ``_threefry_fold_in`` and ``prefill``, and the slot's
key was fetched from the device in front of the dispatch."""

import contextlib
import logging
import re

import jax
import numpy
import pytest

import serving_support as support
from distributed_llm_training_and_inference_system_tpu.serve import (
    SamplingParams)
from distributed_llm_training_and_inference_system_tpu.serve import (
    engine as engine_mod)

COMPILING = re.compile(r"Compiling (\S+) with global shapes")
SEEDED = dict(temperature=0.8, top_k=40, top_p=0.9)


class _Fetches:
    """numpy as the engine module sees it, noting every jax.Array handed to
    ``asarray`` / ``array``: on the CPU that conversion reads the buffer
    and never passes ``jax.Array._value``, which catches the other routes
    (``int(x)``, ``x.tolist()``, ``jax.device_get``)."""

    def __init__(self, seen: list):
        self._seen = seen

    def __getattr__(self, name):
        return getattr(numpy, name)

    def _noting(name):
        def convert(self, a, *args, **kwargs):
            if isinstance(a, jax.Array):
                self._seen.append(f"np.{name}")
            return getattr(numpy, name)(a, *args, **kwargs)
        return convert
    asarray, array = _noting("asarray"), _noting("array")


@contextlib.contextmanager
def watched(monkeypatch):
    """(programs compiled, device values fetched) while the block runs."""
    programs, fetched = [], []

    class Names(logging.Handler):
        def emit(self, record):
            m = COMPILING.search(record.getMessage())
            if m:
                programs.append(m.group(1))
    log = logging.getLogger("jax._src.interpreters.pxla")
    handler, level = Names(), log.level
    array_type = type(jax.numpy.zeros(()))
    value = array_type._value
    with monkeypatch.context() as mp:
        mp.setattr(engine_mod, "np", _Fetches(fetched))
        mp.setattr(array_type, "_value", property(
            lambda self: (fetched.append("_value"), value.fget(self))[1]))
        log.addHandler(handler)
        log.setLevel(logging.DEBUG)
        try:
            yield programs, fetched
        finally:
            log.setLevel(level)
            log.removeHandler(handler)


def _watch_method(eng, name, monkeypatch, seen):
    """Wrap the engine-thread method ``name``: what each call compiled and
    fetched is appended to ``seen``."""
    real = getattr(eng, name)

    def wrapper(*args):
        with watched(monkeypatch) as (programs, fetched):
            out = real(*args)
        seen.append((list(programs), list(fetched)))
        return out
    monkeypatch.setattr(eng, name, wrapper)


@pytest.mark.parametrize("model,program", [
    ("gpt-test", "jit(prefill)"), ("olmoe-test", "jit(prefill)"),
    ("nemotron-h-test", "jit(prefill)")])
def test_a_cold_prefill_is_one_program_and_fetches_nothing(
        model, program, monkeypatch):
    eng = support.engine(model)
    sp = SamplingParams(max_tokens=3, seed=7, **SEEDED)
    eng.generate([support.tokens(20, seed=1)], sp)    # warm-up: same bucket
    jax.clear_caches()
    seen = []
    _watch_method(eng, "_prefill", monkeypatch, seen)
    eng.generate([support.tokens(21, seed=2)], sp)
    assert seen == [([program], [])]


def test_a_suffix_prefill_is_one_program_and_fetches_nothing(monkeypatch):
    eng = support.engine("gpt-test")
    sp = SamplingParams(max_tokens=3, seed=7, **SEEDED)
    shared = support.tokens(32, seed=3)
    eng.generate([shared + support.tokens(9, seed=4)], sp)   # 4 pages kept
    eng.generate([shared + support.tokens(10, seed=5)], sp)  # warm-up: a hit
    jax.clear_caches()
    seen = []
    _watch_method(eng, "_prefill", monkeypatch, seen)
    before = eng.stats()["prefix_cached_tokens"]
    eng.generate([shared + support.tokens(11, seed=6)], sp)
    assert eng.stats()["prefix_cached_tokens"] == before + 32
    assert seen == [(["jit(extend_prefill)"], [])]


def test_a_chunked_prefill_is_one_program_a_chunk(monkeypatch):
    # (a budget of two pages: the one shape this property is about)
    eng = support.engine("gpt-test", chunked_prefill_tokens=16)
    sp = SamplingParams(max_tokens=3, seed=7, **SEEDED)
    eng.generate([support.tokens(41, seed=8)], sp)    # warm-up: 16 + 16 + 9
    jax.clear_caches()
    seen = []
    _watch_method(eng, "_start_chunked_prefill", monkeypatch, seen)
    _watch_method(eng, "_advance_chunked_prefills", monkeypatch, seen)
    eng.generate([support.tokens(42, seed=9)], sp)
    # admission dispatches nothing; the 16-row chunk program compiles once
    # and runs twice; the last chunk is the sampling program
    assert seen == [([], []), (["jit(extend_chunk)"], []), ([], []),
                    (["jit(extend_prefill)"], [])]


def test_the_watch_sees_a_one_operation_program_and_a_fetch(monkeypatch):
    """The hook itself: what the engine thread did before PR 32 is seen."""
    jax.clear_caches()
    with watched(monkeypatch) as (programs, fetched):
        key = jax.random.PRNGKey(5)
        engine_mod.np.asarray(jax.random.key_data(key))
        int(jax.numpy.int32(3))
    assert "jit(_threefry_seed)" in programs
    assert "jit(convert_element_type)" in programs
    assert fetched == ["np.asarray", "_value"]
