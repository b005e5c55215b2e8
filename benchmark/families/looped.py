"""Runs of ``runners/looped.py`` (one dense stack walked several times over
one set of weights, K and V kept per (pass, layer)): bytes from
``flops_looped.py``, the decode program's by-scope seconds, the live rows (the
benchmark's own stamps) and the engine's page counts from
``looped_counters.py``. The kernel's time is every plane's
call of a step (passes x layers of them)."""
from benchmark import families, flops_looped, looped_counters

_serve = families.load("serve")
decode_step_ms = _serve.decode_step_ms
decode_step_bytes = looped_counters.decode_step_bytes
live_kv_tokens = looped_counters.live_kv_tokens
paged_attention_live_page_share = _serve.paged_attention_live_page_share


def paged_attention_ms_per_decode_step(run):
    """``paged_attention`` in the runner's by-scope seconds of the decode
    program: one query a slot, once a plane."""
    return looped_counters.decode_scope_ms_per_step(run, "paged_attention")


def kv_bytes_per_token(run):
    """Of every plane: passes x layers x K and V of every head."""
    return flops_looped.kv_bytes_per_token(run["config"])
