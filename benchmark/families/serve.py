"""Runs of ``runners/serve.py`` (a dense model, GQA pages): bytes from
``flops.py``, live keys and values from the benchmark's own slot ledger
(``facts.live_kv_tokens``)."""
import sys

from benchmark import facts, flops, layer_metrics


def decode_step_ms(run):
    """Device ms of a decode step, as
    ``serve_programs.decode_step_device_ms`` reads it."""
    return layer_metrics.load("serve_programs.decode_step_device_ms").read(run)


def decode_step_bytes(run):
    """Weights once and the live keys and values."""
    live = facts.live_kv_tokens(run, run["trace"]["t0"], run["trace"]["t1"])
    return flops.decode_step_bytes(run["config"], live)


def paged_attention_ms_per_decode_step(run):
    """From ``run["trace"]["device_ops"]``, the ten operations that took
    most time, by the name the program gives the kernel
    (``paged_attention``; the multi-query kernel of a suffix prefill is
    ``paged_attention_mq`` and is not counted). A program whose kernels
    carry no names has no such line, and neither has a run in which the
    kernel is not among those ten: that is said on stderr
    (``trace_reduce`` keeps no seconds by operation name beyond the list;
    PERF.md section 7)."""
    trace = run["trace"]
    n, _ = trace.get("programs", {}).get("decode", (0, 0.0))
    ops = trace.get("device_ops", [])
    seconds = sum(s for name, s in ops if "paged_attention" in name
                  and "paged_attention_mq" not in name)
    if n and ops and not seconds:
        # the list holds the ten longest operations only: a kernel that
        # falls under the tenth is not read as 0, it is not read at all
        print("kernels.paged_attention_ms_per_decode_step: no "
              f"paged_attention operation among the "
              f"{len(ops)} listed (shortest {min(s for _, s in ops):.4f} s);"
              " metric left out", file=sys.stderr)
    if not n or not seconds:
        return None
    return 1e3 * seconds / (n * run["serve_cfg"]["decode_steps_per_dispatch"])


def paged_attention_live_page_share(run):
    a, b = run["stats"]["before"]["kv"], run["stats"]["after"]["kv"]
    if "table_pages" not in a or "table_pages" not in b:
        return None
    table = b["table_pages"] - a["table_pages"]
    if table <= 0:
        return None
    return 100.0 * (b["live_pages"] - a["live_pages"]) / table
