"""Runs of ``runners/moe.py`` (a sparse-expert model on the serving runner):
bytes from ``flops_moe.py``, routing counters from ``moe_counters.py``; the
step's time, the paged-attention kernel and the live pages as
``serve.py`` reads them."""
import re
import sys

from benchmark import facts, families, flops_moe, moe_counters

_serve = families.load("serve")
decode_step_ms = _serve.decode_step_ms
paged_attention_ms_per_decode_step = _serve.paged_attention_ms_per_decode_step
paged_attention_live_page_share = _serve.paged_attention_live_page_share


def decode_step_bytes(run):
    """Attention, router and head weights once, the experts some LIVE token
    chose once (an expert nobody chose is not read, so counting all L x E
    would read high), and the live keys and values."""
    hit = moe_counters.decode_experts_hit_per_step(run)
    if hit is None:
        return None
    live = facts.live_kv_tokens(run, run["trace"]["t0"], run["trace"]["t1"])
    return flops_moe.decode_step_bytes(run["config"], live, hit)


def moe_gmm_ms_per_decode_step(run):
    """From ``run["trace"]["device_ops"]``, the ten operations that took
    most time, by the name the program gives the kernel: ``moe_gmm.<n>``
    (three a layer: gate, up, down). A program without the kernel has no
    such line, and neither has a run in which it is not among those ten:
    that is said on stderr."""
    trace = run["trace"]
    n, _ = trace.get("programs", {}).get("decode", (0, 0.0))
    ops = trace.get("device_ops", [])
    s = sum(s for name, s in ops if re.match(r"moe_gmm\.\d+", name)
            or name.startswith("moe_gmm:"))
    if n and ops and not s:
        print("kernels.moe_gmm_ms_per_decode_step: no moe_gmm operation "
              f"among the {len(ops)} listed; metric left out",
              file=sys.stderr)
    if not n or not s:
        return None
    return 1e3 * s / (n * run["serve_cfg"]["decode_steps_per_dispatch"])


def moe_gmm_step_s(run):
    kernel_ms = moe_gmm_ms_per_decode_step(run)
    return kernel_ms * 1e-3 if kernel_ms else None


def expert_bytes(run):
    """Only experts the engine's counter says were hit are counted, so the
    share cannot read high."""
    hit = moe_counters.decode_experts_hit_per_step(run)
    return None if hit is None else flops_moe.expert_bytes(run["config"], hit)


def experts_hit_share(run):
    d = moe_counters.window(run)
    if not d or not d["layer_steps"]:
        return None
    return 100.0 * d["experts_hit"] / (
        run["config"]["num_experts"] * d["layer_steps"])


def expert_load_imbalance(run):
    d = moe_counters.window(run)
    if not d or not sum(d["choices"]):
        return None
    return max(d["choices"]) / (sum(d["choices"]) / len(d["choices"]))
