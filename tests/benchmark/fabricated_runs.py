"""A run dict made by hand for every serving cell, as ``run.py`` hands it to
the per-layer readers: the cell's own configuration file, the stamp of its
family (the traffic kind's first word), and EVERY counter group, scope and
operation line any family's readers look for, with numbers that are no round
floats (so that two ways of writing one formula do not agree by luck).
``bare`` is the same run of a program from before the families' own
counters and scopes (a parent commit traced with this benchmark): no
``shortconv`` / ``mtp`` group, no by-scope seconds, no latent pool.

``tests/benchmark/merged_readers_golden.json`` holds what the per-cell
readers of the commit before PR 59 (325b885) gave on these runs; this module
imports nothing but the standard library so that it can be run against that
commit's ``benchmark`` package (``python tests/benchmark/fabricated_runs.py
<checkout of 325b885> > merged_readers_golden.json``)."""

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
LATENT_POOL = ("latent", "linear", "selfdraft")
DISPATCHES, STEPS = 8, 8
# the readers whose inputs the fabricated run holds (not the engine's
# phases, the start-up ledger, the load generator's stamps)
READ_FROM_THESE = ("kernels.", "moe.", "serve_programs.", "ssm.", "kda.",
                   "kv.", "residual.", "diffusion.", "selfdraft.",
                   "engine.prefill_ride_token_share")


def family_of(cell: dict, root: Path = ROOT) -> str:
    traffic = root / "benchmark" / "traffic" / (cell["traffic"] + ".json")
    return json.loads(traffic.read_text())["kind"].split("-")[0]


def serving_cells(manifest: dict, root: Path = ROOT) -> dict:
    """{cell name: family} of the cells that serve."""
    return {c["name"]: family_of(c, root) for c in manifest["workloads"]
            if family_of(c, root) != "train"}


def _snapshot(n: int, family: str, bare: bool, experts: int) -> dict:
    """``engine.stats()`` after ``n`` traced stretches' worth of work."""
    steps = DISPATCHES * STEPS
    s = {"decode_steps": 100 + n * steps,
         "prefill_tokens": 700 + n * 40_317,
         "prefill_padded_tokens": 1024 + n * 53_248,
         "prefill_ride_tokens": 300 + n * 39_113,
         "prefix_cached_tokens": 2_000 + n * 91_344,
         "kv": {"kind": "latent" if family in LATENT_POOL and not bare
                else "kv", "page_size": 128, "bytes_per_token": 8960,
                "live_pages": 1_000 + n * DISPATCHES * 3_307,
                "table_pages": 10_000 + n * DISPATCHES * 64 * 17},
         "moe": {"choices": [n * (97 + 3 * (i % 7)) for i in range(experts)],
                 "held_choices": n * 6_411, "all_choices": n * 12_822,
                 "experts_hit": n * 29_517, "layer_steps": n * 486,
                 "decode_experts_hit": n * 29_213,
                 "decode_layer_steps": n * steps * 6}}
    if not bare:
        s["ssm"] = {"slot_steps": n * steps * 59, "state_bytes": 1 << 28}
        s["kda"] = {"slot_steps": n * steps * 61, "state_bytes": 1 << 28,
                    "state_carry_tokens": n * 4_001}
        s["shortconv"] = {"slot_steps": n * steps * 251,
                          "state_bytes": 1 << 20}
        s["diffusion"] = {"block_length": 4, "forwards": n * steps,
                          "slot_forwards": n * 4_033,
                          "live_pages": n * 13_007}
        if family == "selfdraft":
            s.update(mtp={"refused": {"riding": 0}}, mtp_drafts=n * 6_400,
                     mtp_accepted=n * 1_633, mtp_slot_steps=n * 6_400,
                     mtp_tokens=n * 8_033)
    return s


def fabricated(cell: dict, bare: bool = False, root: Path = ROOT,
               manifest: dict | None = None) -> dict:
    manifest = manifest or json.loads((root / "BENCHMARK.json").read_text())
    family = family_of(cell, root)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == cell["config"])
    config = json.loads((root / entry["file"]).read_text())
    experts = max(int(config.get("num_experts") or 0),
                  int(config.get("n_routed_experts") or 0), 8)
    stats = {"before": _snapshot(1, family, bare, experts),
             "after": _snapshot(2, family, bare, experts)}
    scopes = {"moe_gmm": (1152, 0.7717), "moe_gmm_prefill": (18, 0.3119),
              "paged_attention": (448, 0.1043),
              "paged_attention_mq": (12, 0.0411),
              "mla_paged_attention": (448, 0.8961),
              "mla_paged_attention_mq": (360, 0.4173),
              "kda_decode": (576, 0.2213), "ssm_decode": (480, 0.3207),
              "ssm_scan_prefill": (12, 0.0231)}
    trace = {"programs": {"decode": (DISPATCHES, 1.6127),
                          "prefill": (5, 0.3011)},
             "t0": 10.0, "t1": 15.0, "busy_s": 4.7, "window_s": 5.0,
             "device_ops": [["moe_gmm.30:tpu_custom_call", 0.2713],
                            ["moe_gmm.31:tpu_custom_call", 0.2609],
                            ["moe_gmm.32", 0.2507],
                            ["moe_gmm_prefill.7:tpu_custom_call", 0.9],
                            ["paged_attention.7:tpu_custom_call", 0.1043],
                            ["paged_attention_mq.2:tpu_custom_call", 0.3],
                            ["fusion.244", 0.8017]]}
    if not bare:
        trace.update(scope_s=scopes, decode_scope_s=scopes,
                     program_scope_s={"decode": scopes})
    records = [{"chunks": [9.0 - 0.01 * i, 16.0 + 0.02 * i], "tokens": 30 + i,
                "prompt_tokens": 600 + 7 * i} for i in range(64)]
    return {"kind": "serve", "runner": family, "config": config,
            "device": {"kind": "TPU v5 lite"},
            "serve_cfg": {"decode_steps_per_dispatch": STEPS,
                          "max_batch_size": 64},
            "stats": stats, "trace_stats": stats, "trace": trace,
            "stamps": {"kind": "serve-closed", "records": records}}


def golden(checkout: Path) -> dict:
    """What ``checkout``'s per-layer readers give on the fabricated run of
    every serving cell: {cell: {full | bare: {metric: value}}} over the
    metrics that list the cell THERE."""
    sys.path.insert(0, str(checkout))
    from benchmark import layer_metrics
    theirs = json.loads((checkout / "BENCHMARK.json").read_text())
    out = {}
    for cell in theirs["workloads"]:
        if family_of(cell, checkout) == "train":
            continue
        listed = [m["name"] for m in theirs["per_layer"]
                  if cell["name"] in m.get("workloads", [cell["name"]])]
        out[cell["name"]] = {
            which: {name: layer_metrics.load(name).read(
                fabricated(cell, bare, checkout, theirs)) for name in listed
                if name.startswith(READ_FROM_THESE)}
            for which, bare in (("full", False), ("bare", True))}
    return out


if __name__ == "__main__":
    json.dump(golden(Path(sys.argv[1]).resolve()), sys.stdout, indent=1)
