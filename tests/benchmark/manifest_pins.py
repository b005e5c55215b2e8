"""How the tests under ``tests/benchmark/`` hold ``BENCHMARK.json``: by NAME
and by MEMBERSHIP. "The manifest has an entry of this name, with this
``moves``, ``layer`` and unit, and its list holds this cell": never "it is
the last", never "the list equals", never a count of entries, so that a
later PR's append can fail none of them.

``MERGED_INTO`` is PR 59's table: the per-cell copies of shared readers that
left the manifest, and the entry that lists their cell since. A cell's test
names its metrics as its issue did and looks them up through ``entry``."""

import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
READERS = ROOT / "benchmark" / "layer_metrics"
CELLS = [c["name"] for c in MANIFEST["workloads"]]
CHAT = "mistral-7b-16l.chat"


def _merged(base: str, pattern: str, *prefixes: str) -> dict:
    return {pattern.format(p): base for p in prefixes}


MERGED_INTO = {
    **_merged("engine.prefill_ride_token_share",
              "engine.prefill_ride_token_share.{}", "doc-qa", "reason-docs",
              "reason-batch", "sessions", "chat-batch", "assist-batch"),
    **_merged("kernels.moe_gmm_hbm_roofline_share",
              "kernels.{}_moe_gmm_hbm_roofline_share", "hybrid", "latent",
              "linear", "diffusion", "sessions", "selfdraft", "shortconv"),
    **_merged("kernels.moe_gmm_ms_per_decode_step",
              "kernels.{}_moe_gmm_ms_per_decode_step", "hybrid", "linear",
              "shortconv"),
    **_merged("moe.held_experts_hit_share", "moe.{}_held_experts_hit_share",
              "linear", "sessions", "selfdraft"),
    "moe.shortconv_experts_hit_share": "moe.experts_hit_share",
    "moe.shortconv_expert_load_imbalance": "moe.expert_load_imbalance",
    **_merged("serve_programs.decode_hbm_roofline_share",
              "serve_programs.{}_decode_hbm_roofline_share", "moe", "hybrid",
              "latent", "linear", "sessions", "parallel", "selfdraft",
              "shortconv"),
    **_merged("kernels.paged_attention_ms_per_decode_step",
              "kernels.{}_paged_attention_ms_per_decode_step", "sessions",
              "parallel", "shortconv"),
    **_merged("kernels.paged_attention_roofline_share",
              "kernels.{}_paged_attention_roofline_share", "sessions",
              "parallel", "shortconv"),
    "kernels.shortconv_paged_attention_live_page_share":
        "kernels.paged_attention_live_page_share",
    "kernels.linear_mla_attention_ms_per_decode_step":
        "kernels.mla_attention_ms_per_decode_step",
    **_merged("kernels.mla_attention_roofline_share",
              "kernels.{}_mla_attention_roofline_share", "linear",
              "selfdraft"),
    **_merged("kernels.mla_live_page_share", "kernels.{}_mla_live_page_share",
              "linear", "selfdraft"),
    **_merged("kernels.kda_decode_ms_per_decode_step",
              "kernels.{}_kda_decode_ms_per_decode_step", "sessions"),
    **_merged("kernels.kda_decode_hbm_roofline_share",
              "kernels.{}_kda_decode_hbm_roofline_share", "sessions"),
    **_merged("kernels.ssm_decode_ms_per_decode_step",
              "kernels.{}_ssm_decode_ms_per_decode_step", "parallel"),
    **_merged("kernels.ssm_decode_hbm_roofline_share",
              "kernels.{}_ssm_decode_hbm_roofline_share", "parallel"),
    **_merged("kernels.ssm_prefill_roofline_share",
              "kernels.{}_ssm_prefill_roofline_share", "parallel"),
    "ssm.parallel_state_share_of_decode_bytes":
        "ssm.state_share_of_decode_bytes",
    "kv.selfdraft_prefix_cached_token_share": "kv.prefix_cached_token_share",
}


def by_name() -> dict:
    return {m["name"]: m for m in MANIFEST["per_layer"]}


def merged(name: str) -> str:
    """The name under which the manifest holds the reading today."""
    return MERGED_INTO.get(name, name)


def entry(name: str) -> dict:
    """The manifest's entry for the reading an issue called ``name``."""
    [found] = [m for m in MANIFEST["per_layer"] if m["name"] == merged(name)]
    return found


def assert_lists(name: str, cell: str, **fields) -> dict:
    """The entry exists once, has a reader's file, lists ``cell``, moves an
    end-to-end metric the cell reports, and has these ``fields``."""
    e = entry(name)
    assert cell in e["workloads"], (name, cell)
    assert (READERS / (e["name"] + ".py")).is_file(), e["name"]
    reported = {m["name"] for m in MANIFEST["end_to_end"]
                if cell in m.get("workloads", [cell])}
    assert e["moves"] in reported, (name, e["moves"], cell)
    for key, want in fields.items():
        assert e[key] == want, (name, key)
    return e


def listed_by(cell: str) -> set:
    """Names of the per-layer entries that list ``cell``."""
    return {m["name"] for m in MANIFEST["per_layer"]
            if cell in m.get("workloads", [cell])}
