"""`llmctl serve` — start the inference server.

Parity: reference cli/commands/serve.py:16-61, with the --scheduler/--device
options actually forwarded (the reference accepts and drops them, defect
SURVEY §2.4.8).
"""

from __future__ import annotations

import click


# one option for `serve start`, `fleet worker` and `fleet front`
kv_block_size_option = click.option(
    "--kv-block-size", default=0, show_default=True, type=int,
    help="Tokens per KV page; 0 = by the model's rows: the smallest power "
         "of two >= 64 whose K + V copy for one layer reaches 512 KB, at "
         "most 128 (a prefix-cache hit is whole pages).")


@click.group(name="serve", invoke_without_command=True)
@click.pass_context
def app(ctx):
    """Inference serving."""
    if ctx.invoked_subcommand is None:
        click.echo(ctx.get_help())


@app.command()
@click.option("--model", "model_name", default="gpt-125m", show_default=True,
              help="Model template name.")
@click.option("--artifact", default="",
              help="Checkpoint dir, or an `llmctl export` safetensors/npz "
                   "file (pre-quantized exports load straight to device — "
                   "bf16 weights never materialise, the 7B-on-16GB path).")
@click.option("--host", default="0.0.0.0", show_default=True)
@click.option("--port", default=8080, show_default=True, type=int)
@click.option("--max-batch-size", default=8, show_default=True, type=int)
@click.option("--max-seq-len", default=2048, show_default=True, type=int)
@kv_block_size_option
@click.option("--kv-hbm-gb", default=4.0, show_default=True, type=float,
              help="HBM budget for the paged KV cache.")
@click.option("--scheduler", default="continuous", show_default=True,
              type=click.Choice(["continuous", "static"]))
@click.option("--dtype", default=None,
              type=click.Choice(["bfloat16", "float32"]),
              help="Serving dtype (default bf16 on TPU, fp32 on CPU).")
@click.option("--prometheus-port", default=None, type=int,
              help="Also start a Prometheus scrape endpoint.")
@click.option("--speculative", default="off", show_default=True,
              type=click.Choice(["off", "ngram", "mtp"]),
              help="Speculative decoding (ngram = host prompt-lookup "
                   "drafts, device verification; mtp = a model with a "
                   "next-token prediction module drafts for itself, 1 or 2 "
                   "tokens a slot a step; greedy output unchanged).")
@click.option("--spec-tokens", default=8, show_default=True, type=int,
              help="Speculative verify window (drafts per dispatch + 1).")
@click.option("--prefix-cache/--no-prefix-cache", default=True,
              show_default=True,
              help="Share full prompt-prefix KV pages between requests.")
@click.option("--state-snapshot-entries", default=0, show_default=True,
              type=int,
              help="A model with delta-rule (K) layers: entries of the "
                   "snapshot pool (a slot's recurrent state at a prompt's "
                   "last page boundary), which lets a prefix-cache hit be "
                   "followed through the state (0 = such a model's prefix "
                   "reuse stays off).")
@click.option("--tensor-parallel", default=1, show_default=True, type=int,
              help="Shard the model over this many local devices "
                   "(Megatron TP; needs num_kv_heads % tp == 0).")
@click.option("--quantization", default="none", show_default=True,
              type=click.Choice(["none", "int8", "int4", "int4-awq"]),
              help="Weight-only quantization: int8 (W8A16, ~2x block HBM "
                   "freed) or group-wise int4 / int4-awq (W4A16, ~4x; awq "
                   "= activation-aware channel scaling). Composes with "
                   "--tensor-parallel.")
@click.option("--chunked-prefill", default=0, show_default=True, type=int,
              help="Prefill prompts longer than this in chunks of this "
                   "many tokens, interleaved with decode (0 = off).")
@click.option("--prefill-budget-tokens", default=2048, show_default=True,
              type=int,
              help="Max prompt tokens prefetched between two decode "
                   "steps (bounds the inter-token stall resident "
                   "streams see during a long-prompt burst).")
@click.option("--decode-steps", default=8, show_default=True, type=int,
              help="Decode iterations fused into one device dispatch "
                   "(each dispatch pays one host round trip for K "
                   "tokens; K also bounds admission latency).")
@click.option("--max-queue", default=256, show_default=True, type=int,
              help="Per-engine queued-request bound; beyond it "
                   "submissions are rejected.")
@click.option("--swap-space-gb", default=4.0, show_default=True,
              type=float,
              help="Host-memory budget for swapped-out KV "
                   "(--preemption swap); above it evictions fall back "
                   "to recompute.")
@click.option("--spec-ngram", default=3, show_default=True, type=int,
              help="Longest n-gram the speculative proposer tries.")
@click.option("--spec-min-acceptance", default=0.05, show_default=True,
              type=float,
              help="Adaptive kill switch: fall back to plain decode "
                   "when measured draft acceptance stays below this.")
@click.option("--kv-quantization", default="none", show_default=True,
              type=click.Choice(["none", "int8", "int4"]),
              help="Quantized KV pages (+per-token scales): int8 = 2x KV "
                   "capacity and half the decode KV streaming; int4 packs "
                   "two page slots per byte = 4x capacity / quarter the "
                   "streaming (2x decode slots per HBM byte over int8) at "
                   "a larger quality cost — see USER_GUIDE 'KV "
                   "quantization: int8 vs int4'.")
@click.option("--admission", default="ondemand", show_default=True,
              type=click.Choice(["ondemand", "reserve"]),
              help="KV admission: ondemand grows page chains as decode "
                   "advances and preempts newest-first under pressure "
                   "(higher sustained concurrency); reserve holds "
                   "prompt+max_tokens up front.")
@click.option("--preemption", default="recompute", show_default=True,
              type=click.Choice(["recompute", "swap"]),
              help="Evicted-KV policy: recompute re-prefills on "
                   "readmission (prefix-cache-cheap); swap round-trips "
                   "the pages through host memory (zero re-prefill).")
@click.option("--latency-dispatch-steps", default=0, show_default=True,
              type=int,
              help="Shrink decode dispatches to this many steps while "
                   "requests wait in the queue with a free slot, so "
                   "prefill windows open sooner (0 disables).")
@click.option("--pipelined-decode/--no-pipelined-decode", default=True,
              show_default=True,
              help="Keep one un-fetched decode dispatch in flight and "
                   "chain the next on its device carry (overlaps the "
                   "per-dispatch host round trip; engages at >= half-full "
                   "batches; bitwise-identical output; measured +20-25% "
                   "saturation goodput at 1B/7B — round 5).")
@click.option("--int8-pallas/--no-int8-pallas", "int8_pallas",
              default=False, show_default=True,
              help="Route int8 decode matmuls through the in-kernel-"
                   "dequant Pallas kernel instead of XLA's fused dequant "
                   "(enable only where measured faster on your chip).")
@click.option("--cors-origins", default="*", show_default=True,
              help="CORS allowed origins for browser clients: '*', a "
                   "comma-separated list, or '' to disable (parity: the "
                   "reference installs allow-all CORSMiddleware).")
@click.option("--replicas", default=1, show_default=True, type=int,
              help="Engine replicas behind the fleet router (>1 starts "
                   "the serve/fleet control plane: prefix-affinity "
                   "routing, health-driven drain/restart, 429 "
                   "backpressure; `llmctl fleet status/drain` manages "
                   "it).")
@click.option("--fleet-max-pending", default=512, show_default=True,
              type=int,
              help="Fleet-wide queued-request bound; beyond it new "
                   "requests get 429 + Retry-After.")
@click.option("--fleet-probe-interval", default=0.5, show_default=True,
              type=float, help="Supervisor health-probe cadence (s).")
@click.option("--fleet-probe-failures", default=3, show_default=True,
              type=int,
              help="Consecutive probe misses before a replica is "
                   "declared dead and torn down like a crash.")
@click.option("--fleet-restart-backoff", default=0.5, show_default=True,
              type=float,
              help="First replica-restart delay; doubles per consecutive "
                   "restart.")
@click.option("--fleet-max-restarts", default=0, show_default=True,
              type=int,
              help="Give up restarting a replica after this many "
                   "attempts (0 = unlimited).")
@click.option("--fleet-max-requeues", default=3, show_default=True,
              type=int,
              help="Per-request crash/drain requeue budget; above it "
                   "the request fails loudly instead of ping-ponging "
                   "between dying replicas.")
@click.option("--fleet-prefix-inventory-max", default=512,
              show_default=True, type=int,
              help="Newest prefix-page hashes each replica advertises "
                   "for fleet-global prefix-fetch hints (bounds probe "
                   "payloads; 0 disables the inventory).")
@click.option("--fleet-affinity-tokens", default=64, show_default=True,
              type=int,
              help="Prompt-prefix length hashed for replica affinity "
                   "(keeps per-replica prefix caches hot; 0 = pure "
                   "least-outstanding-tokens routing).")
@click.option("--fleet-migrate-on-drain/--fleet-no-migrate-on-drain",
              "fleet_migrate_on_drain", default=True, show_default=True,
              help="Drained replicas hand their resident sequences to "
                   "survivors WITH their KV pages (two-phase live copy, "
                   "zero re-prefill) instead of re-prefilling "
                   "prompt+generated.")
@click.option("--fleet-rebalance-ratio", default=0.0, show_default=True,
              type=float,
              help="Outstanding-token imbalance fraction that triggers "
                   "migration-driven rebalancing (hot replica's longest "
                   "sequences move to the coldest); 0 disables.")
@click.option("--fleet-rebalance-hysteresis", default=3, show_default=True,
              type=int,
              help="Consecutive supervisor polls the imbalance must "
                   "persist before the rebalancer moves KV.")
@click.option("--fleet-max-migrations", default=2, show_default=True,
              type=int,
              help="Concurrently in-flight KV migrations, fleet-wide.")
@click.option("--fleet-roles", default="", show_default=True,
              help="Disaggregated prefill/decode: comma-separated "
                   "per-replica roles (prefill|decode|mixed), e.g. "
                   "'prefill,decode'. Prefill replicas hand each "
                   "freshly-prefilled sequence (with its KV) to a decode "
                   "replica — long prompts stop stalling co-resident "
                   "decode streams. Empty = every replica mixed.")
@click.option("--fleet-role-balance-ratio", default=0.0, show_default=True,
              type=float,
              help="Re-role replicas when one phase's per-replica queue "
                   "depth exceeds this multiple of the other's for "
                   "consecutive supervisor polls (drain-with-migration "
                   "first, so nothing is lost); 0 disables.")
@click.option("--fleet-courier-transport", "fleet_courier_transport",
              type=click.Choice(["inproc", "http"]), default="inproc",
              show_default=True,
              help="KV courier link for migration/handoff payloads: "
                   "inproc (threaded replicas, this process) or http "
                   "(POST chunks to --fleet-courier-endpoint's "
                   "/fleet/courier/chunk — cross-host movement).")
@click.option("--fleet-courier-codec", "fleet_courier_codec",
              type=click.Choice(["none", "zlib", "delta-zlib"]),
              default="none", show_default=True,
              help="Courier wire codec for KV payloads: delta-zlib "
                   "delta-encodes quantized page planes along the token "
                   "axis then deflates each chunk (2-4x fewer wire "
                   "bytes on int8/int4 pages — smaller migration pause, "
                   "handoff stall, and prefix-fetch latency); zlib "
                   "deflates without the delta filter; none ships raw "
                   "bytes. Compression is pipelined behind the wire and "
                   "CRC-verified end to end — a codec failure degrades "
                   "to re-prefill, never wrong tokens.")
@click.option("--fleet-courier-zlib-level", default=-1, show_default=True,
              type=int,
              help="zlib level for the compressing courier codecs "
                   "(-1 = library default, 1 = fastest, 9 = smallest). "
                   "Recorded per transfer in the frame manifest, so "
                   "receivers stay level-agnostic; the tiered KV "
                   "store's at-rest frames use it too.")
@click.option("--fleet-courier-chunk-bytes", default=256 * 1024,
              show_default=True,
              help="Courier frame size: payloads are split into chunks "
                   "of at most this many bytes, each CRC32-checksummed "
                   "and individually retryable.")
@click.option("--fleet-courier-retries", default=4, show_default=True,
              help="Resend rounds before a transfer aborts (only missing "
                   "chunks resend, backoff doubles per round). An "
                   "aborted transfer drops the payload and the "
                   "destination re-prefills — degraded, never wrong.")
@click.option("--fleet-courier-deadline-ms", default=100.0,
              show_default=True, type=float,
              help="Per-chunk delivery deadline; a chunk slower than "
                   "this counts as lost and is retransmitted (the "
                   "receiver absorbs the late duplicate idempotently).")
@click.option("--fleet-courier-endpoint", default="", show_default=True,
              help="http transport only: destination fleet base URL.")
@click.option("--fleet-courier-ticket-ttl-ms", default=60_000.0,
              show_default=True, type=float,
              help="Evict unclaimed courier reassembly buffers / "
                   "attached payloads after this long (counted in "
                   "llmctl_fleet_courier_expired_total; 0 = never).")
@click.option("--fleet-endpoint", "fleet_endpoints", multiple=True,
              metavar="REPLICA=URL",
              help="Per-replica courier endpoint, repeatable (e.g. "
                   "--fleet-endpoint 1=http://hostB:9001). Remote "
                   "replicas need one; in-proc replicas may name this "
                   "front's own URL so remote workers can push KV to "
                   "them.")
@click.option("--fleet-remote-replicas", default="", show_default=True,
              help="Comma-separated replica ids served by `llmctl fleet "
                   "worker` processes instead of in-process engines; "
                   "each MUST have a --fleet-endpoint entry (validated "
                   "at startup).")
@click.option("--fleet-prefix-fetch/--fleet-no-prefix-fetch",
              "fleet_prefix_fetch", default=True, show_default=True,
              help="Fleet-global prefix cache: placements that miss the "
                   "affinity owner FETCH the shared prefix pages from "
                   "the replica that has them (over the courier) "
                   "instead of re-prefilling; fetch failures degrade to "
                   "plain prefill.")
@click.option("--fleet-prefix-fetch-min-pages", default=1,
              show_default=True, type=int,
              help="Skip fetches smaller than this many full pages "
                   "(raise when computing a page is cheaper than your "
                   "link).")
@click.option("--fleet-kv-store/--fleet-no-kv-store", "fleet_kv_store",
              default=False, show_default=True,
              help="Tiered fleet KV store: a host-tier DRAM ring (+ "
                   "optional disk spill) that receives prefix pages "
                   "evicted from replica HBM or flushed at drain/retire "
                   "— in their compressed courier-frame form, encoded "
                   "once — and serves them back over the normal "
                   "prefix-fetch path when no live replica holds them. "
                   "Returning conversations restore from the store at "
                   "wire speed instead of re-prefilling; scale-down "
                   "stops destroying the cluster cache.")
@click.option("--fleet-kv-store-dram-mb", default=256.0,
              show_default=True, type=float,
              help="DRAM ring capacity for the tiered KV store, in MB "
                   "of compressed frames (LRU; overflow spills to "
                   "--fleet-kv-store-dir or drops the oldest).")
@click.option("--fleet-kv-store-dir", default="", show_default=True,
              help="Disk-spill directory for the tiered KV store "
                   "(empty = DRAM only).")
@click.option("--fleet-kv-store-disk-mb", default=1024.0,
              show_default=True, type=float,
              help="Disk-spill capacity bound for the tiered KV store.")
@click.option("--fleet-kv-store-ttl-ms", default=0.0, show_default=True,
              type=float,
              help="Expire store entries nobody fetched for this long "
                   "(0 = keep until capacity pressure evicts).")
@click.option("--fleet-kv-store-endpoint", default="", show_default=True,
              help="Base URL of a standalone `llmctl fleet store` "
                   "service. The in-proc tiered store is replaced by a "
                   "networked client speaking the same courier "
                   "chunk/fetch protocol, so every front and every "
                   "remote worker resolve ONE logical store — demoted "
                   "pages survive any single serving process. Requires "
                   "--fleet-prefix-fetch.")
@click.option("--fleet-kv-store-endpoints", default="",
              show_default=True,
              help="Comma-separated member URLs of a REPLICATED store "
                   "tier (overrides --fleet-kv-store-endpoint): N "
                   "`llmctl fleet store` processes behind the one "
                   "logical store. Demotions fan out to the write-ack "
                   "floor, fetches fail over to survivors, and "
                   "anti-entropy reconciles a rejoining member. "
                   "Requires --fleet-prefix-fetch.")
@click.option("--fleet-kv-store-retry-max", default=2, show_default=True,
              type=int,
              help="Transient-error retries (connection refused/reset) "
                   "per store RPC before the member is rotated past — "
                   "nothing is counted a miss until the budget is "
                   "spent on every member.")
@click.option("--fleet-kv-store-retry-backoff-ms", default=10.0,
              show_default=True, type=float,
              help="First retry delay for store RPCs; doubles per "
                   "retry.")
@click.option("--fleet-kv-store-write-ack", default=1, show_default=True,
              type=int,
              help="Store members that must acknowledge a demotion "
                   "synchronously before it counts as stored; the "
                   "remaining live members are mirrored "
                   "asynchronously.")
@click.option("--fleet-kv-store-hedge-ms", default=0.0, show_default=True,
              type=float,
              help="Hedged store fetches: when the first member has "
                   "not answered within this window, race a second "
                   "live member and take whichever answers first "
                   "(0 disables).")
@click.option("--fleet-pipeline-min-tokens", default=0, show_default=True,
              type=int,
              help="Pipelined multi-replica prefill: needs-prefill "
                   "prompts at least this long are split page-aligned "
                   "across the prefill pool as a chunk pipeline, each "
                   "stage's KV pages pre-shipped to the next replica "
                   "while it computes (0 disables; requires "
                   "--fleet-prefix-fetch).")
@click.option("--fleet-pipeline-max-stages", default=4, show_default=True,
              type=int,
              help="Most prefill stages one pipelined prompt is split "
                   "across (also bounded by accepting prefill-capable "
                   "in-process replicas).")
@click.option("--fleet-pipeline-stage-timeout-ms", default=30_000.0,
              show_default=True, type=float,
              help="A pipeline stage that neither finishes nor reports "
                   "chunk progress within this window collapses the "
                   "pipeline to single-replica prefill (counted, never "
                   "wrong tokens).")
@click.option("--fleet-inventory-ttl-ms", default=0.0, show_default=True,
              type=float,
              help="Cache the per-replica prefix-page inventory map this "
                   "long between placements (0 = re-read every "
                   "placement). Invalidated on replica teardown/drain; "
                   "within-TTL staleness costs a counted fetch miss, "
                   "never wrong tokens.")
@click.option("--fleet-stream-ttl-ms", default=60_000.0,
              show_default=True, type=float,
              help="How long a finished SSE stream stays replayable for "
                   "a Last-Event-ID reconnect at /v1/streams/<id>.")
@click.option("--fleet-stream-max-buffered", default=256,
              show_default=True, type=int,
              help="Per-subscriber SSE backpressure cap: a client "
                   "holding more than this many undelivered token "
                   "batches is disconnected (counted in llmctl_fleet_"
                   "stream_backpressure_drops_total) and replays via "
                   "Last-Event-ID. 0 disables.")
@click.option("--fleet-fronts", default=1, show_default=True, type=int,
              help="HA front tier: run this many stateless front "
                   "processes (each a `llmctl fleet front` child on its "
                   "own port, babysat + fenced by the tier; ports in "
                   "`fleet status`). > 1 requires --fleet-state-store "
                   "file and every replica remote — a front's SIGKILL "
                   "mid-SSE is then healed by the client reconnecting "
                   "to any survivor with Last-Event-ID.")
@click.option("--fleet-state-store", default="memory", show_default=True,
              type=click.Choice(["memory", "file"]),
              help="Where stream logs + router ledger live: memory = "
                   "this process (single front, the default), file = a "
                   "shared fenced journal under "
                   "--fleet-state-store-dir so N fronts serve one "
                   "fleet.")
@click.option("--fleet-state-store-dir", default="", show_default=True,
              help="Directory for the file state store (every front "
                   "must see the same path).")
@click.option("--fleet-state-compact-every", default=1024,
              show_default=True, type=int,
              help="Compact the file state store's journal (snapshot + "
                   "truncate, fenced and flock-serialized) every this "
                   "many records written; fronts reload from snapshot "
                   "+ tail. 0 disables (the journal then grows "
                   "unboundedly).")
@click.option("--fleet-autoscale/--fleet-no-autoscale", "fleet_autoscale",
              default=False, show_default=True,
              help="Elastic autoscaler: add replicas under sustained "
                   "queue pressure and retire idle ones through "
                   "drain-with-migration + KV-store flush (scale-down "
                   "costs zero re-prefill tokens). Decisions ride the "
                   "supervisor poll with hysteresis + cooldown.")
@click.option("--fleet-autoscale-min-replicas", default=1,
              show_default=True, type=int,
              help="Scale-down floor: the autoscaler never retires "
                   "below this many replicas (provisioned role "
                   "coverage is additionally preserved).")
@click.option("--fleet-autoscale-max-replicas", default=0,
              show_default=True, type=int,
              help="Scale-up ceiling (0 = 2x the provisioned fleet).")
@click.option("--fleet-autoscale-up-queue-per-replica", default=4.0,
              show_default=True, type=float,
              help="Scale UP when admission-queue depth per healthy "
                   "replica stays above this for the hysteresis "
                   "window.")
@click.option("--fleet-autoscale-down-queue-per-replica", default=0.5,
              show_default=True, type=float,
              help="Scale DOWN when queue depth per healthy replica "
                   "stays below this (with an idle replica on hand); "
                   "must be under the up threshold or the fleet would "
                   "oscillate.")
@click.option("--fleet-autoscale-hysteresis-polls", default=2,
              show_default=True, type=int,
              help="Consecutive supervisor polls a threshold must hold "
                   "before the autoscaler acts — one bursty poll must "
                   "not resize the fleet.")
@click.option("--fleet-autoscale-cooldown-polls", default=10,
              show_default=True, type=int,
              help="Polls to sit out after any scaling action before "
                   "measuring again (0 = no cooldown).")
@click.option("--fleet-autoscale-spawn", default="", show_default=True,
              type=click.Choice(["", "engine", "worker"]),
              help="What a scale-up adds: 'engine' (default when "
                   "empty) builds an in-proc replica sharing loaded "
                   "weights; 'worker' spawns a fresh `llmctl fleet "
                   "worker` OS process whose argv is synthesized from "
                   "THIS command's flags — no operator command line. "
                   "With --fleet-kv-store-endpoint the spawned worker "
                   "bootstraps its weights from the store service "
                   "(--weights-from-store), so a bare host joins "
                   "without any shared artifact path.")
@click.option("--fleet-autoscale-up-free-page-ratio", default=0.0,
              show_default=True, type=float,
              help="Also scale UP when some healthy replica's free "
                   "KV-page fraction stays below this (page "
                   "starvation: long residents pin the pool while "
                   "queues look shallow). 0 disables; queue pressure "
                   "still applies either way.")
@click.option("--fleet-autoscale-spawn-timeout-s", default=30.0,
              show_default=True, type=float,
              help="How long a spawned `llmctl fleet worker` may take "
                   "to print its LLMCTL_WORKER_READY line (and how "
                   "long a retirement drain may run) before the "
                   "action is counted failed and rolled back.")
@click.option("--fleet-priority-headroom-requests", default=0,
              show_default=True, type=int,
              help="SLO priority tiers: queue slots reserved for "
                   "interactive-class requests — standard admits up "
                   "to max_pending minus this, best-effort up to half "
                   "of max_pending; shed classes get a class-scaled "
                   "Retry-After on the 429.")
@click.option("--fleet-interactive-ttft-target-ms", default=0.0,
              show_default=True, type=float,
              help="TTFT guard: when an interactive request has queued "
                   "past this many ms on a replica, one resident "
                   "best-effort sequence there is preempted — "
                   "migrated with its KV to the least-loaded sibling, "
                   "never dropped (0 disables).")
@click.option("--stream-abort-on-disconnect/--no-stream-abort-on-disconnect",  # noqa: E501
              "stream_abort_on_disconnect", default=True,
              show_default=True,
              help="Single-server SSE only: abort a request whose client "
                   "disconnected mid-stream (frees its decode slot + KV "
                   "pages). The fleet front keeps it running — its "
                   "stream log supports reconnect instead.")
def start(model_name, artifact, host, port, max_batch_size, max_seq_len,
          kv_block_size, kv_hbm_gb, scheduler, dtype, prometheus_port,
          speculative, spec_tokens, prefix_cache, state_snapshot_entries,
          tensor_parallel,
          quantization, chunked_prefill, prefill_budget_tokens,
          decode_steps, max_queue, swap_space_gb, spec_ngram,
          spec_min_acceptance, kv_quantization, admission,
          preemption, latency_dispatch_steps, pipelined_decode,
          int8_pallas, cors_origins, replicas, fleet_max_pending,
          fleet_probe_interval, fleet_probe_failures,
          fleet_restart_backoff, fleet_max_restarts, fleet_max_requeues,
          fleet_prefix_inventory_max,
          fleet_affinity_tokens, fleet_migrate_on_drain,
          fleet_rebalance_ratio, fleet_rebalance_hysteresis,
          fleet_max_migrations, fleet_roles, fleet_role_balance_ratio,
          fleet_courier_transport, fleet_courier_codec,
          fleet_courier_zlib_level, fleet_courier_chunk_bytes,
          fleet_courier_retries, fleet_courier_deadline_ms,
          fleet_courier_endpoint, fleet_courier_ticket_ttl_ms,
          fleet_endpoints, fleet_remote_replicas, fleet_prefix_fetch,
          fleet_prefix_fetch_min_pages, fleet_kv_store,
          fleet_kv_store_dram_mb, fleet_kv_store_dir,
          fleet_kv_store_disk_mb, fleet_kv_store_ttl_ms,
          fleet_kv_store_endpoint, fleet_kv_store_endpoints,
          fleet_kv_store_retry_max, fleet_kv_store_retry_backoff_ms,
          fleet_kv_store_write_ack, fleet_kv_store_hedge_ms,
          fleet_pipeline_min_tokens, fleet_pipeline_max_stages,
          fleet_pipeline_stage_timeout_ms,
          fleet_inventory_ttl_ms,
          fleet_stream_ttl_ms, fleet_stream_max_buffered,
          fleet_fronts, fleet_state_store, fleet_state_store_dir,
          fleet_state_compact_every, fleet_autoscale,
          fleet_autoscale_min_replicas, fleet_autoscale_max_replicas,
          fleet_autoscale_up_queue_per_replica,
          fleet_autoscale_down_queue_per_replica,
          fleet_autoscale_hysteresis_polls,
          fleet_autoscale_cooldown_polls,
          fleet_autoscale_spawn, fleet_autoscale_up_free_page_ratio,
          fleet_autoscale_spawn_timeout_s,
          fleet_priority_headroom_requests,
          fleet_interactive_ttft_target_ms, stream_abort_on_disconnect):
    """Start the OpenAI-compatible inference server."""
    from ...config.presets import get_model_config
    from ...config.schema import (FleetConfig, ServeConfig,
                                  parse_fleet_endpoints)
    from ...metrics.observability import setup_observability
    from ...serve.server import create_server
    from ...utils.platform import devices

    if dtype is None:
        # the process's first look at its devices (llmctl.startup.backend)
        dtype = "bfloat16" if devices()[0].platform == "tpu" else "float32"
    model_cfg = get_model_config(model_name)
    serve_cfg = ServeConfig(
        model=model_name, artifact=artifact, host=host, port=port,
        max_batch_size=max_batch_size,
        max_seq_len=min(max_seq_len, model_cfg.max_position_embeddings),
        kv_block_size=kv_block_size, kv_hbm_budget_gb=kv_hbm_gb,
        scheduler=scheduler, dtype=dtype, speculative=speculative,
        speculative_tokens=spec_tokens, prefix_caching=prefix_cache,
        state_snapshot_entries=state_snapshot_entries,
        speculative_ngram=spec_ngram,
        speculative_min_acceptance=spec_min_acceptance,
        tensor_parallel=tensor_parallel, quantization=quantization,
        chunked_prefill_tokens=chunked_prefill,
        prefill_budget_tokens=prefill_budget_tokens,
        decode_steps_per_dispatch=decode_steps, max_queue=max_queue,
        swap_space_gb=swap_space_gb,
        kv_quantization=kv_quantization, admission=admission,
        preemption=preemption,
        latency_dispatch_steps=latency_dispatch_steps,
        pipelined_decode=pipelined_decode,
        int8_pallas_matmul=int8_pallas,
        cors_origins=cors_origins,
        stream_abort_on_disconnect=stream_abort_on_disconnect)
    serve_cfg.validate()
    fleet_cfg = None
    if replicas > 1:
        fleet_cfg = FleetConfig(
            replicas=replicas, max_pending=fleet_max_pending,
            probe_interval_s=fleet_probe_interval,
            probe_failures=fleet_probe_failures,
            restart_backoff_s=fleet_restart_backoff,
            max_restarts=fleet_max_restarts,
            max_requeues=fleet_max_requeues,
            prefix_inventory_max=fleet_prefix_inventory_max,
            affinity_prefix_tokens=fleet_affinity_tokens,
            migrate_on_drain=fleet_migrate_on_drain,
            rebalance_imbalance_ratio=fleet_rebalance_ratio,
            rebalance_poll_hysteresis=fleet_rebalance_hysteresis,
            max_concurrent_migrations=fleet_max_migrations,
            roles=fleet_roles,
            role_balance_ratio=fleet_role_balance_ratio,
            courier_transport=fleet_courier_transport,
            courier_codec=fleet_courier_codec,
            courier_zlib_level=fleet_courier_zlib_level,
            courier_chunk_bytes=fleet_courier_chunk_bytes,
            courier_max_retries=fleet_courier_retries,
            courier_chunk_deadline_ms=fleet_courier_deadline_ms,
            courier_endpoint=fleet_courier_endpoint,
            courier_ticket_ttl_ms=fleet_courier_ticket_ttl_ms,
            fleet_endpoints=parse_fleet_endpoints(list(fleet_endpoints)),
            remote_replicas=fleet_remote_replicas,
            prefix_fetch=fleet_prefix_fetch,
            prefix_fetch_min_pages=fleet_prefix_fetch_min_pages,
            kv_store=fleet_kv_store,
            kv_store_dram_mb=fleet_kv_store_dram_mb,
            kv_store_dir=fleet_kv_store_dir,
            kv_store_disk_mb=fleet_kv_store_disk_mb,
            kv_store_ttl_ms=fleet_kv_store_ttl_ms,
            kv_store_endpoint=fleet_kv_store_endpoint,
            kv_store_endpoints=fleet_kv_store_endpoints,
            kv_store_retry_max=fleet_kv_store_retry_max,
            kv_store_retry_backoff_ms=fleet_kv_store_retry_backoff_ms,
            kv_store_write_ack=fleet_kv_store_write_ack,
            kv_store_hedge_ms=fleet_kv_store_hedge_ms,
            pipeline_prefill_min_tokens=fleet_pipeline_min_tokens,
            pipeline_prefill_max_stages=fleet_pipeline_max_stages,
            pipeline_prefill_stage_timeout_ms=(
                fleet_pipeline_stage_timeout_ms),
            prefix_inventory_ttl_ms=fleet_inventory_ttl_ms,
            stream_log_ttl_ms=fleet_stream_ttl_ms,
            stream_max_buffered_batches=fleet_stream_max_buffered,
            fronts=fleet_fronts, state_store=fleet_state_store,
            state_store_dir=fleet_state_store_dir,
            state_compact_every=fleet_state_compact_every,
            autoscale=fleet_autoscale,
            autoscale_min_replicas=fleet_autoscale_min_replicas,
            autoscale_max_replicas=fleet_autoscale_max_replicas,
            autoscale_up_queue_per_replica=(
                fleet_autoscale_up_queue_per_replica),
            autoscale_down_queue_per_replica=(
                fleet_autoscale_down_queue_per_replica),
            autoscale_hysteresis_polls=fleet_autoscale_hysteresis_polls,
            autoscale_cooldown_polls=fleet_autoscale_cooldown_polls,
            autoscale_spawn=fleet_autoscale_spawn,
            autoscale_up_free_page_ratio=(
                fleet_autoscale_up_free_page_ratio),
            autoscale_spawn_timeout_s=fleet_autoscale_spawn_timeout_s,
            priority_headroom_requests=fleet_priority_headroom_requests,
            interactive_ttft_target_ms=fleet_interactive_ttft_target_ms)
        fleet_cfg.validate()

    if fleet_cfg is not None and fleet_cfg.fronts > 1:
        # HA front tier: this process becomes the tier babysitter; each
        # front is its own `llmctl fleet front` child over the shared
        # state store and the same remote workers
        from ...serve.engine import InferenceEngine
        from ...serve.fleet.front import FleetFrontTier, default_spawn_cmd
        from ...serve.fleet.state import SharedFileStateStore
        from ...serve.kv_cache import resolve_page_size
        # a front knows nothing of the workers' dtype: it is told the page
        # as THIS process's dtype resolves it, as a worker here would
        resolve_page_size(model_cfg, serve_cfg,
                          most=InferenceEngine.RIDE_ROWS)
        store = SharedFileStateStore(fleet_cfg.state_store_dir,
                                     front_id="tier")
        tier = FleetFrontTier(
            store,
            default_spawn_cmd(
                model=model_name, store_dir=fleet_cfg.state_store_dir,
                replicas=fleet_cfg.replicas,
                endpoints=fleet_cfg.endpoint_map(),
                remote_replicas=fleet_cfg.remote_replicas,
                host=host, artifact=artifact,
                extra=["--max-seq-len", str(max_seq_len),
                       "--max-batch-size", str(max_batch_size),
                       "--kv-block-size", str(serve_cfg.kv_block_size)]),
            fronts=fleet_cfg.fronts)
        ports = tier.start()
        click.echo(f"HA front tier up: {fleet_cfg.fronts} fronts on "
                   f"ports {ports} over {fleet_cfg.state_store_dir}")
        tier.run_forever()
        return

    observer = None
    if prometheus_port:
        obs = setup_observability(prometheus_port=prometheus_port)

        def observer(event, payload):
            # supervisor snapshots carry per-replica gauges; everything
            # else is per-request inference telemetry
            if event == "fleet":
                obs.record_fleet(payload)
            else:
                obs.record_inference(payload)

    server = create_server(model_cfg, serve_cfg, fleet_cfg=fleet_cfg,
                           observer=observer)
    if fleet_cfg is not None and fleet_cfg.kv_store_endpoint_list() \
            and fleet_cfg.autoscale_spawn == "worker" \
            and getattr(server, "fleet", None) is not None:
        # register the loaded checkpoint in the store service up front,
        # so autoscaler-spawned bare workers (--weights-from-store)
        # find it there; idempotent + upload-resumable, so a restart of
        # this front re-ships nothing already held
        try:
            shipped = server.fleet.ship_weights()
            click.echo(f"weights {shipped['name']!r} registered in "
                       f"store ({shipped['sent']} chunks sent, "
                       f"{shipped['skipped']} already held)")
        except Exception as e:
            raise click.ClickException(
                f"weight ship to "
                f"{','.join(fleet_cfg.kv_store_endpoint_list())} failed "
                f"— spawned workers could not bootstrap: {e}")
    from ...utils.platform import device_line
    click.echo(f"serving {model_name} on {host}:{port} "
               f"({device_line()}, dtype={dtype}, scheduler={scheduler}"
               + (f", replicas={replicas}" if replicas > 1 else "") + ")")
    server.run_forever()
