"""Engine configuration + CLI.

Flag surface mirrors what the reference stack passes to `vllm serve`
(helm/templates/deployment-vllm-multi.yaml:96-186, ray-cluster.yaml:520-605 in
/root/reference): tensor/pipeline parallel sizes, chunked prefill, prefix
caching, max len, sleep mode — plus TPU-specific knobs (page count, buckets).
"""

from __future__ import annotations

import argparse
import dataclasses
from typing import Optional


@dataclasses.dataclass
class EngineConfig:
    model: str = "llama-debug"          # preset name or local HF directory
    served_model_name: Optional[str] = None
    tokenizer: Optional[str] = None     # defaults to model dir when it is a path
    host: str = "0.0.0.0"
    port: int = 8100
    max_num_seqs: int = 64
    max_model_len: int = 4096
    # engine-side admission control (overload survival): bound on the waiting
    # queue — at or past it new generation requests are SHED with 429 +
    # Retry-After instead of queued into unbounded TTFT (0 = unbounded,
    # matching vLLM). Export: vllm:engine_saturated / num_requests_shed_total.
    max_waiting_seqs: int = 0
    # per-request queue deadline: a request still undispatched after this many
    # seconds is shed (429) by the engine loop (0 = never shed by age)
    queue_deadline_s: float = 0.0
    # Retry-After seconds advertised on shed responses
    shed_retry_after_s: float = 1.0
    # SLO classes (docs/failure-handling.md "Priority classes & graceful
    # degradation"): waiting-queue slots reserved for interactive requests —
    # batch traffic saturates (sheds) this many slots early, so batch load
    # can never starve interactive out of a bounded queue
    interactive_reserve: int = 1
    # queue deadline applied to batch-class requests only (0 = inherit
    # queue_deadline_s); a shorter batch deadline makes the engine loop
    # expire batch out of a congested queue before any interactive request
    batch_queue_deadline_s: float = 0.0
    # max share of a prefill dispatch's chunk slots batch may hold while an
    # interactive prefill is waiting (1.0 = no cap)
    batch_prefill_share: float = 0.5
    # KV page size (tokens). Larger pages mean fewer (bigger) page DMAs per
    # decode step: measured on v5e (llama-3.2-1b class, B=16, 1k ctx, with
    # deferred-burst KV + stacked-pool streaming) decode runs 1037 tok/s at
    # page 16, 1387 at 32, 1706 at 64, 1954 at 128 — DMA issue rate, not
    # bandwidth, is the limiter at small pages. The sharing-granularity cost
    # of 64 over 32 is measured, not assumed: on the multi-round-qa headline
    # workload (32 users x 5 rounds, ~1k-token shared prefix, through the
    # full router+engine stack on one v5e chip) the prefix-cache hit rate is
    # 93.59% at page 64 vs 93.76% at page 32 — a 0.17% delta — while page 32
    # costs ~20% generation throughput (224.5 vs 178.6 tok/s same run). 64
    # stays the default; it is also 4x finer sharing than the reference's
    # 256-token LMCache chunks.
    page_size: int = 64
    num_pages: Optional[int] = None     # default: sized from kv_cache_memory_gb
    kv_cache_memory_gb: float = 4.0
    prefill_chunk: int = 512
    prefill_batch: int = 4
    # fused decode burst: tokens produced per device program dispatch. >1
    # amortizes host<->device round trips (runner.step_multi); surplus tokens
    # after EOS are discarded host-side. With speculative decoding on, this is
    # the number of fused draft+verify rounds per dispatch instead.
    decode_steps: int = 8
    # chained decode bursts per dispatch when no requests are waiting: burst
    # j+1's input token is fed from burst j's device-resident output, so a
    # chain of m bursts pays one host fetch instead of m (what that buys on
    # a directly attached chip is not measured — ROADMAP D4). Arrivals
    # during a chain wait up to (pipeline-1) extra bursts before prefill.
    # Tradeoff: chaining doubles the decode program variants the engine
    # compiles ((batch, pages) buckets x {chained, unchained}) — enable for
    # long-lived serving pods, not for short benchmark windows.
    decode_pipeline: int = 1
    # speculative decoding (prompt-lookup/n-gram, fused on device): draft
    # length per round; 0 disables. The TPU-native analogue of vLLM's ngram
    # speculator — decode becomes parallel verify instead of serial steps.
    speculative_k: int = 0
    speculative_ngram: int = 3
    enable_prefix_caching: bool = True
    enable_chunked_prefill: bool = True
    # attention implementation, threaded into the model config:
    # auto | xla | pallas | pallas_prefill | pallas_interpret. "auto" is
    # resolved by ONE rule on platform and shapes (runner.resolve_attn_impl)
    # and GET /stats reports the result with the reason whenever a kernel is
    # not used; "pallas" = the decode kernel, "pallas_prefill" additionally
    # the chunked-prefill kernel (both compiled and matched the XLA oracle on
    # a v5e, PERF.md "Bring-up"; their speed is not measured). An explicit
    # kernel request that cannot compile for the model's shapes is an error
    # at start-up.
    attn_impl: str = "auto"
    # tool-call extraction from chat completions (engine/tool_parser.py):
    # auto | hermes | json | off. The reference reaches this via vLLM's
    # --tool-call-parser flag (tutorials/13); we own the engine, so the
    # streaming parser lives here.
    tool_call_parser: str = "auto"
    # KV write placement (threaded into the model config): "pre" writes each
    # layer's K/V into the pool before attending; "post" attends over the
    # stale pool + in-register chunk K/V and commits all layers with one
    # batched scatter after the layer scan (avoids per-layer pool copies)
    kv_write_mode: str = "post"
    # decode-kernel overrides (threaded into the model config;
    # ops/pallas/paged_attention.py derives both from the shapes when 0, and
    # GET /stats decode_kernel_blocks says what it chose per bucket).
    # decode_pages_per_block: KV pages a grid cell consumes as one tile.
    # decode_prefetch_pages: pages the kernel's VMEM ring holds ahead of
    # compute, rounded up to whole blocks (at least two blocks).
    decode_pages_per_block: int = 0
    decode_prefetch_pages: int = 0
    # prefill-kernel memory pipeline tuning (threaded into the model config;
    # ops/pallas/prefill_attention.py). prefill_pages_per_block: KV pages
    # landed CONTIGUOUSLY per packed grid cell and folded as one wide
    # matmul (0 = auto: ~512 slots). prefill_prefetch_pages: page DMAs kept
    # in flight ahead of the cell being consumed (0 = auto: ~2 cells'
    # worth). Neither has been swept on this chip: no benchmark cell is
    # prefill-bound yet, and one must exist first (ROADMAP S4 / S7).
    prefill_pages_per_block: int = 0
    prefill_prefetch_pages: int = 0
    # fused paged-KV write: the prefill kernel commits the chunk's K/V to
    # its pool pages in-kernel (pools aliased input->output), replacing the
    # post-scan scatter pass — the chunk's KV crosses HBM once instead of
    # three times. Disable to fall back to the stacked-output + scatter
    # path (same numerics; tests assert bit-identical pools).
    prefill_fused_kv_write: bool = True
    # KV cache dtype (threaded into the model config; ops/quant.py):
    # auto (= model dtype) | bf16 | fp16 | int8. "int8" stores pages
    # quantized with per-page per-kv-head scales in a parallel scales pool:
    # the bandwidth-bound long-context decode step streams HALF the HBM
    # bytes, and the same kv_cache_memory_gb holds ~2x the tokens.
    # Dequantization happens inside the kernels' VMEM copy rings (fp KV
    # never round-trips through HBM); quantization inside the fused prefill
    # write and on the decode feedback commit. Offload/warm-start/
    # directory/migration blobs ship the int8 bytes + scales (serde v3,
    # CRC-framed, tp split/join-aware). Quality on the chip: not measured
    # (no int8 cell; tests/test_kv_quant.py bounds the logit error against
    # fp pools on the CPU). Requires kv_write_mode=post; not compatible with
    # speculative_k>0, sp/pp meshes, disagg kv_role, or device KV transfer.
    kv_cache_dtype: str = "auto"
    # tensor parallelism: attention heads + MLP hidden shard over the tp mesh
    # axis (parallel/shardings.py); the paged KV pool becomes per-chip — each
    # chip holds its kv-head shard of every page, so page ids, chains, hashes,
    # eviction, offload, and migration are tp-invariant (one logical page = N
    # physical head-shards; serde blobs gather/scatter shards at the tier
    # boundary — docs/multichip-serving.md). ``--tensor-parallel N`` is
    # accepted as an alias (reference vLLM spells it -tp).
    tensor_parallel_size: int = 1
    data_parallel_size: int = 1
    # sequence/context parallelism: long prefill chunks run ring attention
    # over the sp mesh axis (parallel/ring_attention.py) and activations
    # shard their token dim; decode is unaffected. Absent in the reference
    # (SURVEY.md §2.3) — first-class here.
    sequence_parallel_size: int = 1
    # expert parallelism: MoE expert weights shard over the ep mesh axis
    # (parallel/shardings.py moe_* specs); dense models ignore it.
    expert_parallel_size: int = 1
    # pipeline parallelism: the layer stack splits into contiguous stages
    # over the pp mesh axis; microbatches relay stage-to-stage inside the
    # jitted step (parallel/pipeline.py serving_layer_pipeline). The
    # reference reaches this via Ray + vLLM --pipeline-parallel-size
    # (ray-cluster.yaml:560-566); here it is one SPMD program, no Ray.
    pipeline_parallel_size: int = 1
    # multi-host serving (StatefulSet choreography, tutorial 15): process 0
    # serves HTTP and broadcasts device dispatches; others follow. The
    # coordinator address doubles as the JAX rendezvous (replaces the
    # reference's Ray cluster + EXPECTED_NODES barrier).
    distributed_coordinator: Optional[str] = None   # host:port of process 0
    distributed_num_processes: int = 1
    distributed_process_id: Optional[int] = None    # default: hostname -N suffix
    worker_sync_port: int = 8477
    enable_sleep_mode: bool = False
    # register unauthenticated state-mutating debug endpoints (POST
    # /metrics/reset); benchmark and test harnesses only — a production
    # server must not let any client wipe its observability windows
    enable_debug_endpoints: bool = False
    # persistent XLA compilation cache directory (utils/compile_cache.py);
    # None resolves via $PSTPU_COMPILE_CACHE_DIR then ~/.cache. In K8s this
    # is a PVC (helm values.compileCache) so pod restarts start warm instead
    # of paying 20-40 s per program variant.
    compilation_cache_dir: Optional[str] = None
    seed: int = 0
    # multi-LoRA serving (reference: vLLM --enable-lora + load/unload endpoints,
    # helm/templates/deployment-vllm-multi.yaml:197-207)
    enable_lora: bool = False
    max_loras: int = 4
    max_lora_rank: int = 16
    lora_target_modules: str = "q_proj,k_proj,v_proj,o_proj"
    # KV offload (LMCache-equivalent) wiring
    kv_offload_cpu_gb: float = 0.0
    # cap on pages moved per offload operation (one spill batch at eviction,
    # one restore chain at prefix match); 0 = unbounded, -1 (default) = AUTO:
    # the engine probes host<->device link bandwidth at startup
    # (engine/linkprobe.py) and derives the cap — 0 on PCIe-class links
    # (>= 1 GB/s, unbounded is right), a few pages on a slow link, where
    # recomputing a long history beats restoring it — the cap bounds the
    # engine-loop stall and the prefix recomputes past it. Which side a
    # directly attached v5e lands on is not measured (ROADMAP D4 decides
    # from the ledger). The measured bandwidth and chosen cap are
    # exported on /metrics (vllm:kv_offload_link_bandwidth_bytes_per_sec,
    # vllm:kv_offload_max_io_pages); an explicit >= 0 value skips the probe.
    # Spill overflow beyond the cap is dropped + reported evicted (the
    # global KV index stays truthful).
    kv_offload_max_io_pages: int = -1
    # proactive-spill high watermark (fraction of the page pool): past this
    # usage the scheduler spills the coldest evictable pages to the offload
    # tier ahead of eviction, so allocation storms at >100% occupancy free
    # slots without blocking device fetches (0 or >=1 disables)
    kv_spill_watermark: float = 0.9
    kv_offload_dir: Optional[str] = None
    kv_offload_disk_gb: float = 16.0
    # warm-start manifests (kvoffload/warmstart.py, docs/failure-handling.md
    # "Restarts & rolling upgrades"): on SIGTERM drain and every
    # warm_start_interval_s the engine spills its hottest chain-head pages +
    # the prefix-index metadata to the offload tier under a generation-fenced
    # per-engine namespace; on startup it restores them BEFORE reporting
    # ready, so restarts serve warm prefixes instead of recomputing them.
    # Requires at least one offload tier (cpu/disk/remote) to persist into —
    # a DISK or REMOTE tier for state to survive process death.
    warm_start: bool = False
    # seconds between periodic manifest spills (a hard crash loses at most
    # this much warm-state delta); <= 0 spills only on drain
    warm_start_interval_s: float = 60.0
    # manifest namespace in the offload tier; engines sharing a namespace
    # fence each other by generation (rolling upgrades reuse the old pod's
    # namespace). Default: kv_instance_id, else "<model>-<port>".
    warm_start_namespace: Optional[str] = None
    # manifest size cap in pages (highest-reuse-score chain heads first)
    warm_start_max_pages: int = 256
    kv_remote_url: Optional[str] = None
    kv_serde: str = "naive"            # naive | int8 (kvoffload/serde.py)
    kv_controller_url: Optional[str] = None
    # fleet-wide KV directory (production_stack_tpu/kvdirectory,
    # docs/kv-directory.md): hosted by the cache server. When set, the engine
    # PUBLISHES directory entries (prefix-cache inserts -> resident claims;
    # confirmed proactive-spill / warm-start saves -> shared-tier claims;
    # withdraw on evict) dirty-batched every kv_directory_flush_s, and PULLS
    # fleet-warm prefixes: on request admission, chunks beyond the local
    # prefix match that the directory reports restorable are prefetched from
    # the shared tier into the local host tiers so the device-thread restore
    # finds them locally. Entries are fenced by the warm-start generation
    # (boot epoch without --warm-start), so a restarted engine's stale
    # claims expire rather than poison lookups. Usually the same address as
    # --kv-remote-url.
    kv_directory_url: Optional[str] = None
    # seconds between directory publish-batch flushes (the engine-stats
    # cadence; lower = fresher router view, more directory traffic)
    kv_directory_flush_s: float = 5.0
    # consult the directory at admission and prefetch restorable prefix
    # blobs into the local tiers (--no-kv-directory-pull = publish-only)
    kv_directory_pull: bool = True
    # cap on pages one admission may prefetch from the shared tier
    kv_directory_pull_max_pages: int = 256
    kv_instance_id: Optional[str] = None
    advertise_host: Optional[str] = None  # URL other pods reach this engine at
    # live sequence migration (production_stack_tpu/migration,
    # docs/migration.md): serve POST /migrate_out (freeze a running stream,
    # ship its KV chain through the offload tiers + its sampling/decode
    # state to a target engine), POST /migrate_in (park the continuation),
    # POST /migrate_attach (stream it), GET /migratable (controller victim
    # listing). --no-migration disables the subsystem; without an offload
    # tier migrations still work but ship zero pages (full recompute).
    migration: bool = True
    # seconds a parked /migrate_in continuation waits for its
    # /migrate_attach before it is aborted (a router that died mid-handoff
    # must not leak a decoding sequence forever)
    migrate_attach_timeout_s: float = 30.0
    # scale-up warm-up (ISSUE 10 satellite, ROADMAP item 2 remainder): pull
    # the top-N fleet-warm chunks (cache server dir_top_prefixes) into the
    # LOCAL offload tiers during engine construction — BEFORE /ready — so a
    # freshly scaled-up engine serves its first requests with warm prefix
    # hits instead of a cold cache. Needs --kv-directory-url and an offload
    # tier; 0 disables. Counted as vllm:kv_directory_prefetched_pages_total.
    warm_prefetch_on_boot: int = 0
    # disaggregated prefill role: none | producer | consumer
    kv_role: str = "none"
    kv_transfer_port: int = 55555
    kv_peer_url: Optional[str] = None
    # device-to-device KV for co-located P/D slices: pages move over the XLA
    # transfer service (jax.experimental.transfer — ICI/DCN on TPU pods)
    # instead of host serde + TCP blobs (kvoffload/transfer.py). Both roles
    # must enable it; any failure falls back to the TCP path per page.
    kv_transfer_device: bool = False
    # host other pods reach this engine's transfer server at (producer side)
    kv_transfer_device_host: str = "127.0.0.1"
    # staging budget for device-pulled pages awaiting admission (consumer)
    kv_transfer_stage_mb: int = 1024
    # peer-to-peer KV fabric (production_stack_tpu/kvfabric, docs/kv-fabric.md):
    # one engine-to-engine transfer plane for streamed disagg prefill,
    # directory resident-page pulls, and migration page-chain ships. Frames
    # are versioned + CRC'd (pages, scales) pairs, so int8 engines transfer
    # with exact scales — this is what lifts the PR 14 int8 disagg gate.
    # Every fabric path falls back to the tier path on failure (counted as
    # vllm:kv_fabric_fallbacks_total).
    kv_fabric: bool = False
    # fabric listener port; 0 binds an ephemeral port (advertised via
    # GET /kv_fabric and the directory's resident claims)
    kv_fabric_port: int = 0
    # bounded per-request retries below the per-peer breaker
    kv_fabric_retries: int = 2
    # disagg producer: the decode peer's fabric listener ("host:port") or
    # its HTTP URL (GET /kv_fabric then resolves the advertised listener —
    # needed when the peer binds an ephemeral --kv-fabric-port 0)
    kv_fabric_peer: Optional[str] = None
    # streamed disagg prefill: layers shipped per frame (the consumer
    # assembles windows into whole pages); 0 ships whole pages in one frame
    kv_fabric_stream_layers: int = 0
    # distributed tracing (production_stack_tpu/tracing, docs/tracing.md):
    # head-based sampling rate for traces ROOTED at this engine (requests
    # arriving with a traceparent header keep the router's decision); 0.0
    # turns span recording off entirely. Buffer size bounds tracer memory.
    trace_sample_rate: float = 1.0
    trace_buffer_size: int = 4096
    # engine flight recorder (tracing/flightrecorder.py,
    # docs/observability.md): a bounded ring of structured engine events —
    # scheduler dispatches, KV evict/spill/restore, admission sheds, step
    # timings, JAX compiles — exported via the debug-gated
    # GET /v1/debug/flightrecorder and auto-dumped to disk on anomalies.
    # Default ON: the hot-path cost is one dict append per dispatch
    # (recorder-off against recorder-on on the chip: not measured).
    flight_recorder: bool = True
    flight_recorder_capacity: int = 8192
    # anomaly-dump directory (engine crash / SIGTERM drain / shed burst /
    # TTFT watermark breach write a JSON window here for postmortems); None
    # falls back to $PSTPU_FLIGHTRECORDER_DIR, else disk dumps are disabled
    # (the in-memory ring and the debug endpoint still work)
    flight_recorder_dump_dir: Optional[str] = None
    # TTFT breach watermark in ms: a request finishing with TTFT above this
    # triggers a (rate-limited) anomaly dump; 0 disables
    flight_recorder_ttft_watermark_ms: float = 0.0
    # shed-burst trigger: this many admission sheds within a 5 s window
    # dump the recorder (the overload-chaos postmortem); 0 disables
    flight_recorder_shed_burst: int = 10

    @property
    def name(self) -> str:
        return self.served_model_name or self.model


# --help text for flags whose one-line meaning is not obvious from the name;
# the dataclass comments stay the authoritative long-form docs
_FLAG_HELP = {
    "interactive_reserve": (
        "waiting-queue slots reserved for interactive-class requests: batch "
        "traffic sheds (429) this many slots before the queue bound, so "
        "batch load can never starve interactive admission "
        "(docs/failure-handling.md priority classes)"
    ),
    "batch_queue_deadline_s": (
        "queue deadline for batch-class requests only (0 = inherit "
        "--queue-deadline-s); set it shorter so congestion expires batch "
        "out of the queue before any interactive request"
    ),
    "batch_prefill_share": (
        "max share of one prefill dispatch's chunk slots batch-class rows "
        "may hold while an interactive prefill is waiting (1.0 = no cap)"
    ),
    "prefill_pages_per_block": (
        "prefill kernel: KV pages landed contiguously per packed grid cell "
        "and folded as one wide matmul (0 = auto ~512 KV slots; never swept "
        "on the chip)"
    ),
    "prefill_prefetch_pages": (
        "prefill kernel: page DMAs kept in flight ahead of the cell being "
        "consumed (0 = auto ~2 cells' worth)"
    ),
    "prefill_fused_kv_write": (
        "commit each prefill chunk's K/V to its pool pages from inside the "
        "attention kernel instead of a separate post-scan scatter pass "
        "(same numerics; --no-prefill-fused-kv-write falls back)"
    ),
    "kv_cache_dtype": (
        "KV cache dtype: auto (= model dtype) | bf16 | fp16 | int8. int8 "
        "halves the decode HBM byte stream and doubles effective pool "
        "capacity (per-page scales, in-kernel dequant; serde v3 blobs ship "
        "the quantized bytes through every KV tier)"
    ),
    "warm_start": (
        "spill a warm-start manifest (hot chain-head KV pages + prefix-index "
        "metadata) to the offload tier on drain and every "
        "--warm-start-interval-s, and restore it on startup before reporting "
        "ready — engine restarts keep their hot prefixes. Needs an offload "
        "tier (--kv-offload-dir / --kv-remote-url for restart durability)"
    ),
    "warm_start_interval_s": (
        "seconds between periodic warm-start manifest spills (bounds how "
        "much warm state a hard crash loses); <= 0 spills only on SIGTERM "
        "drain"
    ),
    "warm_start_namespace": (
        "offload-tier namespace for this engine's warm-start manifests; "
        "restarts/replacements reusing a namespace fence the previous "
        "incarnation by generation (default: --kv-instance-id, else "
        "<model>-<port>)"
    ),
    "warm_start_max_pages": (
        "cap on pages a warm-start manifest covers (highest-reuse-score "
        "chain heads kept first)"
    ),
    "kv_directory_url": (
        "fleet-wide KV directory address (the cache server; usually the "
        "same as --kv-remote-url): publish this engine's prefix-cache "
        "claims and pull fleet-warm prefixes from the shared tier "
        "(docs/kv-directory.md)"
    ),
    "kv_directory_flush_s": (
        "seconds between dirty-batched directory publish flushes"
    ),
    "kv_directory_pull": (
        "prefetch directory-reported restorable prefix blobs into the "
        "local tiers at request admission (--no-kv-directory-pull = "
        "publish-only)"
    ),
    "kv_directory_pull_max_pages": (
        "cap on pages one admission may prefetch from the shared tier"
    ),
    "kv_fabric": (
        "peer-to-peer KV fabric: engine-to-engine (pages, scales) frames "
        "for streamed disagg prefill, directory resident pulls, and "
        "migration ships, with tier fallback on any failure "
        "(docs/kv-fabric.md)"
    ),
    "kv_fabric_port": (
        "fabric listener port (0 = ephemeral; advertised on GET /kv_fabric)"
    ),
    "kv_fabric_retries": (
        "bounded fabric retries per request, below the per-peer breaker"
    ),
    "kv_fabric_peer": (
        "disagg producer: decode peer's fabric listener (host:port) or its "
        "HTTP URL (resolved via GET /kv_fabric)"
    ),
    "kv_fabric_stream_layers": (
        "streamed disagg prefill: layers per fabric frame so decode starts "
        "before the last layer lands (0 = whole pages per frame)"
    ),
    "migration": (
        "serve the live-sequence-migration endpoints (/migrate_out, "
        "/migrate_in, /migrate_attach, /migratable) so running streams can "
        "move between engines without dropping (docs/migration.md); "
        "--no-migration disables"
    ),
    "migrate_attach_timeout_s": (
        "seconds a parked migrated-in continuation waits for the router's "
        "/migrate_attach before it is aborted"
    ),
    "warm_prefetch_on_boot": (
        "pull this many top fleet-warm chunks (cache server "
        "dir_top_prefixes) into the local offload tiers before /ready, so "
        "a scaled-up engine starts warm; needs --kv-directory-url (0 = off)"
    ),
    "flight_recorder": (
        "record scheduler/KV/shed/compile engine events into a bounded ring "
        "(GET /v1/debug/flightrecorder with --enable-debug-endpoints; "
        "auto-dumped on anomalies; --no-flight-recorder disables)"
    ),
    "flight_recorder_dump_dir": (
        "directory anomaly dumps (engine crash, SIGTERM drain, shed burst, "
        "TTFT watermark breach) are written to as JSON; default "
        "$PSTPU_FLIGHTRECORDER_DIR, unset = no disk dumps"
    ),
    "flight_recorder_ttft_watermark_ms": (
        "dump the flight recorder when a request's TTFT exceeds this many "
        "milliseconds (rate-limited; 0 = off)"
    ),
    "flight_recorder_shed_burst": (
        "dump the flight recorder when this many admission sheds land "
        "within 5 s (0 = off)"
    ),
}


# short/alias spellings accepted in addition to the canonical --<field-name>
# flag (parity with the reference chart's TP config, which spells the knob
# both --tensor-parallel-size and -tp)
_FLAG_ALIASES = {
    "tensor_parallel_size": ("--tensor-parallel",),
}


def add_engine_args(p: argparse.ArgumentParser) -> None:
    for f in dataclasses.fields(EngineConfig):
        flag = "--" + f.name.replace("_", "-")
        aliases = _FLAG_ALIASES.get(f.name, ())
        ftype = str(f.type)
        help_ = _FLAG_HELP.get(f.name)
        if ftype == "bool" or isinstance(f.default, bool):
            p.add_argument(flag, *aliases, action=argparse.BooleanOptionalAction,
                           default=f.default, help=help_)
        else:
            typ = str
            if "int" in ftype or isinstance(f.default, int):
                typ = int
            elif "float" in ftype or isinstance(f.default, float):
                typ = float
            p.add_argument(flag, *aliases, type=typ, default=f.default,
                           dest=f.name, help=help_)


def config_from_args(args: argparse.Namespace) -> EngineConfig:
    kwargs = {
        f.name: getattr(args, f.name)
        for f in dataclasses.fields(EngineConfig)
        if hasattr(args, f.name)
    }
    return EngineConfig(**kwargs)
