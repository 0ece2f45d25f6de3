"""LLMEngine — continuous-batching serving core.

Execution model: one daemon thread owns the device (scheduler + ModelRunner)
and spins the step loop; the asyncio side (HTTP handlers) submits sequences
through a thread-safe inbox and receives ``RequestOutput`` items on per-request
asyncio queues. This is the TPU-native equivalent of the vLLM engine process
the reference stack treats as a black box (SURVEY.md §1 L4 contract).
"""

from __future__ import annotations

import asyncio
import functools
import os
import dataclasses
import queue as queue_mod
import threading
import time
from typing import Any, AsyncIterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from production_stack_tpu import tracing
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.kv_manager import KVPageManager
from production_stack_tpu.engine.model_loader import load_model
from production_stack_tpu.engine.runner import (
    ModelRunner,
    ProgramBuildError,
    StepInput,
)
from production_stack_tpu.engine.lora import LoRAManager
from production_stack_tpu.engine.step_programs import store_stats
from production_stack_tpu.engine.scheduler import (
    SamplingParams, ScheduledBatch, Scheduler, Sequence, host_staged,
)
from production_stack_tpu.engine.tokenizer import load_tokenizer
from production_stack_tpu.tracing import profiler
from production_stack_tpu.utils.logging import init_logger

logger = init_logger(__name__)

_NO_ATTRS: dict = {}  # a span's attributes while no profile runs


class _LoopSection:
    """``with engine._section(name, **attrs):`` — one section of the engine
    loop. On exit its wall seconds go into ``loop_seconds[name]`` (and stay in
    ``.seconds``); while a profile runs it is also the span
    ``pstpu.loop.<name>`` in the profiler's trace (tracing/profiler.py).

    ``apply`` and ``emit`` also run INSIDE a dispatch (a chained decode applies
    each fetched group while later bursts still compute): what they book while
    another section is open is taken off that section, so ``wait``,
    ``schedule``, ``step``, ``apply`` and ``emit`` are disjoint and sum to the
    loop's wall. ``stage``, ``call``, ``fetch``, ``hold``, ``runahead``,
    ``chain_dispatch`` and ``chain_fetch`` are parts of ``step``: they nest
    inside it and it keeps their seconds.

    What a section learns only once it is open (which dispatch its turn
    enqueued, and why nothing was queued ahead of it) goes into its span
    through ``annotate``; callers build such attributes only while
    ``profiler.active()``."""

    __slots__ = ("_secs", "_name", "_span", "_t0", "_inner0", "seconds")

    def __init__(self, secs: dict, name: str, attrs: dict):
        self._secs, self._name = secs, name
        self._span = profiler.span("pstpu.loop." + name, **attrs)
        self.seconds = 0.0

    def __enter__(self):
        self._span.__enter__()
        self._inner0 = self._secs["apply"] + self._secs["emit"]
        self._t0 = time.perf_counter()
        return self

    def annotate(self, **attrs) -> None:
        self._span.set_metadata(**attrs)

    def __exit__(self, *exc):
        secs = self._secs
        inner = secs["apply"] + secs["emit"] - self._inner0
        self.seconds = time.perf_counter() - self._t0 - inner
        secs[self._name] += self.seconds
        self._span.__exit__(*exc)
        return False


def _kv_tokens_read(kv_len, steps, window) -> int:
    """KV tokens that ``steps`` consecutive decode tokens attend, the first at
    a context of ``kv_len`` tokens (itself included), each capped by the
    sliding window: sum of min(c, window) for c in kv_len .. kv_len+steps-1.
    Arrays in, one integer out."""
    kv_len = np.asarray(kv_len, np.int64)
    steps = np.maximum(np.asarray(steps, np.int64), 0)
    last = kv_len + steps - 1
    total = steps * (kv_len + last) // 2
    if window:
        over_first = np.maximum(kv_len, window + 1)
        n_over = np.maximum(last - over_first + 1, 0)
        total = total - n_over * ((over_first - window) + (last - window)) // 2
    return int(total.sum())


@dataclasses.dataclass
class _Dispatched:
    """A dispatch the device has and the host has not read yet."""

    batch: ScheduledBatch
    result: Any    # device tokens: [B, k] of a decode burst, [B] of a prefill step
    work: dict     # what it computes, for the flight recorder's ``step`` event
    step: int      # its step index
    t0: float      # when it was enqueued (perf_counter)
    drain: Optional[str]  # what had emptied the loop (None: behind one that still ran)
    first: bool    # its shape's first dispatch: built and timed to its result
    pinned: bool = True  # its rows still hold what the manager lent them for it


@functools.partial(jax.jit, static_argnums=1)
def _last_tokens(result, width: int):
    """[width] int32: each row's last token of a dispatch's result, padded
    with zeros (one program a result shape, whatever is fed from it)."""
    last = result[:, -1] if result.ndim == 2 else result
    return jnp.pad(last.astype(jnp.int32), (0, width - last.shape[0]))


@jax.jit
def _fed_ids(host_ids, fed_from, last):
    """The input tokens of a decode queued behind a running dispatch:
    ``last[fed_from]`` where a row is fed from it, the host's elsewhere."""
    fed = last[jnp.maximum(fed_from, 0)][:, None]
    return jnp.where(fed_from[:, None] >= 0, fed, host_ids)


def _placed_like_numpy(x):
    """``x``'s buffer as an array with no placement of its own: a jitted
    program takes it under the executable it built for the host's numpy input.
    (A committed array is another in-sharding to ``jax.jit``: every step
    program would compile a second time the first time it is fed from the
    device.) One device only; jax 0.9.0 has no public constructor for it."""
    from jax._src.array import ArrayImpl

    (device,) = x.sharding.device_set
    return ArrayImpl(
        jax.core.ShapedArray(x.shape, x.dtype),  # no mesh in its type either
        jax.sharding.SingleDeviceSharding(device), x._arrays, committed=False,
    )


@dataclasses.dataclass
class RequestOutput:
    seq_id: str
    text_delta: str
    token_ids: list[int]
    finished: bool
    finish_reason: Optional[str] = None
    prompt_tokens: int = 0
    completion_tokens: int = 0
    cached_tokens: int = 0
    # per-token logprob entries aligned with token_ids (when requested):
    # {"logprob": float, "top_ids": [int], "top_logprobs": [float]}
    logprobs: Optional[list] = None


class LLMEngine:
    def __init__(self, cfg: EngineConfig, mesh=None):
        from production_stack_tpu.utils.compile_cache import enable_persistent_cache

        scope = None
        if cfg.distributed_num_processes > 1:
            import jax as _jax

            # jax.distributed is already initialized by serve(); executables
            # cached under a different process topology must not be reused
            scope = (
                f"mh{cfg.distributed_num_processes}p{_jax.process_index()}"
            )
        enable_persistent_cache(cfg.compilation_cache_dir, scope=scope)
        self.cfg = cfg
        model_mod, model_cfg, params = load_model(
            cfg.model, max_model_len=cfg.max_model_len
        )
        # a family that keeps recurrent state beside its pages: what cannot
        # serve it refuses to start, what is on by default is switched off,
        # each with its reason (GET /stats repeats them)
        self.state_family = hasattr(model_mod, "init_state")
        self.state_family_refusals: dict[str, str] = {}
        self.state_family_off: dict[str, str] = {}
        if self.state_family:
            cfg = self._restrict_to_state_family(cfg)
            self.cfg = cfg
        if cfg.attn_impl != "auto":
            model_cfg = dataclasses.replace(model_cfg, attn_impl=cfg.attn_impl)
        if getattr(model_cfg, "kv_write_mode", "pre") != cfg.kv_write_mode:
            if any(
                f.name == "kv_write_mode" for f in dataclasses.fields(model_cfg)
            ):
                model_cfg = dataclasses.replace(
                    model_cfg, kv_write_mode=cfg.kv_write_mode
                )
            else:
                logger.warning(
                    "kv_write_mode=%s unsupported for this model family; "
                    "keeping 'pre'", cfg.kv_write_mode,
                )
        # decode/prefill-kernel pipeline tuning rides the model config the
        # same way attn_impl does (the kernel call sites live in the model
        # forwards)
        for knob in (
            "decode_pages_per_block", "decode_prefetch_pages",
            "prefill_pages_per_block", "prefill_prefetch_pages",
        ):
            val = getattr(cfg, knob, 0)
            if val and any(
                f.name == knob for f in dataclasses.fields(model_cfg)
            ):
                model_cfg = dataclasses.replace(model_cfg, **{knob: val})
        # fused paged-KV write is a bool (default on): copy it whenever the
        # model family has the field and the value differs
        if any(
            f.name == "prefill_fused_kv_write"
            for f in dataclasses.fields(model_cfg)
        ) and model_cfg.prefill_fused_kv_write != cfg.prefill_fused_kv_write:
            model_cfg = dataclasses.replace(
                model_cfg, prefill_fused_kv_write=cfg.prefill_fused_kv_write
            )
        # KV cache dtype rides the model config like attn_impl (the
        # quantized read/write sites live in the model forwards); int8 is
        # gated on the combinations the quant contract covers
        self.kv_quant = cfg.kv_cache_dtype == "int8"
        if cfg.kv_cache_dtype != "auto":
            if not any(
                f.name == "kv_cache_dtype" for f in dataclasses.fields(model_cfg)
            ):
                raise ValueError(
                    f"kv_cache_dtype={cfg.kv_cache_dtype} is not supported "
                    "for this model family"
                )
            model_cfg = dataclasses.replace(
                model_cfg, kv_cache_dtype=cfg.kv_cache_dtype
            )
        if self.kv_quant:
            if cfg.kv_write_mode != "post":
                raise ValueError(
                    "--kv-cache-dtype int8 requires --kv-write-mode post"
                )
            if cfg.speculative_k:
                raise ValueError(
                    "--kv-cache-dtype int8 is not compatible with "
                    "--speculative-k (the spec scan carries raw pool blocks)"
                )
            if cfg.sequence_parallel_size > 1 or cfg.pipeline_parallel_size > 1:
                raise ValueError(
                    "--kv-cache-dtype int8 does not compose with sp/pp meshes"
                )
            if (cfg.kv_role != "none" or cfg.kv_transfer_device) and not cfg.kv_fabric:
                # gate lifted by the KV fabric (docs/kv-fabric.md): fabric
                # frames are (pages, scales) pairs, so quantized pages ship
                # with their exact scales. Without the fabric, the transfer
                # paths still move raw pool bytes — keep the PR 14 gate.
                raise ValueError(
                    "--kv-cache-dtype int8 with disaggregated-prefill or "
                    "device KV transfer requires --kv-fabric (fabric frames "
                    "carry the per-page scales; the raw page paths would "
                    "ship quantized bytes without them)"
                )
        self.model_cfg = model_cfg
        self.tokenizer = load_tokenizer(
            cfg.tokenizer or (cfg.model if "/" in cfg.model or cfg.model.startswith(".") else None)
        )
        kv_itemsize = (
            1 if self.kv_quant
            else np.dtype(getattr(model_cfg, "dtype", None) or "bfloat16").itemsize
        )
        # ``num_kv_layers``: the layers that hold pages (a family whose other
        # layers keep recurrent state has fewer than ``num_layers``)
        page_bytes = (
            2 * model_cfg.num_kv_layers * cfg.page_size * model_cfg.num_kv_heads
            * model_cfg.head_dim  # k+v
            * kv_itemsize
        )
        if self.kv_quant:
            # per-page scale rows ride the pool budget too (f32 per kv head,
            # k and v) — a rounding detail next to the 2x page shrink that
            # DOUBLES how many tokens the same kv_cache_memory_gb holds
            page_bytes += 2 * model_cfg.num_kv_layers * model_cfg.num_kv_heads * 4
        # device telemetry (engine/devicemon.py): page footprint for the KV
        # pool-vs-headroom gauges, and the jax.monitoring compile listener
        # feeding vllm:compile_seconds_total + flight-recorder compile events
        self.kv_page_bytes = page_bytes
        from production_stack_tpu.engine import devicemon

        devicemon.install_compile_listener()
        # engine flight recorder (tracing/flightrecorder.py): bounded ring of
        # scheduler/KV/shed/step/compile events, auto-dumped on anomalies
        self._fr = tracing.configure_flightrecorder(
            capacity=cfg.flight_recorder_capacity,
            enabled=cfg.flight_recorder,
            dump_dir=(
                cfg.flight_recorder_dump_dir
                or os.environ.get("PSTPU_FLIGHTRECORDER_DIR")
            ),
        )
        num_pages = cfg.num_pages or max(64, int(cfg.kv_cache_memory_gb * 1e9 / page_bytes))
        from production_stack_tpu.parallel.mesh import make_mesh

        if mesh is None:
            mesh = make_mesh(
                tp=cfg.tensor_parallel_size,
                dp=cfg.data_parallel_size,
                sp=cfg.sequence_parallel_size,
                ep=cfg.expert_parallel_size,
                pp=cfg.pipeline_parallel_size,
            )
        # validate against the ACTUAL mesh so callers passing their own mesh
        # hit the same guards as config-built ones
        mesh_pp = dict(mesh.shape).get("pp", 1)
        mesh_dp = dict(mesh.shape).get("dp", 1)
        if mesh_pp > 1 and cfg.kv_write_mode != "post":
            raise ValueError(
                "--pipeline-parallel-size > 1 requires --kv-write-mode post"
            )
        if mesh_pp > 1 and mesh_dp > 1:
            raise ValueError(
                "pipeline parallelism does not compose with in-engine data "
                "parallelism yet; use router-level replicas for DP"
            )
        lora_targets = ()
        if cfg.enable_lora:
            from production_stack_tpu.engine.lora import _HF_TO_LEAF

            mods = [m.strip() for m in cfg.lora_target_modules.split(",") if m.strip()]
            bad = [m for m in mods if m not in _HF_TO_LEAF]
            if bad:
                raise ValueError(
                    f"unknown --lora-target-modules {bad}; valid: {sorted(_HF_TO_LEAF)}"
                )
            lora_targets = tuple(_HF_TO_LEAF[m] for m in mods)
        self.runner = ModelRunner(
            model_cfg, mesh=mesh, params=params, module=model_mod,
            num_pages=num_pages, page_size=cfg.page_size, seed=cfg.seed,
            enable_lora=cfg.enable_lora, max_loras=cfg.max_loras,
            max_lora_rank=cfg.max_lora_rank, lora_targets=lora_targets,
            max_batch=cfg.max_num_seqs, state_slots=cfg.max_num_seqs,
        )
        # KV quantization observability: bytes one token costs the pool
        # (the byte-wall number), and a startup quantize->dequantize
        # round-trip error bound on synthetic normal data — a cheap on-box
        # sanity check that the quant math is sane on this build, exported
        # as vllm:kv_quant_dequant_err_max
        from production_stack_tpu.ops.quant import kv_bytes_per_token

        self.kv_bytes_per_token = kv_bytes_per_token(
            model_cfg.num_kv_layers, model_cfg.num_kv_heads, model_cfg.head_dim,
            cfg.page_size, self.kv_quant,
            np.dtype(getattr(model_cfg, "dtype", None) or "bfloat16").itemsize,
        )
        self.kv_quant_dequant_err_max = 0.0
        if self.kv_quant:
            from production_stack_tpu.ops.quant import (
                dequantize_page_host,
                quantize_page_host,
            )

            rng_chk = np.random.RandomState(0)
            x = rng_chk.randn(
                model_cfg.num_kv_layers, cfg.page_size, model_cfg.num_kv_heads,
                model_cfg.head_dim,
            ).astype(np.float32)
            qx, sx = quantize_page_host(x)
            self.kv_quant_dequant_err_max = float(
                np.abs(dequantize_page_host(qx, sx) - x).max()
                / max(np.abs(x).max(), 1e-9)
            )
        # serving mesh degrees, read from the ACTUAL mesh (a caller-passed
        # mesh wins over the config): /stats + vllm:tensor_parallel_degree +
        # the flight recorder's sched events all report these, and the paged
        # pool's per-chip footprint is kv_page_bytes / tp per shard
        # (docs/multichip-serving.md)
        mesh_shape = dict(mesh.shape)
        self.tensor_parallel = mesh_shape.get("tp", 1)
        self.mesh_devices = int(mesh.devices.size)
        import jax

        dev0 = mesh.devices.flat[0]
        self.device_info = {
            "platform": dev0.platform,
            "device_kind": dev0.device_kind,
            "device_count": len(jax.devices()),
        }
        self.lora: Optional[LoRAManager] = None
        if cfg.enable_lora:
            self.lora = LoRAManager(
                self.runner, max_loras=cfg.max_loras, max_rank=cfg.max_lora_rank
            )
        self._offload = self._make_offload_connector(cfg)
        # offload I/O budget: explicit >= 0 is honored verbatim; the -1
        # default auto-derives from a startup link-bandwidth probe (0 on
        # PCIe-class links) — both the measurement and the chosen cap are
        # exported on /metrics. No offload configured -> nothing to cap.
        self.kv_link_bandwidth_bytes_per_s: Optional[float] = None
        self._max_io_pages = cfg.kv_offload_max_io_pages
        if self._max_io_pages < 0:
            if self._offload is not None:
                from production_stack_tpu.engine.linkprobe import (
                    derive_max_io_pages,
                    probe_link_bandwidth,
                )

                bw = probe_link_bandwidth()
                self.kv_link_bandwidth_bytes_per_s = bw
                self._max_io_pages = derive_max_io_pages(bw, page_bytes)
                logger.info(
                    "kv offload link probe: %s MB/s -> max_io_pages=%d",
                    "?" if bw is None else f"{bw / 1e6:.1f}",
                    self._max_io_pages,
                )
            else:
                self._max_io_pages = 0
        self.kv = KVPageManager(
            num_pages, cfg.page_size, offload=self._offload,
            max_io_pages=self._max_io_pages,
            spill_watermark=cfg.kv_spill_watermark,
            state_slots=self.runner.state_slots,
        )
        # warm-start manifests (kvoffload/warmstart.py): restore the previous
        # incarnation's hot working set into the pool BEFORE the API server
        # exists, so the first post-restart requests hit warm prefixes. The
        # restore runs here on the construction thread — the engine loop has
        # not started, so the batched set_pages uploads race nothing.
        self.warm = None
        if cfg.warm_start:
            if self._offload is None:
                logger.warning(
                    "--warm-start needs an offload tier that survives "
                    "restarts (--kv-offload-dir or --kv-remote-url); disabled"
                )
            elif cfg.distributed_num_processes > 1:
                # the restore dispatches device programs during __init__,
                # before serve() wraps the runner in the multi-host
                # broadcaster — followers would never see them and desync
                logger.warning(
                    "--warm-start is single-host only for now; disabled"
                )
            else:
                from production_stack_tpu.kvoffload.warmstart import (
                    WarmStartManager,
                )

                self.warm = WarmStartManager(
                    self.kv, self._offload,
                    namespace=(
                        cfg.warm_start_namespace or cfg.kv_instance_id
                        or f"{cfg.name}-{cfg.port}"
                    ),
                    interval_s=cfg.warm_start_interval_s,
                    max_pages=cfg.warm_start_max_pages,
                    model=cfg.name,
                )
                self.warm.restore()
        # fleet-wide KV directory (ISSUE 9, docs/kv-directory.md): publisher
        # advertises this engine's prefix-cache claims (dirty-batched,
        # off-thread); puller prefetches fleet-warm prefixes at admission.
        # Created AFTER warm restore so the generation fence tracks the
        # warm-start generation (boot epoch without --warm-start: wall-clock
        # seconds are monotonic across restarts, which is all fencing needs).
        self._kvdir_pub = None
        self._kvdir_pull = None
        if cfg.kv_directory_url:
            from production_stack_tpu.kvdirectory import (
                DirectoryPublisher,
                DirectoryPuller,
            )

            self._kvdir_pub = DirectoryPublisher(
                cfg.kv_directory_url,
                engine_url=self._advertised_url(cfg),
                page_size=cfg.page_size,
                generation=(
                    self.warm.generation if self.warm is not None
                    else int(time.time())
                ),
                flush_interval_s=cfg.kv_directory_flush_s,
                # shared-tier claims need the write-through remote tier;
                # without one this engine's blobs are private (publish-only
                # resident claims still feed router-v2 resident ranking)
                shared_enabled=(
                    self._offload is not None
                    and self._offload.store.remote is not None
                ),
            )
            self.kv.directory = self._kvdir_pub
            if self.kv.hash_to_page:
                # warm restore ran before the publisher existed: re-advertise
                # the restored working set under the NEW generation (this is
                # also what makes a reborn engine republish after a restart)
                self._kvdir_pub.publish_resident([
                    (h, self.kv.pages[pid].depth, self.kv.pages[pid].hits)
                    for h, pid in self.kv.hash_to_page.items()
                ])
            if (
                cfg.kv_directory_pull
                and self._offload is not None
                and self._offload.store.remote is not None
            ):
                # same gate as shared_enabled: the shared tier IS the remote
                # cache server — without one every prefetch would miss while
                # still paying a directory round trip per admission
                self._kvdir_pull = DirectoryPuller(
                    cfg.kv_directory_url, self.kv, self._offload.store,
                    cfg.page_size,
                    max_pages=cfg.kv_directory_pull_max_pages,
                )
            elif cfg.kv_directory_pull:
                logger.warning(
                    "--kv-directory-pull needs --kv-remote-url (the shared "
                    "tier blobs are pulled from the cache server); "
                    "publish-only mode"
                )
        # scale-up warm-up (docs/migration.md): pull the fleet's top warm
        # chunks into the LOCAL tiers before the API server exists (still on
        # the construction thread, like warm restore — blocking here is what
        # makes "warm before /ready" true). Blobs land tier-side only; the
        # first matching request's admission restores them into HBM through
        # the ordinary _extend_from_offload path and scores a prefix hit.
        self.kv_directory_prefetched_pages = 0
        if (
            cfg.warm_prefetch_on_boot > 0
            and cfg.kv_directory_url
            and self._offload is not None
        ):
            self.kv_directory_prefetched_pages = self._boot_prefetch(cfg)
        # disaggregated prefill (SURVEY.md §2.3): producer pushes finished
        # prefill KV to the decode peer; consumer receives into its store
        self._kv_sender = None
        self._kv_receiver = None
        if cfg.kv_role == "producer":
            if not cfg.kv_peer_url:
                raise ValueError("kv_role=producer requires --kv-peer-url")
            from production_stack_tpu.kvoffload.transfer import KVTransferSender

            self._kv_sender = KVTransferSender(cfg.kv_peer_url)
            if cfg.kv_transfer_device and cfg.distributed_num_processes <= 1:
                # single-host producer: same assignment protocol as the
                # multi-host path with P=1 — one endpoint, direct offers
                # (multi-host arming happens in serve() after the
                # BroadcastingRunner wrap: enable_multihost_device_kv)
                try:
                    self.runner.kv_endpoint_host = cfg.kv_transfer_device_host
                    self.runner.kv_endpoint_start()
                    self._kv_sender.enable_multihost(
                        [self.runner.kv_endpoint.address],
                        lambda pid, base, pullers: self.runner.kv_offer_page(
                            pid, base, pullers
                        ),
                    )
                except Exception as e:  # noqa: BLE001 - platform w/o transfer svc
                    logger.warning(
                        "device kv transfer unavailable (%s); using TCP blobs",
                        e,
                    )
        elif cfg.kv_role == "consumer":
            from production_stack_tpu.kvoffload.transfer import (
                DeviceStaging,
                KVTransferReceiver,
            )

            endpoint = self._make_device_endpoint(cfg)
            staging = None
            if endpoint is not None:
                staging = DeviceStaging(cfg.kv_transfer_stage_mb << 20)
                self._offload.device_staging = staging
            self._kv_receiver = KVTransferReceiver(
                self._offload.store, host=cfg.host, port=cfg.kv_transfer_port,
                device_endpoint=endpoint, staging=staging,
            )
            self._kv_receiver.start()
        # peer-to-peer KV fabric (ISSUE 16, docs/kv-fabric.md): one
        # engine-to-engine transfer plane for streamed disagg prefill,
        # directory resident-page pulls, and migration page-chain ships.
        # The listener serves resident pages straight off the device pool
        # (gathers run on the device thread); pushed frames land as tier
        # blobs in the LOCAL store, where the ordinary admission/restore
        # path finds them. Every fabric consumer falls back to the tier
        # path on failure (client breaker + counted fallbacks).
        self._fabric_server = None
        self._fabric_client = None
        self._fabric_peer_addr: Optional[str] = None
        if cfg.kv_fabric:
            from production_stack_tpu.kvfabric import (
                FrameAssembler,
                KVFabricClient,
                KVFabricServer,
            )

            self._fabric_asm = FrameAssembler()
            self._fabric_client = KVFabricClient(retries=cfg.kv_fabric_retries)
            self._fabric_server = KVFabricServer(
                host=cfg.host,
                port=cfg.kv_fabric_port,
                generation=(
                    self._kvdir_pub.generation
                    if self._kvdir_pub is not None
                    else (
                        self.warm.generation if self.warm is not None
                        else int(time.time())
                    )
                ),
                quant=self.kv_quant,
                page_size=cfg.page_size,
                nlayers=model_cfg.num_kv_layers,
                pages_fn=self._fabric_pages,
                sink_fn=self._fabric_sink,
                advertise_host=cfg.advertise_host or None,
            )
            self._fabric_server.start()
            if self._kvdir_pull is not None:
                # resident-page pulls go engine-to-engine: the puller gets
                # the fabric client plus this engine's advertised URL (so
                # it never "pulls" from itself) — tier fetch stays the
                # fallback inside the puller
                self._kvdir_pull.enable_fabric(
                    self._fabric_client,
                    self._advertised_url(cfg),
                    serde=self._offload.serde,
                )
        self.scheduler = Scheduler(
            self.kv,
            max_num_seqs=cfg.max_num_seqs,
            max_model_len=cfg.max_model_len,
            prefill_chunk=cfg.prefill_chunk if cfg.enable_chunked_prefill else 10**9,
            prefill_batch=cfg.prefill_batch,
            enable_prefix_caching=cfg.enable_prefix_caching,
            batch_multiple=cfg.data_parallel_size,
            decode_steps=cfg.decode_steps,
            decode_pipeline=cfg.decode_pipeline,
            # a family whose decode attention reads a row's gathered pages
            # once says so: every decode dispatch then has max_model_len's
            # own page-table width, and the decode programs differ by batch
            # bucket alone (7 shapes to compile cold, not 21: PERF.md PR 46)
            decode_page_bucket_floor=(
                -(-cfg.max_model_len // cfg.page_size)
                if getattr(model_cfg, "decode_one_page_width", False) else 0
            ),
            spec_k=cfg.speculative_k,
            spec_ngram=cfg.speculative_ngram,
            max_waiting_seqs=cfg.max_waiting_seqs,
            queue_deadline_s=cfg.queue_deadline_s,
            interactive_reserve=cfg.interactive_reserve,
            batch_queue_deadline_s=cfg.batch_queue_deadline_s,
            batch_prefill_share=cfg.batch_prefill_share,
            # whether running decode rows take one step inside a prefill
            # dispatch is what the runner reports of the model module and of
            # its own set-up, and nothing else
            rider_refusal=self.runner.rider_refusal,
        )
        # this loop dispatches run-ahead prefills behind in-flight chains
        # (_runahead_prefills), which is what licenses the scheduler's
        # one-extra-burst chaining floor past the admission-wait budget
        self.scheduler.runahead_available = True
        # live sequence migration (production_stack_tpu/migration): frozen
        # sequences are OUT of the running set but keep their pages while
        # the target decides; device-thread-owned by construction (freeze/
        # commit/rollback/abort all run as device commands), so no lock
        self._frozen: dict[str, Sequence] = {}  # owned-by: device-thread
        self.migration = None
        if cfg.migration:
            from production_stack_tpu.migration import MigrationManager

            self.migration = MigrationManager(self)
        self._inbox: queue_mod.Queue = queue_mod.Queue()
        # prefill dispatches whose results were never fetched (skip-fetch
        # optimization); a deferred device error taints these sequences
        self._unfetched: list = []
        # the dispatch the device has and the loop has not read yet: the loop
        # plans and enqueues the next one behind it, THEN reads it (_turn)
        self._inflight: Optional[_Dispatched] = None
        # device commands that arrived while a dispatch was in flight: they
        # run once the loop has drained (and nothing after them is read from
        # the inbox before they do)
        self._held_cmds: list = []
        # why nothing was running when the next dispatch goes out
        self._drain_reason = "idle"
        self._last_retire = 0.0
        # what the loop has measured of itself, to enqueue the next dispatch
        # as LATE as is safe (_hold_back): the device's seconds for a step
        # program's shape, and the host's from planning a dispatch to having
        # enqueued it
        self._device_secs: dict[tuple, float] = {}
        self._turn_secs = 0.004
        self._turn_t0 = 0.0
        # result shapes whose joining helpers are built (_enqueue)
        self._seams_built: set = set()
        # widest batch a step program returns: what _last_tokens pads to (a
        # prefill step's result holds its rows and the riders' slot)
        self._feed_width = max(
            self.scheduler._batch_bucket(cfg.max_num_seqs),
            self.scheduler._batch_bucket(cfg.prefill_batch)
            + self.scheduler.rider_slots,
        )
        # two-writer maps (event-loop generate() registers/pops, device
        # thread _emit/_process_token reads/writes): every touch goes
        # through _lock — graftcheck GC004 enforces the discipline
        self._outputs: dict[str, tuple[asyncio.AbstractEventLoop, asyncio.Queue]] = {}  # guarded-by: _lock
        self._texts: dict[str, str] = {}  # guarded-by: _lock
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._sleeping = False
        self._sleep_level = 0
        self._lock = threading.Lock()
        # serving stats (scraped by /metrics)
        self.total_prompt_tokens = 0
        self.total_generation_tokens = 0
        # dispatch-shape observability: chaining only engages on a quiescent
        # batch (each unchained dispatch pays its own host fetch; what that
        # costs on a directly attached chip is not measured)
        self.decode_dispatches_total = 0
        self.decode_chained_dispatches_total = 0
        # prefill dispatches issued while a decode chain was in flight
        # (run-ahead): the device queued them behind the chain instead of
        # idling through its fetch + scheduling turnaround
        self.runahead_prefill_dispatches_total = 0
        # dispatches enqueued while the one before them still ran, by kind,
        # and those that found the device idle, by what had emptied the loop
        # (docs/observability.md lists the reasons)
        self.queued_ahead_dispatches = {"decode": 0, "prefill": 0}
        self.queue_ahead_drains = dict.fromkeys((
            "idle", "late", "first_dispatch", "device_cmd", "host_staged_rows",
            "no_pages", "chained", "speculative", "multi_process",
            "kv_transfer", "step_error",
        ), 0)
        # work per decode dispatch, counted from the batch the scheduler
        # built (host numpy, no device read): KV tokens the decoded tokens
        # attend (min(context, sliding window) each — what a decode-attention
        # roofline prices)
        self.decode_kv_tokens_read_total = 0
        # tokens the state-space layers' selective scan walked (a family with
        # recurrent state): prompt tokens in prefill chunks, output tokens in
        # decode bursts; each crosses every state-space layer once
        self.ssm_prefill_tokens_total = 0
        self.ssm_decode_tokens_total = 0
        # what the device counted in its dispatches (a configuration with
        # ``step_counters``, models/lfm2.py: rows the expert layers routed by
        # expert, experts read, experts held), summed as the counters arrive
        # with the tokens; the family's ``counter_stats`` names them
        self.step_counter_totals = np.zeros(self.runner.num_counters, np.int64)
        # the device thread adds after every dispatch, stats() on its own
        # thread takes what the last dispatch before an idle spell left
        self._step_counter_lock = threading.Lock()
        # engine steps that raised (device thread is the only writer), and
        # the first step program that failed to BUILD (runner.
        # ProgramBuildError): every later batch of that shape fails the same
        # way, so /health answers 503 from then on instead of staying green
        # while requests finish with "error"
        self.step_errors_total = 0
        self.program_fault: Optional[str] = None
        self.spec_draft_tokens = 0     # drafts proposed (rounds * spec_k)
        self.spec_accepted_tokens = 0  # drafts the target accepted
        self.num_preemptions = 0
        # load-shed accounting (admission control). Single writer per
        # counter — a shared `dict[k] += 1` from two threads drops
        # increments (load/add/store is not atomic): requests_shed is
        # mutated ONLY on the engine device thread (_inbox_accept /
        # _shed_expired), api_requests_shed ONLY on the aiohttp event loop
        # (the API-layer fast-path 429); stats() sums them
        self.requests_shed = {"queue_full": 0, "queue_deadline": 0}
        self.api_requests_shed = 0
        # per-SLO-class shed accounting (docs/failure-handling.md priority
        # classes), same single-writer split: requests_shed_by_class is
        # mutated ONLY on the device thread, api_requests_shed_by_class ONLY
        # on the event loop (note_api_shed); stats() sums the pairs
        self.requests_shed_by_class = {"interactive": 0, "batch": 0}  # owned-by: device-thread
        self.api_requests_shed_by_class = {"interactive": 0, "batch": 0}  # owned-by: event-loop
        # admission instrumentation: arrival -> first prefill dispatch, in ms
        # (the piece of TTFT a chained decode dispatch can inflate — an
        # arrival mid-chain waits for the whole chain before its prefill).
        # /metrics exposes p50/p99 as the ttft_hop_admission_wait gauge.
        import collections

        self.admission_wait_ms: collections.deque = collections.deque(maxlen=2048)
        # recent arrival timestamps, feeding the adaptive chain-depth bound
        # (scheduler.arrival_rate): chaining pays off only on a quiescent
        # batch, so expected arrivals during a chain cap its depth
        self._arrival_times: collections.deque = collections.deque(maxlen=64)
        # per-burst wall-time EMA feeding the same bound; the 50 ms seed
        # only holds until the first measured burst replaces it
        self._burst_seconds = 0.05
        # engine-loop section time accounting (seconds, cumulative), scraped
        # via /metrics: attributes serving-loop overhead between the device
        # program (step = stage+dispatch+fetch) and the host-side bookkeeping
        # (apply = scheduler state, emit = detokenize+queue put)
        self.loop_seconds = {
            "wait": 0.0, "schedule": 0.0, "step": 0.0, "apply": 0.0,
            "emit": 0.0, "chain_dispatch": 0.0, "chain_fetch": 0.0,
            "stage": 0.0, "runahead": 0.0, "fetch": 0.0, "hold": 0.0,
            "call": 0.0,
        }
        # the runner books its host->device staging into the same accounting
        self.runner.section = self._section
        # per-request SLO accounting (ISSUE 7 tentpole b): every finished
        # sequence appends a terminal record (queue wait, TTFT, tokens,
        # inter-token p99, KV pages peak, outcome) to this bounded log; the
        # router scrapes GET /slo_records with a cursor and aggregates the
        # records into per-model/backend SLO attainment counters. Single
        # writer (this device thread); /slo_records snapshots with a retry.
        import itertools

        self.slo_records: collections.deque = collections.deque(maxlen=2048)
        self._slo_seq = itertools.count(1)
        # rolling window of recent interactive ok-request latencies, feeding
        # the interactive_{ttft,itl}_p99_ms gauges the fleet controller's
        # latency-protection policy scrapes (docs/failure-handling.md
        # priority classes); bounded deque appends are atomic, stats()
        # snapshots with list()
        self._interactive_ttft_ms: collections.deque = collections.deque(maxlen=64)  # owned-by: device-thread
        self._interactive_itl_ms: collections.deque = collections.deque(maxlen=64)  # owned-by: device-thread
        # engine step index: every dispatched batch increments it; flight
        # recorder events carry it so a debug window can be cut by step range
        self.step_idx = 0
        # shed-burst anomaly trigger (flight recorder): timestamps of recent
        # sheds across BOTH writer threads (deque.append is thread-safe)
        self._shed_times: collections.deque = collections.deque(maxlen=64)

    # -- admission control / load shedding ----------------------------------

    def saturated(self, priority: str = "interactive") -> bool:
        """Waiting queue at its configured bound for this SLO class — the
        API layer should shed new generation work with 429 + Retry-After
        instead of queueing it. Batch saturates ``interactive_reserve``
        slots early (scheduler.saturated)."""
        return self.scheduler.saturated(priority)

    def shed_retry_after(self) -> float:
        return max(0.0, self.cfg.shed_retry_after_s)

    def can_shed_queued(self) -> bool:
        """Whether already-accepted requests may still shed after submission
        (queue deadline, or the engine-side authoritative queue bound in
        _inbox_accept) — the API layer then defers response headers until
        the first engine output so a shed converts to a clean 429 instead of
        a committed 200."""
        return (
            self.scheduler.queue_deadline_s > 0
            or self.scheduler.max_waiting_seqs > 0
        )

    def _note_shed(self, reason: str, seq: "Optional[Sequence]" = None) -> None:
        """Flight-recorder shed event + burst detection: a burst of sheds is
        THE overload postmortem moment — dump the surrounding scheduler/KV
        window while it is still in the ring. Thread-safe (called from the
        device thread for engine sheds and the event loop for API-layer
        fast-path sheds)."""
        fr = self._fr
        now = time.monotonic()
        self._shed_times.append(now)
        if not fr.enabled:
            return
        tr = getattr(seq, "trace", None)
        fr.record(
            "shed", step=self.step_idx, reason=reason,
            seq_id=seq.seq_id if seq is not None else None,
            waiting=self.scheduler.num_waiting(),
            running=self.scheduler.num_running(),
            trace_id=getattr(tr, "trace_id", None),
        )
        burst = self.cfg.flight_recorder_shed_burst
        if burst > 0:
            recent = sum(1 for t in list(self._shed_times) if now - t <= 5.0)
            if recent >= burst:
                # async: sheds fire on the event loop (API fast path) and
                # the device thread — neither may pay the ring serialization
                fr.dump_async("shed_burst")

    def note_api_shed(
        self,
        request_id: Optional[str] = None,
        priority: str = "interactive",
    ) -> None:
        """API-layer fast-path shed (api_server owns that counter; the event,
        burst accounting, the per-class counter, AND the SLO terminal record
        land here so neither the recorder nor the router's availability
        counters are blind to the most common overload shed — no Sequence
        ever exists for these). Thread-safe: deque.append and the itertools
        cursor are atomic, and this is the only writer on the event loop."""
        if priority not in self.api_requests_shed_by_class:
            priority = "interactive"
        self.api_requests_shed_by_class[priority] += 1
        self._note_shed("api_queue_full")
        self.slo_records.append({
            "seq": next(self._slo_seq),
            "request_id": request_id or "unknown",
            "model": self.cfg.name,
            "outcome": "shed",
            "finish_reason": "shed",
            "priority": priority,
            "queue_ms": 0.0,
            "ttft_ms": None,
            "e2e_ms": None,
            "prompt_tokens": 0,
            "output_tokens": 0,
            "cached_tokens": 0,
            "itl_p99_ms": None,
            "kv_pages_peak": 0,
            "trace_id": None,
            "t": time.time(),
        })

    def _shed_expired(self) -> None:
        """Shed waiting requests past the queue deadline: finish with reason
        'shed' and emit the terminal output so the consumer (blocked on its
        output queue) converts it to a 429 instead of hanging. shed_exempt
        sequences (parallel-sampling siblings, see Sequence.shed_exempt) are
        skipped: their request is mid-stream — shedding one choice could
        never surface as a clean 429."""
        for s in self.scheduler.expired_waiting():
            if s.shed_exempt:
                continue
            self.scheduler._finish(s, "shed")
            self.requests_shed["queue_deadline"] += 1
            self.requests_shed_by_class[
                s.priority if s.priority in self.requests_shed_by_class
                else "interactive"
            ] += 1
            self._note_shed("queue_deadline", s)
            self._emit(s, "")

    def _recent_arrival_rate(self, window: float = 1.0) -> float:
        """Arrivals/sec over the trailing ``window`` seconds."""
        now = time.monotonic()
        n = 0
        for t in reversed(self._arrival_times):
            if now - t > window:
                break
            n += 1
        return n / window


    def _make_device_endpoint(self, cfg: EngineConfig):
        """Device-to-device KV endpoint (opt-in; falls back to None so the
        TCP blob path serves everything when the transfer service cannot
        start on this platform)."""
        if not cfg.kv_transfer_device:
            return None
        if cfg.distributed_num_processes > 1:
            # multi-host: endpoints are per-process and REPLICATED through
            # the step stream (runner.kv_endpoint_start); serve() arms them
            # via enable_multihost_device_kv after the broadcaster is wired
            return None
        from production_stack_tpu.kvoffload.transfer import DeviceKVEndpoint

        try:
            ep = DeviceKVEndpoint(self.runner, host=cfg.kv_transfer_device_host)
            logger.info("device kv endpoint at %s", ep.address)
            return ep
        except Exception as e:  # noqa: BLE001 - platform without transfer svc
            logger.warning(
                "device kv transfer unavailable (%s); using TCP blobs", e
            )
            return None

    def _restrict_to_state_family(self, cfg: EngineConfig) -> EngineConfig:
        """A cache hit means "a run of pages"; for a family with recurrent
        layers a run of pages without the state at its end is not a prefix of
        the model, and nobody writes that state down at a page boundary yet.
        So every plane that moves or reuses page runs refuses to start
        (ValueError naming the option and the reason), and what is on by
        default (prefix caching, migration) is switched off with its reason
        logged. No stand-ins, no silent fallbacks."""
        no_snapshot = (
            "a run of pages without the recurrent state at its end is not a "
            "prefix of this model, and no state snapshot is written at page "
            "boundaries yet"
        )
        one_device = (
            "the recurrent-state pool lives whole on one device: nothing "
            "shards or replicates it yet"
        )
        refusals = {
            "--kv-offload-cpu-gb / --kv-offload-dir / --kv-remote-url / "
            "--kv-controller-url (KV offload)": (
                cfg.kv_offload_cpu_gb > 0 or cfg.kv_offload_dir
                or cfg.kv_remote_url or cfg.kv_controller_url, no_snapshot),
            "--warm-start": (cfg.warm_start, no_snapshot),
            "--kv-directory-url / --warm-prefetch-on-boot (KV directory)": (
                cfg.kv_directory_url or cfg.warm_prefetch_on_boot > 0,
                no_snapshot),
            "--kv-fabric": (cfg.kv_fabric, no_snapshot),
            "--kv-role / --kv-transfer-device (disaggregated prefill)": (
                cfg.kv_role != "none" or cfg.kv_transfer_device, no_snapshot),
            "--speculative-k": (
                cfg.speculative_k > 0,
                "a rejected draft token cannot be taken back out of the "
                "recurrent state"),
            "--enable-lora": (
                cfg.enable_lora,
                "adapters are defined for the llama family's projections only"),
            "--kv-cache-dtype int8": (
                cfg.kv_cache_dtype == "int8",
                "the family's attention layers read fp pages (no quantised "
                "read path)"),
            "--tensor-parallel-size / --data-parallel-size / "
            "--sequence-parallel-size / --expert-parallel-size / "
            "--pipeline-parallel-size > 1, --distributed-num-processes > 1": (
                max(cfg.tensor_parallel_size, cfg.data_parallel_size,
                    cfg.sequence_parallel_size, cfg.expert_parallel_size,
                    cfg.pipeline_parallel_size,
                    cfg.distributed_num_processes) > 1, one_device),
        }
        self.state_family_refusals = {k: why for k, (_, why) in refusals.items()}
        for option, (asked, why) in refusals.items():
            if asked:
                raise ValueError(
                    f"model {cfg.model!r} keeps recurrent state beside its KV "
                    f"pages and cannot start with {option}: {why}"
                )
        off = {}
        if cfg.enable_prefix_caching:
            off["prefix_caching"] = no_snapshot
        if cfg.migration:
            off["migration"] = no_snapshot
        for what, why in off.items():
            logger.warning("%s is OFF for model %r: %s", what, cfg.model, why)
        self.state_family_off = off
        return dataclasses.replace(
            cfg, enable_prefix_caching=False, migration=False
        )

    def _make_offload_connector(self, cfg: EngineConfig):
        """Build the LMCache-equivalent offload connector when any tier or the
        KV-index controller is configured (SURVEY.md §7 step 5). A
        disaggregated-prefill consumer always gets a CPU tier — received KV
        lands there before admission restores it into HBM."""
        if cfg.kv_role == "consumer" and cfg.kv_offload_cpu_gb <= 0:
            cfg = dataclasses.replace(cfg, kv_offload_cpu_gb=2.0)
        if not (
            cfg.kv_offload_cpu_gb > 0
            or cfg.kv_offload_dir
            or cfg.kv_remote_url
            or cfg.kv_controller_url
            or cfg.kv_directory_url
        ):
            return None
        from production_stack_tpu.kvoffload.connector import KVOffloadConnector

        return KVOffloadConnector(
            self.runner,
            cpu_bytes=int(cfg.kv_offload_cpu_gb * 1e9),
            disk_path=cfg.kv_offload_dir,
            disk_bytes=int(cfg.kv_offload_disk_gb * 1e9) if cfg.kv_offload_dir else 0,
            remote_url=cfg.kv_remote_url,
            serde=cfg.kv_serde,
            controller_url=cfg.kv_controller_url,
            instance_id=cfg.kv_instance_id or f"{cfg.name}-{cfg.port}",
            engine_url=self._advertised_url(cfg),
        )

    def _boot_prefetch(self, cfg: EngineConfig) -> int:
        """Directory-driven scale-up prefetch: ask the cache server for the
        fleet's top warm chunks (``dir_top_prefixes``, heads-first) and pull
        their blobs into the LOCAL host tiers. Runs on the construction
        thread BEFORE the server reports ready. Never raises — a cold boot
        is a degradation, not a failure."""
        try:
            from production_stack_tpu.kvoffload.protocol import (
                BlockingClient,
                parse_hostport,
            )

            host, port = parse_hostport(cfg.kv_directory_url, default_port=8200)
            client = BlockingClient(host, port, timeout=10)
            try:
                hdr, _ = client.request({
                    "op": "dir_top_prefixes",
                    "limit": cfg.warm_prefetch_on_boot,
                    "page_size": cfg.page_size,
                })
            finally:
                client.close()
            keys = hdr.get("hashes") or []
            store = self._offload.store
            n = 0
            for key in keys:
                try:
                    if store.contains_local(key) or store.get(key) is not None:
                        n += 1
                except Exception:  # noqa: BLE001 - one bad blob: keep pulling
                    logger.exception("boot prefetch failed for %s", key)
            logger.info(
                "warm prefetch on boot: pulled %d/%d fleet-warm chunks into "
                "local tiers", n, len(keys),
            )
            return n
        except Exception as e:  # noqa: BLE001 - directory down = cold boot
            logger.warning("warm prefetch on boot failed: %s", e)
            return 0

    def _advertised_url(self, cfg: EngineConfig) -> str:
        """URL other pods (router, KV controller/directory consumers) reach
        this engine at. A wildcard bind address would never match a
        discovered endpoint, so it resolves to the pod hostname's address."""
        host = cfg.advertise_host or cfg.host
        if host in ("0.0.0.0", "::", ""):
            import socket

            try:
                host = socket.gethostbyname(socket.gethostname())
            except OSError:
                host = "127.0.0.1"
            if cfg.kv_controller_url or cfg.kv_directory_url:
                logger.warning(
                    "--advertise-host not set; registering with the KV "
                    "index as %s (set it to the pod IP for kvaware routing)",
                    host,
                )
        return f"http://{host}:{cfg.port}"

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> None:
        self._thread = threading.Thread(target=self._run_loop, daemon=True, name="engine-loop")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._inbox.put(None)
        if self._thread:
            self._thread.join(timeout=10)
        if self._kvdir_pub is not None:
            self._kvdir_pub.stop()
        if self._offload is not None:
            self._offload.stop()
        if self._kv_sender is not None:
            self._kv_sender.close()
        ep = getattr(self.runner, "kv_endpoint", None)
        if ep is not None:
            ep.close()
        if self._kv_receiver is not None:
            self._kv_receiver.stop()
            if self._kv_receiver.device_endpoint is not None:
                self._kv_receiver.device_endpoint.close()
            if self._kv_receiver.staging is not None:
                self._kv_receiver.staging.clear()
        if self._fabric_server is not None:
            self._fabric_server.stop()
        if self._fabric_client is not None:
            self._fabric_client.close()

    def _run_on_device_thread(self, fn, timeout: float = 120.0):
        """Run ``fn`` on the engine device thread (serialized with steps via
        the device_cmd inbox) and return its result. Replicated runner
        dispatches MUST go through here from any other thread, or the
        leader's local dispatch order could diverge from the broadcast
        order the followers replay.

        Re-entrant: called ON the device thread (e.g. a staging-TTL expiry
        firing inside a prefix-cache probe during scheduling) it runs ``fn``
        directly — queueing would deadlock waiting on ourselves."""
        if threading.current_thread() is self._thread:
            return fn()
        done = threading.Event()
        box: dict = {}

        def run():
            try:
                box["r"] = fn()
            except Exception as e:  # noqa: BLE001 - re-raised on the caller
                box["e"] = e
            finally:
                done.set()

        self._inbox.put(("device_cmd", run))
        if not done.wait(timeout):
            raise TimeoutError("device thread did not service the command")
        if "e" in box:
            raise box["e"]
        return box.get("r")

    def enable_multihost_device_kv(self) -> None:
        """Arm the multi-host device-to-device KV path (called by serve() on
        the leader AFTER the BroadcastingRunner wrap): every process starts a
        transfer endpoint (replicated kv_endpoint_start, addresses exchanged
        through the JAX coordination KV store), the producer's sender learns
        the per-process addresses, and the consumer's receiver gets the
        replicated pull/unstage dispatchers. KV pages then move
        device->device over DCN between the prefill and decode clusters —
        the reference's NIXL GPU-direct analogue
        (deployment-vllm-multi.yaml:256-296) — with TCP blobs as the
        per-page fallback."""
        self.runner.kv_endpoint_start()  # replicated -> all processes
        n = self.cfg.distributed_num_processes
        if self._kv_sender is not None:
            from jax._src import distributed as jdist

            client = jdist.global_state.client
            addrs = [
                client.blocking_key_value_get(f"pstpu/kv_ep/{i}", 300_000)
                for i in range(n)
            ]
            self._kv_sender.enable_multihost(
                addrs,
                lambda pid, base, pullers: self.runner.kv_offer_page(
                    pid, base, pullers
                ),
            )
        if self._kv_receiver is not None:
            from production_stack_tpu.kvoffload.transfer import DeviceStaging

            staging = DeviceStaging(
                self.cfg.kv_transfer_stage_mb << 20,
                on_expire=self._mh_unstage,
            )
            if self._offload is not None:
                self._offload.device_staging = staging
            self._kv_receiver.staging = staging
            self._kv_receiver.procs = n
            self._kv_receiver.pull_fn = self._mh_pull
            self._kv_receiver.unstage_fn = self._mh_unstage

    def _mh_pull(self, assignments, shape, dtype, key: str) -> int:
        return int(self._run_on_device_thread(
            lambda: self.runner.kv_pull_page(assignments, shape, dtype, key)
        ) or 0)

    def _mh_unstage(self, key: str) -> None:
        try:
            self._run_on_device_thread(
                lambda: self.runner.kv_unstage_page(key)
            )
        except Exception:  # noqa: BLE001 - cleanup is best-effort
            logger.exception("multi-host kv unstage(%s) failed", key)

    # -- request api (asyncio side) -----------------------------------------

    async def generate(
        self,
        seq_id: str,
        prompt: Optional[str] = None,
        prompt_token_ids: Optional[list[int]] = None,
        params: Optional[SamplingParams] = None,
        lora_name: Optional[str] = None,
        trace: Optional[object] = None,
        shed_exempt: bool = False,
        priority: str = "interactive",
    ) -> AsyncIterator[RequestOutput]:
        params = params or SamplingParams()
        if priority not in ("interactive", "batch"):
            priority = "interactive"  # closed label set, unknown -> default
        if lora_name and self.lora is None:
            raise ValueError("LoRA is not enabled (--enable-lora)")
        if prompt_token_ids is None:
            prompt_token_ids = self.tokenizer.encode(prompt or "")
        if not prompt_token_ids:
            prompt_token_ids = [self.tokenizer.bos_token_id]
        if len(prompt_token_ids) + 1 > self.cfg.max_model_len:
            raise ValueError(
                f"prompt has {len(prompt_token_ids)} tokens, max_model_len is "
                f"{self.cfg.max_model_len}"
            )
        if self._sleeping:
            raise RuntimeError("engine is sleeping")
        if self._kvdir_pull is not None and not lora_name:
            # fleet-warm pull (docs/kv-directory.md): prefetch directory-
            # reported restorable prefix blobs into the LOCAL host tiers
            # before the sequence reaches the scheduler, so the device-thread
            # restore reads locally instead of probing the remote per chunk.
            # Best-effort with its own timeout/backoff; LoRA prompts are
            # skipped (adapter-salted chains are never shared fleet-wide).
            try:
                await self._kvdir_pull.maybe_prefetch(prompt_token_ids)
            except Exception:  # noqa: BLE001 - pull is a hint, never a gate
                logger.exception("kv directory prefetch failed")
        lora_slot, cache_salt = 0, b""
        if lora_name:
            # atomic resolve+pin, LAST before enqueue: every later path runs
            # inside the try/finally, so the ref is always released
            lora_slot, cache_salt = self.lora.acquire(lora_name)
        out_q: asyncio.Queue = asyncio.Queue()
        loop = asyncio.get_running_loop()
        with self._lock:
            self._outputs[seq_id] = (loop, out_q)
            self._texts[seq_id] = ""
        seq = Sequence(
            seq_id=seq_id, prompt_ids=list(prompt_token_ids), params=params,
            lora_slot=lora_slot, cache_salt=cache_salt, trace=trace,
            shed_exempt=shed_exempt, priority=priority,
        )
        self._inbox.put(seq)
        try:
            while True:
                item = await out_q.get()
                yield item
                if item.finished:
                    break
        finally:
            with self._lock:
                self._outputs.pop(seq_id, None)
                self._texts.pop(seq_id, None)
            if lora_slot:
                self.lora.release(lora_slot)
            self._inbox.put(("abort", seq_id))

    def abort(self, seq_id: str) -> None:
        self._inbox.put(("abort", seq_id))

    # -- engine loop (device thread) ----------------------------------------

    def _drain_inbox(self, block: bool, defer_aborts: bool = False,
                     timeout: float = 0.5) -> list:
        """Drain queued arrivals/aborts/device commands. With
        ``defer_aborts`` (mid-chain run-ahead), aborts are RETURNED instead
        of applied: an abort frees the sequence's pages, and a page freed
        while a dispatched-but-unfetched chain still writes to it must not
        be reallocated to a run-ahead admission. The caller re-queues them
        once the chain has been applied (aborts are idempotent and
        order-independent — abort of an already-finished seq is a no-op).

        With a dispatch in flight (the loop's ordinary state, _turn) an abort
        is applied at once: the row is finished and answered, and what the
        manager lent it goes back when the last dispatch that names it has
        retired (Scheduler.pin / retire). A device command is HELD there, and
        nothing behind it is read, until the loop has drained."""
        deferred: list = []
        timeout = timeout if block else None
        while not self._held_cmds:
            try:
                item = self._inbox.get(block=block, timeout=timeout)
            except queue_mod.Empty:
                return deferred
            block = False
            if item is None:
                return deferred
            if isinstance(item, tuple) and item[0] == "device_cmd":
                if self._inflight is not None:
                    self._held_cmds.append(item[1])
                    continue
                item[1]()  # LoRA update / embed forward, serialized with steps
            elif isinstance(item, tuple) and item[0] == "abort":
                if defer_aborts:
                    deferred.append(item)
                    continue
                # a FROZEN sequence (mid-migration) is outside the
                # scheduler's queues; an abort (client disconnect during the
                # handoff window) must still free it or it leaks forever
                frozen = self._frozen.pop(item[1], None)
                if frozen is not None and not frozen.finished:
                    self.scheduler._finish(frozen, "abort")
                    self._emit(frozen, "")
                for s in self.scheduler.waiting + self.scheduler.running:
                    if s.seq_id == item[1] and not s.finished:
                        self.scheduler._finish(s, "abort")
                        # deliver the terminal output: a router-initiated
                        # abort (POST /abort) has a consumer still blocked on
                        # out_q.get() — without this it would wait forever
                        # even though the slot and pages are already freed
                        self._emit(s, "")
            else:
                self._inbox_accept(item)
        return deferred

    def _inbox_accept(self, seq: Sequence) -> None:
        self._arrival_times.append(time.monotonic())
        if self._sleeping:
            # a request can pass generate()'s sleeping check on the event loop
            # just as sleep flips the flag on the device thread; it must be
            # answered, not parked in the scheduler until wake
            seq.finished = True
            self._emit(seq, "", error=True)
            return
        sched = self.scheduler
        # authoritative queue bound: the API layer's saturation check races
        # a burst of arrivals (it reads scheduler state the inbox hasn't
        # drained into yet), so the bound is ENFORCED here on the device
        # thread — same free-seat projection (scheduler.saturated).
        # shed_exempt sequences (parallel-sampling siblings of an admitted,
        # mid-flight request — see Sequence.shed_exempt) bypass it:
        # admission control gates requests, not choices.
        if sched.saturated(seq.priority) and not seq.shed_exempt:
            sched._finish(seq, "shed")
            self.requests_shed["queue_full"] += 1
            self.requests_shed_by_class[
                seq.priority if seq.priority in self.requests_shed_by_class
                else "interactive"
            ] += 1
            self._note_shed("queue_full", seq)
            self._emit(seq, "")
            return
        sched.add(seq)

    def _run_loop(self) -> None:
        """One dispatch is queued on the device behind the one that runs: each
        pass plans the next dispatch from the state the running one WILL
        leave, enqueues it, and only then reads the running one's tokens,
        applies and streams them (_turn) — the host's turn for dispatch N
        happens while N+1 computes. A batch that needs the host between two
        steps runs with nothing in flight, as it always did."""
        logger.info("engine loop started (model=%s)", self.cfg.name)
        while not self._stop.is_set():
            if self._sleeping:
                time.sleep(0.05)
                self._drain_inbox(block=False)
                continue
            with self._section("wait"):
                if self._inflight is None:
                    while self._held_cmds:  # the loop has drained: run them
                        self._held_cmds.pop(0)()
                self._drain_inbox(
                    block=self._inflight is None and not self.scheduler.has_work()
                )
                self._shed_expired()  # queue-deadline load shedding
                if self.warm is not None:
                    # periodic warm-start manifest (crash protection): prefers
                    # idle loop iterations, forced past 2x the interval
                    self.warm.maybe_spill(busy=self.scheduler.has_work())
                # adaptive chain depth inputs: the scheduler caps chained
                # bursts so the expected number of arrivals stuck waiting
                # behind a chain stays below ~half a request
                # (scheduler.schedule)
                self.scheduler.arrival_rate = self._recent_arrival_rate()
                self.scheduler.burst_seconds = self._burst_seconds
                self.scheduler.last_arrival_age = (
                    time.monotonic() - self._arrival_times[-1]
                    if self._arrival_times else float("inf")
                )
            if self._inflight is not None:
                # held back: the dispatch that the next turn may enqueue
                with self._section("step"), self._section(
                    "hold", **self._seq_attr(self.step_idx + 1)
                ):
                    self._hold_back()
            self._turn_t0 = time.perf_counter()
            with self._section("schedule"):  # the scheduler's decision alone
                batch = self._plan()
            if batch is not None or self._inflight is not None:
                self._turn(batch)
        logger.info("engine loop exited")

    @staticmethod
    def _shape(batch) -> tuple:
        return batch.kind, batch.input_ids.shape, batch.page_table.shape

    @staticmethod
    def _step_input(batch, input_ids=None, rider_ids=None) -> StepInput:
        """``batch`` as the runner takes it; ``input_ids`` / ``rider_ids``
        where the device holds some of the tokens (_enqueue)."""
        r = batch.riders
        return StepInput(
            batch.input_ids if input_ids is None else input_ids,
            batch.positions, batch.page_table, batch.kv_lens,
            batch.temperature, batch.top_k, batch.top_p,
            lora_ids=batch.lora_ids, kv_limits=batch.kv_limits,
            state_slots=batch.state_slots,
            riders=r and (
                r.input_ids if rider_ids is None else rider_ids, r.positions,
                r.page_table, r.kv_lens, r.temperature, r.top_k, r.top_p,
                r.state_slots,
            ),
        )

    def _hold_back(self) -> None:
        """Wait, reading the inbox, until the running dispatch is a margin
        from its end: the dispatch queued behind it is then planned with the
        arrivals of nearly the whole of it, and a request waits for one
        dispatch, not two, before its prefill. The end is reckoned from the
        device's seconds for the same shape the last time (nothing is held
        back behind a shape not timed yet); the margin is the loop's own
        measured turn four times over, a fifth of the dispatch, 10 ms at the
        least. Too long a hold costs idle time (``late``), never tokens."""
        running = self._inflight
        secs = self._device_secs.get(self._shape(running.batch))
        if secs is None:
            return
        margin = max(4 * self._turn_secs, 0.2 * secs, 0.010)
        enqueue_at = max(running.t0, self._last_retire) + secs - margin
        while not self._held_cmds and not self._stop.is_set():
            left = enqueue_at - time.perf_counter()
            if left <= 0:
                return
            self._drain_inbox(block=True, timeout=left)

    def _plan(self) -> Optional[ScheduledBatch]:
        """The next dispatch: behind the one in flight where there is one
        (planned from the state it will leave), else from the state as it
        is. Where nothing can be queued, ``_drain_reason`` keeps why."""
        sched = self.scheduler
        if self._inflight is None:
            batch = sched.schedule()
            if batch is None:
                self._drain_reason = "idle"
            return batch
        if self._held_cmds:
            self._drain_reason = "device_cmd"
            return None
        batch = sched.schedule(
            ahead_of=self._inflight.batch, allow=self._runahead_allowed
        )
        if batch is None:
            self._drain_reason = sched.ahead_refusal or "idle"
        return batch

    def _synchronous(self, batch) -> Optional[str]:
        """Why ``batch`` runs with nothing queued behind or before it (None:
        it may be queued). Read from the engine's roles and the batch itself:
        a mesh whose leader broadcasts before it runs, a producer that ships
        KV at apply, speculative rounds, a chain, rows whose dispatch is
        staged from the host's copy of their tokens."""
        if self.cfg.distributed_num_processes > 1:
            return "multi_process"
        if self._kv_sender is not None:
            return "kv_transfer"
        if self.scheduler.spec_k:
            return "speculative"
        if batch.bursts > 1:
            return "chained"
        if batch.want_logprobs or batch.want_penalties or not all(
            self._runahead_allowed(s) for s in batch.seqs
        ):
            return "host_staged_rows"
        return None

    def _shape_known(self, batch) -> bool:
        """Whether ``batch``'s step program has been dispatched before (a
        shape's first dispatch is timed to its result, runner._dispatch: it
        goes out with nothing in flight)."""
        k = self.scheduler.decode_steps
        kind, ids, pages = self._shape(batch)
        want = (
            ("multi_step", (k, False, False), ids, pages)
            if kind == "decode" and k > 1
            else ("step", (False, False), ids, pages)
        )
        return any(key[:4] == want for key in self.runner._programs)

    def _turn(self, batch) -> None:
        """Enqueue ``batch`` (None: nothing to queue) and retire the dispatch
        that was in flight, in that order; a failure anywhere makes the rows
        of both suspect."""
        running = feeds = self._inflight
        queued: Optional[_Dispatched] = None
        step = self._section(
            "step",
            **(self._dispatch_attrs(batch)
               if batch is not None and profiler.active() else {}),
        )
        try:
            with step:
                why, first = None, False
                if batch is not None:
                    seq = self.step_idx + 1  # the dispatch this turn hands over
                    why = self._synchronous(batch) if running is None else None
                    first = why is None and not self._shape_known(batch)
                    if first and running is not None:
                        # timed to its result: nothing runs beside it
                        self._retire(running)
                        running = None
                        self._drain_reason = "first_dispatch"
                    if why is None:
                        queued = self._enqueue(batch, feeds, running, first)
                        drain = queued.drain
                    else:
                        self._dispatch_now(batch, why)
                        drain = why
                    if profiler.active():
                        # WHICH dispatch the turn enqueued and, where the device
                        # had nothing queued behind the one before, why (the words
                        # of the ``sched`` event and ``queue_ahead_drains_total``;
                        # a trace keeps no empty value, so none when queued ahead)
                        step.annotate(
                            seq=seq, **({"drain": drain} if drain else _NO_ATTRS)
                        )
                if running is not None:
                    self._retire(running)
            self._inflight = queued
        except Exception as step_err:
            self._step_failed(step_err, batch, feeds, queued)

    def _step_failed(self, step_err, batch, feeds, queued) -> None:
        """A turn raised: what failed, what ran before it and what was queued
        behind it share the pools, so the rows of all of them are finished
        with ``error``, and so are those of the prefill dispatches nobody
        fetched."""
        logger.exception("engine step failed; aborting batch")
        self.step_errors_total += 1
        if (
            isinstance(step_err, ProgramBuildError)
            and self.program_fault is None
        ):
            self.program_fault = str(step_err)[:2000]
            logger.critical(
                "a step program failed to build; /health now "
                "answers 503: %s", self.program_fault,
            )
        # postmortem: the window of scheduler/KV/compile events that
        # led INTO this failure, while it is still in the ring
        self._fr.record(
            "error", step=self.step_idx,
            batch_kind=(batch or feeds.batch).kind,
            error=repr(step_err)[:500],
        )
        self._fr.dump("engine_step_error", force=True)
        if self.cfg.distributed_num_processes > 1:
            # multi-host: catch-and-continue would leave the leader
            # serving while followers are dead or desynced (a broadcast
            # happens before local execution). Exit so K8s restarts the
            # StatefulSet and the set re-rendezvouses — this enforces
            # the documented failure model (distributed.py).
            logger.critical(
                "fatal in multi-host mode: exiting so the pod set "
                "restarts in sync"
            )
            os._exit(13)
        flying = [d for d in (feeds, queued) if d is not None and d.pinned]
        suspect = list(batch.rows) if batch is not None else []
        for b in [d.batch for d in flying] + self._unfetched:
            suspect.extend(b.rows)
        self._unfetched.clear()
        for s in suspect:
            if not s.finished:
                self.scheduler._finish(s, "error")
                self._emit(s, "", error=True)
        for d in flying:
            d.pinned = False
            self.scheduler.retire(d.batch)
        self._inflight = None
        self._drain_reason = "step_error"

    def _count_dispatch(self, batch, reason: Optional[str]) -> None:
        """One count a dispatch: queued ahead (``reason`` None), or what had
        emptied the loop before it; the flight recorder's ``sched`` event
        carries the same."""
        if reason is None:
            self.queued_ahead_dispatches[batch.kind] += 1
        else:
            self.queue_ahead_drains[reason] += 1
        self._record_sched_event(
            batch, queued_ahead=reason is None, drain=reason
        )
        if batch.kind == "prefill":
            self._note_first_dispatch(batch)

    def _enqueue(
        self, batch, feeds: Optional[_Dispatched],
        running: Optional[_Dispatched], first: bool,
    ) -> _Dispatched:
        """Hand ``batch`` to the device without reading anything back. The
        rows that ``feeds`` (the dispatch it was planned behind) feeds take
        their input token from its device-resident result; ``running`` is
        that dispatch while it has not been retired. The step programs are
        the ones every other dispatch runs. The first result of a shape has
        the two helpers that join dispatches built for its batch size, fed or
        not: a run's set-up meets them all, as it meets the step programs."""
        def fed(ids, fed_from):
            """``ids`` with the tokens ``feeds`` holds on the device."""
            if fed_from is None or not (fed_from >= 0).any():
                return ids
            ids = _fed_ids(
                ids, fed_from, _last_tokens(feeds.result, self._feed_width)
            )
            # the runner hands a one-chip program its inputs as they come;
            # over a mesh it places every input itself
            return _placed_like_numpy(ids) if self.mesh_devices == 1 else ids

        riders = batch.riders
        inp = self._step_input(
            batch, fed(batch.input_ids, batch.fed_from),
            riders and fed(riders.input_ids, riders.fed_from),
        )
        # the device ended before the next was enqueued: it stood idle
        drain = (
            self._drain_reason if running is None
            else "late" if running.result.is_ready() else None
        )
        self._count_dispatch(batch, drain)
        work = self._count_work(batch)
        self.scheduler.pin(batch)
        try:
            with self._section("call", **self._seq_attr(self.step_idx)):  # staging included
                if batch.kind == "decode":
                    self.decode_dispatches_total += 1
                    result = self.runner.step_multi(
                        inp, self.scheduler.decode_steps
                    )
                else:
                    result, _ = self.runner.step(inp)
                result.copy_to_host_async()
            if running is not None:
                # 50 ms is no turn: something compiled, or the host froze
                turn = min(time.perf_counter() - self._turn_t0, 0.05)
                self._turn_secs += 0.2 * (turn - self._turn_secs)
            if result.shape not in self._seams_built:
                self._seams_built.add(result.shape)
                last = _last_tokens(result, self._feed_width)
                slot = batch.riders and len(batch.riders.kv_lens)
                for rows in {len(batch.kv_lens), slot or len(batch.kv_lens)}:
                    _fed_ids(
                        np.zeros((rows, 1), np.int32),
                        np.full((rows,), -1, np.int32), last,
                    )
        except Exception:
            self.scheduler.retire(batch)
            raise
        return _Dispatched(
            batch, result, work, self.step_idx, time.perf_counter(), drain, first
        )

    def _retire(self, done: _Dispatched) -> None:
        """Wait for a dispatch's tokens (the loop's one blocking wait), apply
        and stream them, and let go of what its rows held for it."""
        batch = done.batch
        with self._section("fetch", **self._seq_attr(done.step)):
            tokens = np.asarray(done.result)
        now = time.perf_counter()
        # the device ran it once it had it AND what ran before it had ended
        wall = now - max(done.t0, self._last_retire)
        self._last_retire = now
        if not done.first:
            # the least seen lately: a wall the host was late for reads too long
            timed = self._device_secs.get(self._shape(batch), wall)
            self._device_secs[self._shape(batch)] = min(wall, 0.9 * timed + 0.1 * wall)
        self._count_device_work()
        self._unfetched.clear()  # what was dispatched before it has ended too
        if self._fr.enabled:
            self._fr.record(
                "step", step=done.step, batch_kind=batch.kind,
                wall_ms=round(wall * 1000, 3), bursts=batch.bursts,
                fetched=True, queued_ahead=done.drain is None, **done.work,
            )
        self._observe_dispatch(batch, wall)
        self._apply_and_emit(batch, tokens)
        done.pinned = False
        self.scheduler.retire(batch)

    def _observe_dispatch(self, batch, wall: float) -> None:
        """Dispatch-granular prefill-phase observability (the Grafana prefill
        panel): chunk latency of prefill dispatches whose result was waited
        for, and decode per-token time while a prefill is resident (the
        interleave the demand gate schedules)."""
        if batch.kind == "prefill":
            tracing.prefill_chunk_hist.observe(wall)
        elif batch.kind == "decode" and any(
            s.in_prefill for s in self.scheduler.running
        ):
            toks_n = max(1, self.scheduler.decode_steps * batch.bursts)
            tracing.interleaved_decode_hist.observe(wall / toks_n)

    def _dispatch_now(self, batch, why: str) -> None:
        """The synchronous path: dispatch ``batch`` with nothing in flight,
        fetch what the host needs of it, apply and stream it."""
        self._count_dispatch(batch, why)
        self._drain_reason = why
        secs = self.loop_seconds
        t0 = time.perf_counter() + secs["apply"] + secs["emit"]
        work = self._count_work(batch)
        tokens, lp_data, fetched = self._dispatch_batch(batch)
        self._count_device_work()
        self._last_retire = time.perf_counter()
        # less what a chained decode applied and streamed inside it
        step_wall = self._last_retire + secs["apply"] + secs["emit"] - t0
        if self._fr.enabled:
            # runner step timing, dispatch-granular: a fetched step's
            # wall is real device time; a skip-fetch dispatch's wall is
            # enqueue-only (the trailing fetched step absorbs its compute)
            self._fr.record(
                "step", step=self.step_idx, batch_kind=batch.kind,
                wall_ms=round(step_wall * 1000, 3), bursts=batch.bursts,
                fetched=fetched, queued_ahead=False, **work,
            )
        if fetched:
            self._unfetched.clear()  # a real fetch retires prior dispatches
            self._observe_dispatch(batch, step_wall)
        if tokens is not None:
            self._apply_and_emit(batch, tokens, lp_data)

    def _section(self, name: str, **attrs) -> _LoopSection:
        return _LoopSection(self.loop_seconds, name, attrs)

    @staticmethod
    def _seq_attr(seq: int) -> dict:
        """The dispatch a ``call`` / ``fetch`` / ``hold`` span hands over,
        waits for or holds back (the ``sched`` event's ``step``), built only
        while a profile runs."""
        return {"seq": seq} if profiler.active() else _NO_ATTRS

    def _dispatch_attrs(self, batch) -> dict:
        """What the scheduler knows of a dispatch, for its span in the
        profiler's trace. A shape's FIRST dispatch shows as the span
        ``pstpu.first_dispatch`` nested inside (runner._dispatch)."""
        decode, sched = batch.kind == "decode", self.scheduler
        return {
            "kind": batch.kind,
            # the branch _dispatch_batch takes
            "family": (
                "spec_step" if decode and sched.spec_k and batch.history is not None
                else "multi_step" if decode and sched.decode_steps > 1
                else "step"
            ),
            "rows": len(batch.seqs),
            "chunk": int(batch.input_ids.shape[1]),
            "pages": int(batch.page_table.shape[1]),
            "bursts": batch.bursts,
        }

    def _count_work(self, batch) -> dict:
        """The dispatch's work, for the flight recorder's ``step`` event; a
        decode's KV tokens read are also added to the total."""
        n = len(batch.seqs)
        window = getattr(self.model_cfg, "sliding_window", None)
        if batch.kind == "prefill":
            tokens = int(sum(batch.chunk_sizes))
            if self.state_family:
                self.ssm_prefill_tokens_total += tokens
            work = {"prefill_tokens": tokens}
            if batch.riders is not None and batch.riders.seqs:
                # the riders' one step each is decode attention's work too
                r = len(batch.riders.seqs)
                read = _kv_tokens_read(batch.riders.kv_lens[:r], 1, window)
                self.decode_kv_tokens_read_total += read
                if self.state_family:
                    self.ssm_decode_tokens_total += r
                work.update(rider_rows=r, kv_tokens_read=read)
            return work
        steps = max(1, self.scheduler.decode_steps) * batch.bursts
        kv_len = batch.kv_lens[:n]
        if batch.kv_limits is not None:
            # a row decodes while its KV length stays under its limit
            steps = np.minimum(steps, batch.kv_limits[:n] - kv_len + 1)
        read = _kv_tokens_read(kv_len, steps, window)
        self.decode_kv_tokens_read_total += read
        if self.state_family:
            self.ssm_decode_tokens_total += int(
                np.sum(np.maximum(np.broadcast_to(steps, (n,)), 0))
            )
        return {"kv_tokens_read": read}

    def _count_device_work(self) -> None:
        """Add the counters of the dispatches that have ended (they came with
        their tokens; one that still runs is counted at a later call)."""
        if self.runner.num_counters:
            with self._step_counter_lock:
                done = self.runner.take_counters()
                if done is not None:
                    self.step_counter_totals += done

    def _dispatch_batch(self, batch):
        """Stage and dispatch one scheduled batch and fetch what the host
        needs of it. Returns (tokens, lp_data, fetched): ``tokens`` is None
        when a chained decode was applied and emitted inline, ``lp_data`` is
        (chosen [B, cols], top_ids, top_lp [B, cols, K]) or None, and
        ``fetched`` says whether a host fetch retired the earlier dispatches."""
        fetched = True
        lp_data = None
        inp = self._step_input(batch)
        if batch.want_penalties:
            inp.history = batch.history
            inp.prompt_lens = batch.prompt_lens
            inp.presence = np.array(
                [s.params.presence_penalty for s in batch.seqs]
                + [0.0] * (len(batch.kv_lens) - len(batch.seqs)),
                np.float32,
            )
            inp.frequency = np.array(
                [s.params.frequency_penalty for s in batch.seqs]
                + [0.0] * (len(batch.kv_lens) - len(batch.seqs)),
                np.float32,
            )
            inp.repetition = np.array(
                [s.params.repetition_penalty for s in batch.seqs]
                + [1.0] * (len(batch.kv_lens) - len(batch.seqs)),
                np.float32,
            )
        # rows still under their min_tokens floor get EOS masked out
        # of the sampled distribution (vLLM semantics — suppressing
        # only the FINISH would feed a sampled EOS back into the
        # context and derail the continuation). Conservative within
        # a dispatch: the ban holds for ALL the tokens one dispatch
        # covers, and the scheduler caps chaining for rows near the
        # floor (scheduler.schedule), so the overshoot stays
        # < decode_steps regardless of pipeline depth; the
        # scheduler's finish gate stays as the exact backstop.
        eos = self.tokenizer.eos_token_id
        def _eos_ban(s):
            return (
                not s.params.ignore_eos
                and len(s.output_ids) < s.params.min_tokens
            )
        if any(s.params.logit_bias or _eos_ban(s) for s in batch.seqs):
            B = len(batch.kv_lens)
            # bucket the bias width so a batch's entry count doesn't
            # mint a fresh program variant per distinct size
            need = max(
                len(s.params.logit_bias or {}) + (1 if _eos_ban(s) else 0)
                for s in batch.seqs
            )
            K = 8
            while K < need:
                K *= 2
            V = self.model_cfg.vocab_size
            # out-of-range sentinel V drops unused slots on device
            bias_ids = np.full((B, K), V, np.int32)
            bias_vals = np.zeros((B, K), np.float32)
            for i, s in enumerate(batch.seqs):
                j = 0
                for tid, bv in (s.params.logit_bias or {}).items():
                    bias_ids[i, j] = tid
                    bias_vals[i, j] = bv
                    j += 1
                if _eos_ban(s):
                    bias_ids[i, j] = eos
                    bias_vals[i, j] = -1e9
            inp.bias_ids, inp.bias_vals = bias_ids, bias_vals
        if (
            batch.kind == "decode"
            and self.scheduler.spec_k
            and batch.history is not None
        ):
            tokens = np.asarray(
                self.runner.step_spec(
                    inp, batch.history, self.scheduler.decode_steps,
                    self.scheduler.spec_k, self.scheduler.spec_ngram,
                )
            )  # [B, steps, 1+spec_k], -1 padded
            emitted = tokens >= 0
            rounds = int(emitted.any(axis=2).sum())
            self.spec_draft_tokens += rounds * self.scheduler.spec_k
            # each round emits its accepted drafts plus one bonus token
            self.spec_accepted_tokens += int(emitted.sum()) - rounds
        elif batch.kind == "decode" and self.scheduler.decode_steps > 1:
            wlp = batch.want_logprobs
            self.decode_dispatches_total += 1
            if batch.bursts > 1:
                self.decode_chained_dispatches_total += 1
                t_chain = time.perf_counter()
                # chained bursts: all dispatches go out before any
                # fetch, so the chain costs bursts*compute + 1 round
                # trip for the LAST burst only.
                with self._section("chain_dispatch"):
                    devs = self.runner.step_multi_pipelined(
                        inp, self.scheduler.decode_steps, batch.bursts,
                        wlp,
                        # grouped on-device concat + eager host copy at
                        # each 4-burst boundary (see runner docstring);
                        # the logprobs path still fetches whole-chain
                        fetch_group=0 if wlp else 4,
                    )
                with self._section("chain_fetch"):
                    import jax.numpy as jnp

                    if wlp:
                        import jax

                        # one pytree fetch: device_get starts all four
                        # copies together (~1 RTT), where sequential
                        # np.asarray calls would pay one RTT each
                        tokens, *lps = jax.device_get((
                            jnp.concatenate([d[0] for d in devs], axis=1),
                            *(jnp.concatenate([d[1][x] for d in devs], axis=1)
                              for x in range(3)),
                        ))
                        lp_data = tuple(lps)
                    else:
                        # incremental grouped fetch: the runner already
                        # enqueued each group's on-device concat at its
                        # burst boundary and started its host copy, so
                        # group j's tokens stream back while groups
                        # j+1.. still compute — the fetch RTT (and the
                        # ~50 ms per-RPC floor, amortized 4x) hides
                        # inside the chain's own compute, and clients
                        # get a chunk per group instead of one
                        # chain-sized batch. Applying group j before
                        # j+1 lands is safe: a row that finishes
                        # (EOS/stop) keeps computing masked/discarded
                        # tokens, its freed pages cannot be reallocated
                        # until the next schedule() (this thread), and
                        # the garbage tokens write past the region the
                        # prefix cache registered.
                        gcats = devs
                        # run-ahead: admit fresh arrivals and dispatch
                        # their prefill chunks NOW — the device queues
                        # them straight behind the chain's bursts
                        # instead of idling through the chain's fetch +
                        # scheduling turnaround. Aborts are deferred
                        # (see _drain_inbox) so no page freed under the
                        # in-flight chain can be re-allocated here.
                        with self._section("runahead"):
                            ra_done, ra_inter = self._runahead_prefills(batch)
                        for c in gcats:
                            self._apply_and_emit(batch, np.asarray(c))
                        # the chain's fetches retire dispatches QUEUED
                        # BEFORE the chain; run-ahead intermediates came
                        # after, so they stay suspect until the next
                        # fetch unless a run-ahead final fetch follows
                        self._unfetched = ra_inter
                        for ra, ids in ra_done:
                            self._apply_and_emit(ra, np.asarray(ids))
                        if ra_done:
                            self._unfetched = []
                        fetched = False  # retirement handled above
                        tokens = None  # processed inline
                # per-burst wall time EMA (includes fetch + apply +
                # emit amortized over the chain — a mild
                # overestimate, erring toward shorter chains and so
                # better TTFT under arrivals)
                dt = (time.perf_counter() - t_chain) / batch.bursts
                self._burst_seconds = (
                    0.7 * self._burst_seconds + 0.3 * dt
                )
            elif wlp:
                toks, lps = self.runner.step_multi(
                    inp, self.scheduler.decode_steps, True
                )
                tokens = np.asarray(toks)
                lp_data = tuple(np.asarray(x) for x in lps)
            else:
                tokens = np.asarray(
                    self.runner.step_multi(inp, self.scheduler.decode_steps)
                )  # [B, k]
        elif batch.kind == "prefill" and not (
            batch.riders and batch.riders.seqs  # their tokens are needed
        ) and not any(
            s.num_computed + c >= s.prefill_len
            for s, c in zip(batch.seqs, batch.chunk_sizes)
        ):
            # every chunk in this step is intermediate — nobody's
            # prompt completes, so the sampled tokens are discarded
            # anyway. Dispatch async and skip the host fetch, so an
            # N-chunk prefill costs N*compute + 1 fetch instead of
            # N*(compute + fetch) (the fetch's cost on a directly
            # attached chip is not measured). A deferred device error
            # surfaces at the next fetched step; _unfetched records
            # whose KV state is then suspect so the handler can abort
            # them too, not just the batch it surfaced on.
            self.runner.step(inp)
            self._unfetched.append(batch)
            fetched = False
            tokens = np.full((len(batch.seqs),), -1, np.int32)
        elif batch.want_logprobs:
            ids, _, lps = self.runner.step(inp, want_logprobs=True)
            tokens = np.asarray(ids)
            lp_data = tuple(np.asarray(x)[:, None] for x in lps)
        else:
            ids, _ = self.runner.step(inp)
            tokens = np.asarray(ids)
        return tokens, lp_data, fetched

    def _record_sched_event(self, batch, **queue_ahead) -> None:
        """Flight-recorder "sched" event: the batch composition and the
        interleave-gate inputs that produced it, stamped with the step index
        and the members' trace ids so a slow request's spans cross-link to
        the exact dispatches that served (or starved) it. ``queue_ahead``:
        whether it was enqueued behind a running dispatch, else what had
        emptied the loop (_count_dispatch)."""
        self.step_idx += 1
        fr = self._fr
        if not fr.enabled:
            return
        trace_ids = [
            s.trace.trace_id
            for s in batch.seqs
            if s.trace is not None and getattr(s.trace, "sampled", False)
        ][:4]
        fr.record(
            "sched", step=self.step_idx, batch_kind=batch.kind,
            tp=self.tensor_parallel,
            rows=len(batch.seqs), bursts=batch.bursts,
            # decode rows that take one step inside this prefill dispatch
            riders=len(batch.riders.seqs) if batch.riders else 0,
            chunk_tokens=sum(batch.chunk_sizes) if batch.chunk_sizes else 0,
            seq_ids=[s.seq_id for s in batch.seqs[:8]],
            trace_ids=trace_ids,
            gate=getattr(self.scheduler, "last_gate", None),
            running=self.scheduler.num_running(),
            waiting=self.scheduler.num_waiting(),
            kv_usage=round(self.kv.usage(), 4),
            trace_id=trace_ids[0] if trace_ids else None,
            **queue_ahead,
        )

    def _note_first_dispatch(self, batch) -> None:
        """Record the admission-wait hop (arrival -> first prefill dispatch)
        for rows reaching the device for the first time — in the main loop
        or via run-ahead."""
        now = time.monotonic()
        for s in batch.seqs:
            if s.first_dispatch_time is None:
                s.first_dispatch_time = now
                self.admission_wait_ms.append((now - s.arrival_time) * 1000)

    @staticmethod
    def _runahead_allowed(s: Sequence) -> bool:
        """Rows whose dispatch needs no bias/penalty/logprob staging — that
        staging lives on the normal path only; others wait for it."""
        return not host_staged(s)

    def _runahead_prefills(self, chain_batch):
        """Dispatch prefill work for sequences disjoint from an in-flight
        decode chain (the device queues it behind the chain's bursts — zero
        idle). Returns (final_dispatches_to_fetch, intermediate_batches).
        Stops at the first final-chunk dispatch so a single trailing fetch
        retires every intermediate before it. Deferred aborts are re-queued
        HERE, before anything can raise — they are only processed at the
        next ordinary inbox drain, after the chain has been applied."""
        for item in self._drain_inbox(block=False, defer_aborts=True):
            self._inbox.put(item)
        ra_done: list = []
        ra_inter: list = []
        if self._sleeping:
            return ra_done, ra_inter
        exclude = {id(s) for s in chain_batch.seqs}
        for _ in range(4):  # bound the work queued behind one chain
            ra = self.scheduler.schedule_prefill_runahead(
                exclude, allow=self._runahead_allowed
            )
            if ra is None:
                break
            self._record_sched_event(ra)
            self._note_first_dispatch(ra)
            self.runahead_prefill_dispatches_total += 1
            inp = self._step_input(ra)
            if not any(
                s.num_computed + c >= s.prefill_len
                for s, c in zip(ra.seqs, ra.chunk_sizes)
            ):
                # all-intermediate chunks: skip-fetch (same optimization as
                # the main loop) and account the progress immediately so the
                # next planning round sees it
                self.runner.step(inp)
                self._unfetched.append(ra)
                ra_inter.append(ra)
                self._apply_and_emit(
                    ra, np.full((len(ra.seqs),), -1, np.int32)
                )
            else:
                ids, _ = self.runner.step(inp)
                ra_done.append((ra, ids))
                break  # one trailing fetch retires all intermediates above
        return ra_done, ra_inter

    def _apply_and_emit(self, batch, tokens, lp_data=None) -> None:
        """Apply one fetched token matrix to scheduler state and stream the
        resulting deltas — called once per dispatch, or once per BURST for
        incrementally-fetched chains (the per-column apply is identical
        either way; scheduler.apply_step skips finished rows)."""
        with self._section("apply"):
            events = self.scheduler.apply_step(
                batch, tokens, self.tokenizer.eos_token_id
            )
            if batch.kind == "prefill":
                for s, c in zip(batch.seqs, batch.chunk_sizes):
                    self.total_prompt_tokens += c
            if self._kv_sender is not None:
                # ship KV before emitting the finish event: the prefill HTTP
                # response must not return until the decode peer holds the KV
                pushed = set()
                for s, _, _, _ in events:
                    if s.finished and s.seq_id not in pushed:
                        pushed.add(s.seq_id)
                        self._push_finished_kv(s)
        with self._section("emit"):
            self._emit_events(events, lp_data)

    def _emit_events(self, events, lp_data) -> None:
        """Stream the applied tokens to their requests."""
        # group burst events per sequence: one RequestOutput per seq per
        # device step, carrying every new token (finished only on the
        # last, so consumers never drop trailing burst tokens)
        grouped: dict[str, tuple[Sequence, list[int], list]] = {}
        for s, tok, i, j in events:
            g = grouped.setdefault(s.seq_id, (s, [], []))
            g[1].append(tok)
            if lp_data is not None and s.params.logprobs is not None:
                n = min(s.params.logprobs, lp_data[1].shape[2])
                g[2].append({
                    "logprob": float(lp_data[0][i, j]),
                    "top_ids": lp_data[1][i, j, :n].tolist(),
                    "top_logprobs": lp_data[2][i, j, :n].tolist(),
                })
        for s, toks, lps in grouped.values():
            self.total_generation_tokens += len(toks)
            self._process_token(s, toks, lps or None)

    def _push_finished_kv(self, seq: Sequence) -> None:
        """Producer role: push every hashed page of a finished sequence to the
        decode peer. Runs on the device thread right after scheduler._finish
        registered the pages, so their pids are still valid (nothing else has
        allocated since)."""
        from production_stack_tpu.engine.kv_manager import prefix_hashes

        tokens = seq.prompt_ids + seq.output_ids
        hashes = list(prefix_hashes(tokens, self.kv.page_size, seq.cache_salt))
        if self._fabric_client is not None:
            # fabric-first: stream the whole chain as (pages, scales)
            # frames; anything the fabric could not cover falls through to
            # the per-page TCP-blob / device paths below (counted fallback)
            hashes = self._fabric_stream_push(hashes)
        for h in hashes:
            pid = self.kv.hash_to_page.get(h)
            if pid is None:
                continue
            key = h.hex()
            if self._kv_sender._mh_addrs is not None and not self.kv_quant:
                # device path (assignment protocol, single- or multi-host):
                # REPLICATED offer on every producer process, one pull
                # assignment per consumer process; nbytes from pool metadata
                # only — the page gather runs inside kv_offer_page AFTER the
                # consumer accepts, so refusals cost no device work. A
                # refused/failed page falls through to the TCP blob push.
                kp = self.runner.k_pages
                page_nbytes = 2 * (kp.nbytes // kp.shape[1])
                if self._kv_sender.push_device_multihost(key, page_nbytes, pid):
                    continue
            blob = None
            if self._offload is not None:
                blob = self._offload.store.get(key)
            if blob is None:
                if self.kv_quant:
                    # quantized pool: ship the exact pool bytes + scales
                    # (serde v3); the raw get_page path has no scales
                    from production_stack_tpu.kvoffload.serde import Int8PageSerde

                    ks, vs, sks, svs = self.runner.get_pages_quant([pid])
                    blob = Int8PageSerde().serialize_quant(
                        np.asarray(ks[0]), np.asarray(sks[0]),
                        np.asarray(vs[0]), np.asarray(svs[0]),
                    )
                else:
                    k, v = self.runner.get_page(pid)
                    serde = (
                        self._offload.serde
                        if self._offload is not None
                        else self._default_serde()
                    )
                    blob = serde.serialize(np.asarray(k), np.asarray(v))
            self._kv_sender.push(key, blob)

    def _default_serde(self):
        from production_stack_tpu.kvoffload.serde import get_serde

        return get_serde(self.cfg.kv_serde)

    # -- KV fabric plumbing ---------------------------------------------------

    def _fabric_gather(self, keys: "list[str]"):
        """Gather resident pages for hex ``keys`` off the device pool.
        Returns (found_keys, ks, vs, sks, svs) with host arrays; sks/svs are
        None on fp engines. MUST run on the device thread (replicated
        runner-dispatch discipline)."""
        found, pids = [], []
        for key in keys:
            try:
                pid = self.kv.hash_to_page.get(bytes.fromhex(key))
            except ValueError:
                pid = None
            if pid is not None:
                found.append(key)
                pids.append(pid)
        if not pids:
            return [], [], [], None, None
        if self.kv_quant:
            ks, vs, sks, svs = self.runner.get_pages_quant(pids)
            sks = [np.asarray(s) for s in sks]
            svs = [np.asarray(s) for s in svs]
        else:
            ks, vs = self.runner.get_pages(pids)
            sks = svs = None
        return (
            found,
            [np.asarray(k) for k in ks],
            [np.asarray(v) for v in vs],
            sks,
            svs,
        )

    def _fabric_pages(self, keys: "list[str]"):
        """Fabric listener pull handler: resident pages for ``keys`` as one
        encoded wire frame. Called on the listener's worker thread; the pool
        gather is marshalled onto the device thread."""
        from production_stack_tpu.kvfabric import wire as fabric_wire

        found, ks, vs, sks, svs = self._run_on_device_thread(
            lambda: self._fabric_gather(keys)
        )
        if not found:
            return [], b""
        frame = fabric_wire.encode_frame(
            found, ks, vs, sks, svs, nlayers=int(ks[0].shape[0])
        )
        return found, frame

    def _fabric_sink(self, frame: dict) -> int:
        """Fabric push handler: assemble layer windows into whole pages and
        land them as LOCAL tier blobs, where the ordinary admission/restore
        path (and migration's prefetch walk) finds them — zero shared-tier
        I/O. Quant frames keep their scales verbatim (serde v3 blob); the
        serde cross-dtype contract covers fp<->int8 engine pairs at restore
        time."""
        if self._offload is None:
            return 0
        from production_stack_tpu.kvoffload.serde import Int8PageSerde

        stored = 0
        for key, (k, v, sk, sv) in self._fabric_asm.add(frame):
            if sk is not None:
                blob = Int8PageSerde().serialize_quant(k, sk, v, sv)
            else:
                blob = self._offload.serde.serialize(k, v)
            self._offload.store.put_local(key, blob)
            stored += 1
        return stored

    def _resolve_fabric_peer(self) -> Optional[str]:
        """Fabric listener address of the disagg decode peer.
        ``--kv-fabric-peer`` is either the address itself ("host:port") or
        the peer's HTTP URL — then GET /kv_fabric resolves the advertised
        listener (the peer may bind an ephemeral port). Cached; cleared
        after a fabric failure so the next push re-resolves."""
        if self._fabric_peer_addr is not None:
            return self._fabric_peer_addr
        target = self.cfg.kv_fabric_peer
        if not target:
            return None
        addr = target
        if target.startswith("http"):
            try:
                import json as json_mod
                import urllib.request

                with urllib.request.urlopen(
                    target.rstrip("/") + "/kv_fabric", timeout=5
                ) as r:
                    info = json_mod.loads(r.read())
                addr = info.get("addr") if info.get("enabled", True) else None
            except Exception as e:  # noqa: BLE001 - fabric is optional
                logger.warning("fabric peer resolve failed for %s: %s", target, e)
                addr = None
        self._fabric_peer_addr = addr
        return addr

    def _fabric_stream_push(self, hashes: list) -> list:
        """Streamed disagg prefill: ship a finished prefill's page chain to
        the decode peer as layer-windowed (pages, scales) frames
        (``--kv-fabric-stream-layers`` layers per frame), so the consumer
        starts landing pages before the last layer arrives — this replaces
        the shared-tier re-acquire of phase 1. Returns the hashes NOT
        covered (no peer, gather/push failure): the caller's TCP-blob path
        is the per-page fallback, counted on kv_fabric_fallbacks_total."""
        addr = self._resolve_fabric_peer()
        if addr is None:
            return hashes
        from production_stack_tpu.kvfabric import wire as fabric_wire

        try:
            found, ks, vs, sks, svs = self._fabric_gather(
                [h.hex() for h in hashes]
            )
        except Exception as e:  # noqa: BLE001 - fall back to TCP blobs
            logger.warning("fabric page gather failed: %s", e)
            self._fabric_client.count_fallback(len(hashes))
            return hashes
        if not found:
            return []
        nlayers = int(ks[0].shape[0])
        win = self.cfg.kv_fabric_stream_layers or nlayers
        ok = True
        for lo in range(0, nlayers, win):
            hi = min(lo + win, nlayers)
            frame = fabric_wire.encode_frame(
                found,
                [k[lo:hi] for k in ks],
                [v[lo:hi] for v in vs],
                [s[lo:hi] for s in sks] if sks is not None else None,
                [s[lo:hi] for s in svs] if svs is not None else None,
                layers=(lo, hi),
                nlayers=nlayers,
            )
            if not self._fabric_client.push(addr, frame):
                ok = False
                break
        if ok:
            return []
        # mid-stream failure: drop the cached peer (it may have restarted
        # on a new port) and let the TCP path re-ship the whole chain; the
        # consumer's assembler bounds any partial windows we left behind
        self._fabric_peer_addr = None
        self._fabric_client.count_fallback(len(found))
        return hashes

    def fabric_ship_pairs(
        self, addr: str, pairs: "list[tuple[int, str]]"
    ) -> "list[str]":
        """Ship explicit ``(pid, key_hex)`` pages to ``addr`` over the
        fabric — migration's freeze->ship path, where a frozen sequence's
        pages are not yet registered in hash_to_page (registration happens
        at finish). Returns the keys actually shipped. Safe from any
        thread: the gather marshals onto the device thread, and
        _run_on_device_thread is re-entrant for callers already on it (the
        freeze path)."""
        if self._fabric_client is None or not pairs:
            return []
        from production_stack_tpu.kvfabric import wire as fabric_wire

        def gather():
            pids = [p for p, _ in pairs]
            if self.kv_quant:
                ks, vs, sks, svs = self.runner.get_pages_quant(pids)
                sks = [np.asarray(s) for s in sks]
                svs = [np.asarray(s) for s in svs]
            else:
                ks, vs = self.runner.get_pages(pids)
                sks = svs = None
            return (
                [np.asarray(k) for k in ks],
                [np.asarray(v) for v in vs],
                sks,
                svs,
            )

        try:
            ks, vs, sks, svs = self._run_on_device_thread(gather)
        except Exception as e:  # noqa: BLE001 - tier save is the fallback
            logger.warning("fabric migration gather failed: %s", e)
            self._fabric_client.count_fallback(len(pairs))
            return []
        keys = [k for _, k in pairs]
        frame = fabric_wire.encode_frame(
            keys, ks, vs, sks, svs, nlayers=int(ks[0].shape[0])
        )
        if self._fabric_client.push(addr, frame):
            return keys
        self._fabric_client.count_fallback(len(pairs))
        return []

    def _process_token(
        self, seq: Sequence, new_tokens: list[int], logprobs: Optional[list] = None
    ) -> None:
        """Detokenize incrementally, check stop strings, emit the delta (with
        this step's new tokens — one or a whole decode burst; ``logprobs``
        aligns 1:1 with ``new_tokens`` when requested)."""
        raw = full = self.tokenizer.decode(seq.output_ids)
        if not seq.finished and full.endswith("�"):
            # hold back a trailing incomplete byte sequence (renders as
            # replacement chars) until later tokens complete it — emitting it
            # now would desync the incremental stream, and the emit boundaries
            # (per-token, burst, or speculative round) must not change the
            # streamed text. Held-back chars flush on the finishing emit.
            full = full.rstrip("�")
        if not seq.finished and seq.params.stop:
            # hold back a trailing PARTIAL stop-string match until later
            # tokens resolve it: a decode_steps=1 engine otherwise streams
            # the stop's first chars one token at a time (they cannot be
            # retracted once emitted), while a burst engine sees the whole
            # stop inside one dispatch and trims before it — the emitted
            # text must not depend on the dispatch boundary. A completed
            # stop is handled by the trim below; non-stop text flushes on
            # the finishing emit (gate above), exactly like the byte hold.
            hold = 0
            for s in seq.params.stop:
                for j in range(min(len(s) - 1, len(full)), hold, -1):
                    if full.endswith(s[:j]):
                        hold = j
                        break
            if hold:
                full = full[: len(full) - hold]
        # under _lock: generate()'s finally pops this entry from the event
        # loop concurrently (unlocked read found by graftcheck GC004)
        with self._lock:
            prev = self._texts.get(seq.seq_id, "")
        delta = full[len(prev):] if full.startswith(prev) else full
        if seq.params.stop and any(s in raw for s in seq.params.stop):
            # Stop detection must not depend on emission boundaries (per-token
            # vs burst vs chained bursts give the same stream): scan this
            # step's token prefixes and stop at the FIRST prefix whose decode
            # contains a stop string — exactly where a decode_steps=1 engine
            # detects it. The prefix scan is O(burst * output length)
            # detokenization, so it only runs once the full decode contains a
            # stop (a stop visible at some prefix is made of complete chars
            # and stays visible in the full text).
            base = len(seq.output_ids) - len(new_tokens)
            hit = None  # (keep, text_at_keep, stop_index)
            for m in range(1, len(new_tokens) + 1):
                txt = self.tokenizer.decode(seq.output_ids[: base + m])
                for stop in seq.params.stop:
                    idx = txt.find(stop)
                    if idx >= 0:
                        hit = (m, txt, idx)
                        break
                if hit:
                    break
            if hit:
                keep, txt, idx = hit
                delta = txt[len(prev): idx] if txt.startswith(prev) else txt[:idx]
                del seq.output_ids[base + keep:]
                # the loop already counted the whole burst
                self.total_generation_tokens -= len(new_tokens) - keep
                new_tokens = new_tokens[:keep]
                if logprobs is not None:
                    logprobs = logprobs[:keep]
                if not seq.finished:
                    self.scheduler._finish(seq, "stop")
                elif seq.finish_reason == "length":
                    # the length cap landed in the same step the stop text
                    # appeared; the emitted text ends at the stop, so report it
                    seq.finish_reason = "stop"
        with self._lock:
            # presence-gated: generate()'s finally may have popped the entry
            # since the read above (client abandoned the stream) — an
            # unconditional write would RESURRECT it, and with the only
            # removal site already run, leak the full text forever
            if seq.seq_id in self._texts:
                self._texts[seq.seq_id] = prev + delta
        self._emit(seq, delta, tokens=new_tokens, logprobs=logprobs)

    def _record_phase_trace(self, seq: Sequence) -> None:
        """Record the per-phase spans and histograms for a finished sequence.

        Phase boundaries come from timestamps the scheduler already keeps
        (arrival, first prefill dispatch, first token, finish), so this runs
        once per request at finish — zero cost on the step path. Histograms
        are always-on (they back the dashboard's phase panels); spans only
        when the request carries a sampled trace context."""
        seq.trace_done = True
        now_m = time.monotonic()
        anchor = time.time() - now_m  # monotonic -> wall clock
        end = seq.finish_time or now_m
        fd = seq.first_dispatch_time
        ft = seq.first_token_time
        queue_s = max(0.0, (fd if fd is not None else end) - seq.arrival_time)
        prefill_s = max(0.0, (ft - fd)) if fd is not None and ft is not None else 0.0
        decode_s = max(0.0, (end - ft)) if ft is not None else 0.0
        steps = len(seq.output_ids)
        tracing.queue_time_hist.observe(queue_s)
        if fd is not None and ft is not None:
            tracing.prefill_time_hist.observe(prefill_s)
        if ft is not None and steps > 1:
            tracing.decode_step_time_hist.observe(decode_s / (steps - 1))
        tr = seq.trace
        if tr is None or not getattr(tr, "sampled", False):
            return
        col = tracing.get_collector()
        # the scheduler pre-allocated the phase-span contexts at admission so
        # offload spill/restore spans could nest under the phase whose wall
        # window contains them; record the phases under those same contexts
        col.record(
            "engine.queue", seq.queue_span or tr.child(),
            anchor + seq.arrival_time, queue_s, seq_id=seq.seq_id,
        )
        if fd is not None and ft is not None:
            col.record(
                "engine.prefill", seq.prefill_span or tr.child(),
                anchor + fd, prefill_s,
                seq_id=seq.seq_id, prompt_tokens=len(seq.prompt_ids),
                cached_tokens=seq.num_cached,
            )
        if ft is not None:
            attrs = {
                "seq_id": seq.seq_id,
                "output_tokens": steps,
                "finish_reason": seq.finish_reason,
            }
            if steps > 1:
                attrs["per_token_ms"] = round(decode_s / (steps - 1) * 1000, 3)
            if seq.lora_slot:
                # LoRA sub-phase marker: which adapter slot served the decode
                attrs["lora_slot"] = seq.lora_slot
            if self.cfg.speculative_k:
                attrs["spec_k"] = self.cfg.speculative_k
            col.record(
                "engine.decode", seq.decode_span or tr.child(),
                anchor + ft, decode_s, **attrs,
            )

    def _record_slo(self, seq: Sequence, error: bool = False) -> None:
        """Attribute the finished sequence its SLO terminal record: queue
        wait, TTFT, token counts, inter-token p99, peak KV footprint, and the
        terminal outcome. Appended to the bounded ``slo_records`` log the
        router scrapes (GET /slo_records) and mirrored as a flight-recorder
        event so anomaly dumps carry the requests that were in flight."""
        seq.slo_done = True
        end = seq.finish_time or time.monotonic()
        fd, ft = seq.first_dispatch_time, seq.first_token_time
        reason = "error" if error else (seq.finish_reason or "error")
        outcome = (
            "ok" if reason in ("stop", "length", "tool_calls") else reason
        )
        itl_p99_ms = None
        if seq.itl_samples:
            s = sorted(seq.itl_samples)
            itl_p99_ms = round(
                s[min(len(s) - 1, int(len(s) * 0.99))] * 1000, 3
            )
        ttft_ms = (
            round((ft - seq.arrival_time) * 1000, 3) if ft is not None else None
        )
        rec = {
            "seq": next(self._slo_seq),
            "request_id": seq.seq_id,
            "model": self.cfg.name,
            "outcome": outcome,
            "finish_reason": reason,
            "queue_ms": round(((fd if fd is not None else end)
                               - seq.arrival_time) * 1000, 3),
            "ttft_ms": ttft_ms,
            "e2e_ms": round((end - seq.arrival_time) * 1000, 3),
            "prompt_tokens": len(seq.prompt_ids),
            "output_tokens": len(seq.output_ids),
            "cached_tokens": seq.num_cached,
            "itl_p99_ms": itl_p99_ms,
            "kv_pages_peak": seq.pages_peak,
            "trace_id": getattr(seq.trace, "trace_id", None),
            "priority": getattr(seq, "priority", "interactive"),
            "t": time.time(),
        }
        self.slo_records.append(rec)
        if outcome == "ok" and rec["priority"] == "interactive":
            if ttft_ms is not None:
                self._interactive_ttft_ms.append(ttft_ms)
            if itl_p99_ms is not None:
                self._interactive_itl_ms.append(itl_p99_ms)
        fr = self._fr
        if fr.enabled:
            fr.record(
                "slo", step=self.step_idx, trace_id=rec["trace_id"],
                request_id=seq.seq_id, outcome=outcome, ttft_ms=ttft_ms,
                itl_p99_ms=itl_p99_ms, output_tokens=rec["output_tokens"],
            )
            watermark = self.cfg.flight_recorder_ttft_watermark_ms
            if watermark > 0 and ttft_ms is not None and ttft_ms > watermark:
                fr.dump_async("ttft_breach")  # off the device thread

    def _emit(
        self,
        seq: Sequence,
        delta: str,
        tokens: Optional[list[int]] = None,
        error: bool = False,
        logprobs: Optional[list] = None,
    ) -> None:
        if tokens:
            # inter-token latency accounting for the SLO terminal record: a
            # burst emit of k tokens contributes its gap/k, so the p99 below
            # approximates what a streaming client measures. Capped — a long
            # stream must not grow an unbounded list (the p99 of the first
            # 4096 emits is representative; steady-state decode is stationary)
            now_m = time.monotonic()
            if seq.last_emit_time is not None and len(seq.itl_samples) < 4096:
                seq.itl_samples.append(
                    (now_m - seq.last_emit_time) / len(tokens)
                )
            seq.last_emit_time = now_m
        if seq.finished and not seq.trace_done:
            try:
                self._record_phase_trace(seq)
            except Exception:  # noqa: BLE001 - tracing must never break serving
                logger.exception("phase trace recording failed")
        if seq.finished and not seq.slo_done:
            try:
                self._record_slo(seq, error=error)
            except Exception:  # noqa: BLE001 - accounting must never break serving
                logger.exception("SLO terminal record failed")
        with self._lock:
            entry = self._outputs.get(seq.seq_id)
        if entry is None:
            return
        loop, out_q = entry
        out = RequestOutput(
            seq_id=seq.seq_id,
            text_delta=delta,
            token_ids=(
                tokens
                if tokens is not None
                else [seq.output_ids[-1]] if seq.output_ids else []
            ),
            finished=seq.finished,
            finish_reason=("error" if error else seq.finish_reason) if seq.finished else None,
            prompt_tokens=len(seq.prompt_ids),
            completion_tokens=len(seq.output_ids),
            cached_tokens=seq.num_cached,
            logprobs=logprobs,
        )
        loop.call_soon_threadsafe(out_q.put_nowait, out)

    # -- sleep / wake (engine contract: /sleep /wake_up /is_sleeping) -------

    def _lora_cmd(self, op: str, name: str, path: Optional[str] = None):
        """Run a LoRA load/unload. Device-buffer writes must not race the step
        loop (the slot update donates the live buffers), so when the engine
        loop is running the command is executed *by the device thread* between
        steps; otherwise it runs inline."""
        if self.lora is None:
            raise ValueError("LoRA is not enabled (--enable-lora)")

        if op == "load":
            # cheap prechecks before the (possibly large) checkpoint read;
            # load_parsed re-checks authoritatively under the manager lock
            from production_stack_tpu.engine.lora import LoRAError

            if self.lora.is_adapter(name):
                raise LoRAError(f"adapter {name!r} is already loaded")
            if not self.lora.has_free_slot():
                raise LoRAError(f"no free LoRA slots (max_loras={self.cfg.max_loras})")
            # parse on the caller thread: no disk I/O on the device thread
            tensors, scale = self.lora.read_checkpoint(path)

            def run():
                return self.lora.load_parsed(name, tensors, scale)
        else:
            def run():
                slot = self.lora.slot_for(name)  # 0 when not loaded
                in_use = slot != 0 and any(
                    s.lora_slot == slot
                    for s in self.scheduler.waiting + self.scheduler.running
                    if not s.finished
                )
                return self.lora.unload(name, in_use=in_use)

        return self._run_on_device_thread(run, what=f"LoRA {op} of {name!r}")

    def _run_on_device_thread(self, fn, what: str = "device command"):
        """Execute `fn` on the engine-loop thread between steps (device-state
        mutations and extra forwards must not race the step loop). Runs inline
        when the loop is not running."""
        if self._thread is None or not self._thread.is_alive():
            return fn()
        done = threading.Event()
        box: dict = {}

        def cmd():
            try:
                box["result"] = fn()
            except BaseException as e:  # surfaced on the caller thread
                box["error"] = e
            finally:
                done.set()

        self._inbox.put(("device_cmd", cmd))
        if not done.wait(timeout=120):
            raise TimeoutError(f"{what} timed out")
        if "error" in box:
            raise box["error"]
        return box.get("result")

    def load_lora_adapter(self, name: str, path: str) -> int:
        """Load a PEFT adapter; served under model name `name`.
        Contract parity: POST /v1/load_lora_adapter driven by the reference's
        LoraAdapter controller (loraadapter_controller.go:586-616)."""
        return self._lora_cmd("load", name, path)

    def unload_lora_adapter(self, name: str) -> None:
        """Unload an adapter. Refuses while requests using it are in flight
        (the controller retries), so a slot can never be re-targeted under a
        running sequence."""
        self._lora_cmd("unload", name)

    def list_lora_adapters(self) -> list[str]:
        return self.lora.list_adapters() if self.lora is not None else []

    _EMBED_T_BUCKETS = (16, 32, 64, 128, 256, 512, 1024, 2048, 4096)
    _EMBED_B_BUCKETS = (1, 2, 4, 8, 16, 32)

    async def embed(self, token_id_lists: list[list[int]]) -> np.ndarray:
        """Pooled unit-norm embeddings for a batch of tokenized inputs
        ([N, hidden_size] float32). Serves /v1/embeddings, /v1/rerank,
        /v1/score. Runs on the device thread, bucketed like generation."""
        if self._sleeping:
            raise RuntimeError("engine is sleeping")
        # capability check BEFORE the runner call: in multi-host mode every
        # runner.encode is broadcast to followers first, and a validation
        # error after broadcast desyncs the set (the wrapper treats it as
        # fatal) — a client request must never reach that path
        if not hasattr(self.runner.module, "encode"):
            raise ValueError(
                f"embeddings are not supported for model family "
                f"{self.runner.module.__name__.rsplit('.', 1)[-1]!r}"
            )
        for ids in token_id_lists:
            if len(ids) > self.cfg.max_model_len:
                raise ValueError(
                    f"input has {len(ids)} tokens, max_model_len is "
                    f"{self.cfg.max_model_len}"
                )

        def bucket(n, buckets):
            for b in buckets:
                if n <= b:
                    return b
            return buckets[-1]

        out = np.zeros((len(token_id_lists), self.model_cfg.hidden_size), np.float32)
        loop = asyncio.get_running_loop()
        # one device pass per B-bucket group of similar lengths
        order = sorted(range(len(token_id_lists)), key=lambda i: len(token_id_lists[i]))
        pos = 0
        while pos < len(order):
            group = order[pos : pos + self._EMBED_B_BUCKETS[-1]]
            pos += len(group)
            B = bucket(len(group), self._EMBED_B_BUCKETS)
            t_raw = max(max(len(token_id_lists[i]) for i in group), 1)
            T = bucket(t_raw, self._EMBED_T_BUCKETS)
            if T < t_raw:  # longer than the largest preset bucket: next pow2
                T = 1 << (t_raw - 1).bit_length()
            input_ids = np.zeros((B, T), np.int32)
            positions = np.full((B, T), -1, np.int32)
            for row, i in enumerate(group):
                ids = token_id_lists[i]
                input_ids[row, : len(ids)] = ids
                positions[row, : len(ids)] = np.arange(len(ids))
            def encode_cmd(input_ids=input_ids, positions=positions):
                if self._sleeping:  # may have gone to sleep since the check above
                    raise RuntimeError("engine is sleeping")
                return self.runner.encode(input_ids, positions)

            vecs = await loop.run_in_executor(
                None,
                lambda: np.asarray(
                    self._run_on_device_thread(encode_cmd, what="embedding forward")
                ),
            )
            for row, i in enumerate(group):
                out[i] = vecs[row]
            with self._lock:
                self.total_prompt_tokens += sum(
                    len(token_id_lists[i]) for i in group
                )
        return out

    def warm_spill(self) -> int:
        """Final warm-start manifest spill (SIGTERM drain path — the API
        server calls this after in-flight requests finish, before teardown).
        Runs on the device thread so the page fetches serialize with any
        still-running steps. No-op without --warm-start."""
        if self.warm is None:
            return 0
        try:
            return int(
                self._run_on_device_thread(
                    lambda: self.warm.spill("drain"), what="warm-start spill"
                ) or 0
            )
        except Exception:  # noqa: BLE001 - shutdown must not hang on a spill
            logger.exception("warm-start drain spill failed")
            return 0

    def sleep(self, level: int = 1) -> None:
        """Free HBM without killing the process. Level 1 drops the KV pools;
        level 2 additionally moves weights to host DRAM (SURVEY.md §7 hard
        part #5). Runs on the device thread, serialized with steps."""
        if self._sleeping:
            return

        def do_sleep():
            if self._sleeping:
                return  # raced with a concurrent sleep (handlers run on
                        # executor threads; only the device thread is serial)
            self._sleeping = True
            self._sleep_level = level
            for s in list(self.scheduler.running) + list(self.scheduler.waiting):
                self.scheduler._finish(s, "abort")
                self._emit(s, "")
            if self._kvdir_pub is not None and self.kv.hash_to_page:
                # dropping the pools invalidates every resident claim this
                # engine advertised; withdraw them or KV-aware v2 routers
                # keep resident-routing prompts at a cold sleeper (the idle
                # heartbeat would keep the stale claims alive forever).
                # Shared-tier claims stay — the blobs outlive the pools.
                self._kvdir_pub.withdraw(
                    list(self.kv.hash_to_page.keys()), "resident"
                )
            # replicated in multi-host: followers drop their pool shards too
            self.runner.drop_kv_pools()
            if level >= 2:
                # REPLICATED: every process offloads its own param shards to
                # its own host RAM, so level 2 works multi-host too
                self.runner.offload_params()
            import gc

            gc.collect()

        self._run_on_device_thread(do_sleep, what="sleep")

    def wake_up(self) -> None:
        if not self._sleeping:
            return

        def do_wake():
            if not self._sleeping:
                return  # raced with a concurrent wake
            if self._sleep_level >= 2:
                # REPLICATED: each process re-materializes its shards from
                # its own host copy (offload_params saved them)
                self.runner.restore_params()
            self.runner.reset_kv()  # replicated in multi-host
            self.kv = KVPageManager(
                self.kv.num_pages, self.kv.page_size, offload=self._offload,
                max_io_pages=self._max_io_pages,
                spill_watermark=self.cfg.kv_spill_watermark,
            )
            self.kv.directory = self._kvdir_pub  # keep fleet publishes alive
            if self._kvdir_pull is not None:
                self._kvdir_pull.kv = self.kv
            self.scheduler.kv = self.kv
            self._sleeping = False

        self._run_on_device_thread(do_wake, what="wake_up")

    @property
    def is_sleeping(self) -> bool:
        return self._sleeping

    # -- stats --------------------------------------------------------------

    def stats(self) -> dict:
        out = {
            "num_requests_running": self.scheduler.num_running(),
            "num_requests_waiting": self.scheduler.num_waiting(),
            "num_requests_swapped": self.scheduler.num_swapped(),
            "num_preemptions_total": self.scheduler.preemptions_total,
            "num_requests_shed_total": (
                sum(self.requests_shed.values()) + self.api_requests_shed
            ),
            "num_requests_shed_queue_full_total": (
                self.requests_shed["queue_full"] + self.api_requests_shed
            ),
            "num_requests_shed_queue_deadline_total": (
                self.requests_shed["queue_deadline"]
            ),
            # per-SLO-class shed counters (device-thread + event-loop writer
            # pairs summed, like num_requests_shed_total above)
            "num_requests_shed_interactive_total": (
                self.requests_shed_by_class["interactive"]
                + self.api_requests_shed_by_class["interactive"]
            ),
            "num_requests_shed_batch_total": (
                self.requests_shed_by_class["batch"]
                + self.api_requests_shed_by_class["batch"]
            ),
            "engine_saturated": int(self.saturated()),
            # batch-class saturation engages interactive_reserve slots early
            # — 1 here with engine_saturated 0 is the reserve protecting
            # interactive admission while batch already sheds
            "engine_saturated_batch": int(self.saturated("batch")),
            # serving-mesh shape: the router's scraper and the fleet
            # controller read these to reason about per-engine capacity (a
            # tp=4 engine is one replica on 4 chips, not 4 replicas)
            "tensor_parallel": self.tensor_parallel,
            "mesh_devices": self.mesh_devices,
            # the device the engine actually runs on, as JAX reports it, and
            # what attn_impl resolved to (runner.resolve_attn_impl) with the
            # reason whenever a kernel is not selected — strings, so the
            # /metrics sweep skips them; device_count is the process-wide
            # jax.devices() count (a tp=1 engine still claims every chip it
            # can see)
            **self.device_info,
            "attn_impl_requested": self.runner.attn.requested,
            "attn_impl_prefill": self.runner.attn.prefill,
            "attn_impl_decode": self.runner.attn.decode,
            "attn_impl_reason": self.runner.attn.reason,
            "engine_step_errors_total": self.step_errors_total,
            "engine_program_fault": self.program_fault or "",
            # KV quantization surface (docs/benchmarking.md byte-wall
            # model): pool bytes per token, quantized page count (= whole
            # pool when int8, 0 otherwise), and the startup dequant
            # round-trip error bound. cache_dtype is the string form for
            # GET /stats (non-numeric, so the /metrics kv_* sweep skips it)
            "cache_dtype": self.cfg.kv_cache_dtype,
            "kv_cache_dtype_bytes_per_token": round(self.kv_bytes_per_token, 3),
            "kv_quant_pages": self.kv.num_pages if self.kv_quant else 0,
            "kv_quant_dequant_err_max": round(self.kv_quant_dequant_err_max, 6),
            "gpu_cache_usage_perc": self.kv.usage(),
            "gpu_prefix_cache_hits_total": self.kv.prefix_hits,
            "gpu_prefix_cache_queries_total": self.kv.prefix_queries,
            "gpu_prefix_cache_hit_rate": self.kv.hit_rate(),
            "prompt_tokens_total": self.total_prompt_tokens,
            "generation_tokens_total": self.total_generation_tokens,
            "decode_dispatches_total": self.decode_dispatches_total,
            "decode_chained_dispatches_total": self.decode_chained_dispatches_total,
            "runahead_prefill_dispatches_total": (
                self.runahead_prefill_dispatches_total
            ),
            "decode_kv_tokens_read_total": self.decode_kv_tokens_read_total,
            # dispatches enqueued behind a running one, by kind, and those
            # that found the device idle, by what had emptied the loop
            "queued_ahead_dispatches_total": dict(self.queued_ahead_dispatches),
            "queue_ahead_drains_total": dict(self.queue_ahead_drains),
            # decode rows that take one step inside a prefill dispatch
            # (scheduler._plan_riders): engagement is the dispatches that
            # carried riders over those and the ones that had decode demand
            # and carried none (by reason); ``rider_refusal``: why this
            # engine's prefill programs have no slot at all ("": they have)
            "rider_refusal": self.scheduler.rider_refusal or "",
            "prefill_dispatches_total": self.scheduler.prefill_dispatches_total,
            "prefill_rider_dispatches_total": (
                self.scheduler.prefill_rider_dispatches_total
            ),
            "prefill_rider_rows_total": self.scheduler.prefill_rider_rows_total,
            "prefill_riderless_dispatches_total": dict(
                self.scheduler.prefill_riderless_dispatches
            ),
        }
        if self.state_family:
            # the second kind of state (models/jamba.py): slots of the
            # recurrent-state pool, the scan's work, the implementation the
            # platform resolved to, and what this family switches off or
            # refuses to start with, each with its reason
            # seats and bytes from the one place the start-up line reads
            # (runner.state_report); the page manager deals the same seats
            out.update(self.runner.state_report())
            assert out["ssm_state_slots"] == self.kv.state_slots
            out["ssm_state_slots_in_use"] = self.kv.slots_in_use()
            out["conv_state_bytes"] = self.runner.conv_state_bytes
            out["ssm_prefill_tokens_total"] = self.ssm_prefill_tokens_total
            out["ssm_decode_tokens_total"] = self.ssm_decode_tokens_total
            out["ssm_kernel"] = self.runner.ssm_impl
            out["ssm_kernel_reason"] = self.runner.ssm_reason
            out["state_family_off"] = dict(self.state_family_off)
            out["state_family_refusals"] = dict(self.state_family_refusals)
        if self.runner.num_counters:
            # counted by the device, named by the family (models/lfm2.py:
            # moe_routed_rows_total, moe_expert_reads_total,
            # moe_expert_slots_total, moe_expert_rows)
            self._count_device_work()
            out.update(self.runner.module.counter_stats(
                self.model_cfg, self.step_counter_totals
            ))
        # first dispatches of step-program shapes (runner._dispatch): how
        # many, their wall seconds, and the split trace / lower / compile
        # (or cache load) / run (first execution and the rest)
        fd = self.runner.first_dispatch
        out["first_dispatches_total"] = fd["count"]
        out["first_dispatch_seconds_total"] = round(fd["seconds"], 4)
        for phase in ("trace", "lower", "compile", "run"):
            out[f"first_dispatch_{phase}_seconds_total"] = round(fd[phase], 4)
        # how often those first dispatches found their exported program
        # beside the compile cache, wrote it, or could not use the store
        # (step_program_store_bypassed: program -> why it runs its plain jit)
        out.update(store_stats(self.runner.step_store))
        # and what the loader built of the store's listing as the process
        # started (step_programs.Preloader): a first dispatch that takes one
        # of those reads store "preloaded" and costs its run alone
        out.update(self.runner.preloaded.stats())
        # per decode (batch x pages) bucket dispatched: the block of pages and
        # the ring depth the kernel's derivation chose (a dict, so /metrics,
        # which names its keys, leaves it to GET /stats)
        out["decode_kernel_blocks"] = dict(self.runner.decode_blocks)
        for section, secs in self.loop_seconds.items():
            # stage, call, fetch, hold and runahead are parts of step that the
            # loop did not separate before: under a prefix of their own, so that a
            # reader summing engine_loop_* reads what it always did
            prefix = (
                "engine_dispatch"
                if section in ("stage", "call", "fetch", "hold", "runahead")
                else "engine_loop"
            )
            out[f"{prefix}_{section}_seconds_total"] = round(secs, 3)
        # interactive-SLO degradation signal for the fleet controller's
        # latency-protection policy (migration/controller.py): p99 over the
        # recent interactive ok-request window, 0.0 while idle
        for name, window in (
            ("interactive_ttft_p99_ms", self._interactive_ttft_ms),
            ("interactive_itl_p99_ms", self._interactive_itl_ms),
        ):
            snap = sorted(window)
            out[name] = (
                round(snap[min(len(snap) - 1, int(len(snap) * 0.99))], 3)
                if snap else 0.0
            )
        if self.cfg.speculative_k:
            # read accepted before drafts: the engine thread increments drafts
            # first, so this order keeps any unsynchronized snapshot at
            # accepted <= drafts (acceptance rate never exceeds 1.0)
            accepted = self.spec_accepted_tokens
            drafts = self.spec_draft_tokens
            out["spec_decode_num_draft_tokens_total"] = drafts
            out["spec_decode_num_accepted_tokens_total"] = accepted
            out["spec_decode_draft_acceptance_rate"] = (
                accepted / drafts if drafts else 0.0
            )
        if self._kv_sender is not None:
            out["kv_transfer_sent_chunks_total"] = self._kv_sender.sent_chunks
            out["kv_transfer_sent_bytes_total"] = self._kv_sender.sent_bytes
            out["kv_transfer_device_pages_total"] = self._kv_sender.device_pages
        if self._kv_receiver is not None:
            out["kv_transfer_received_chunks_total"] = self._kv_receiver.received_chunks
            out["kv_transfer_received_bytes_total"] = self._kv_receiver.received_bytes
            out["kv_transfer_device_pages_total"] = self._kv_receiver.device_pages
        if self._offload is not None and self._offload.device_staging is not None:
            out["kv_offload_device_loaded_pages_total"] = (
                self._offload.device_loaded_pages
            )
        ep = getattr(self.runner, "kv_endpoint", None)
        if ep is not None:
            # offer-retirement observability (transfer.py sweep): pinned HBM
            # and the upper bound on unpulled-offer leaks
            out["kv_transfer_pinned_offer_bytes"] = ep.pinned_offer_bytes()
            out["kv_transfer_leaked_offers_total"] = ep.leaked_offers
            out["kv_transfer_cap_evicted_offers_total"] = ep.cap_evicted_offers
        # eviction-policy observability (hot-prefix protection): total page
        # evictions, evictions that hit a page with a nonzero reuse count
        # (hot-set casualties — the "protected-page evictions" panel), and
        # pages spilled ahead of eviction by the high-watermark path
        out["kv_evicted_pages_total"] = self.kv.evicted_pages_total
        out["kv_evicted_hot_pages_total"] = self.kv.evicted_hot_pages_total
        out["kv_proactive_spilled_pages_total"] = (
            self.kv.proactive_spilled_pages_total
        )
        if self._offload is not None:
            o = self._offload.stats()
            out["kv_offload_hit_pages_total"] = self.kv.offload_hits
            out["kv_offload_saved_pages_total"] = o["saved_pages"]
            out["kv_offload_loaded_pages_total"] = o["loaded_pages"]
            out["kv_offload_cpu_bytes"] = o["cpu_bytes"]
            out["kv_offload_disk_bytes"] = o["disk_bytes"]
            # offload-tier integrity: blobs that failed their checksum on
            # read and were quarantined (never served) — local tiers plus,
            # on a disagg consumer, pushes rejected at the receiver
            corrupt = o.get("corrupt_pages", 0)
            if self._kv_receiver is not None:
                corrupt += getattr(self._kv_receiver, "corrupt_chunks", 0)
            out["kv_corrupt_pages_total"] = corrupt
            # permanent KV loss at the bottom local tier (satellite: was a
            # silent drop) — nonzero means blobs left the hierarchy entirely
            out["kv_offload_dropped_evictions_total"] = o.get(
                "dropped_evictions", 0
            )
            # offload I/O budget provenance: the active cap and, when the
            # startup probe chose it, the measured link bandwidth
            out["kv_offload_max_io_pages"] = self.kv.max_io_pages
            if self.kv_link_bandwidth_bytes_per_s is not None:
                out["kv_offload_link_bandwidth_bytes_per_sec"] = round(
                    self.kv_link_bandwidth_bytes_per_s
                )
        if self._kvdir_pub is not None:
            # fleet-directory surface (docs/kv-directory.md): publish-side
            p = self._kvdir_pub.stats()
            out["kv_directory_publishes_total"] = p["kv_directory_publishes_total"]
            out["kv_directory_withdrawals_total"] = (
                p["kv_directory_withdrawals_total"]
            )
            out["kv_directory_flush_errors_total"] = (
                p["kv_directory_flush_errors_total"]
            )
        if self.cfg.warm_prefetch_on_boot > 0:
            # scale-up warm-up surface (docs/migration.md): chunks pulled
            # into the local tiers before /ready
            out["kv_directory_prefetched_pages_total"] = (
                self.kv_directory_prefetched_pages
            )
        if self._kvdir_pull is not None:
            # ...and pull-side: lookups/hits drive the cross-engine pull
            # hit-rate panel; pulled pages are blobs fetched into local tiers
            q = self._kvdir_pull.stats()
            out["kv_directory_lookups_total"] = q["kv_directory_lookups_total"]
            out["kv_directory_lookup_hits_total"] = (
                q["kv_directory_lookup_hits_total"]
            )
            out["kv_directory_pulled_pages_total"] = (
                q["kv_directory_pulled_pages_total"]
            )
        if self._fabric_server is not None or self._fabric_client is not None:
            # KV fabric surface (docs/kv-fabric.md): push/pull volume, the
            # tier fallbacks every fabric path is allowed to take, corrupt
            # frames quarantined on either side, generation-fenced stale
            # pulls, and the live op depth peers fold into transfer-cost
            # scores (peers.transfer_cost_score)
            srv = self._fabric_server.stats() if self._fabric_server else {}
            cli = self._fabric_client.stats() if self._fabric_client else {}
            out["kv_fabric_pushed_pages_total"] = cli.get("pushed_pages", 0)
            out["kv_fabric_pulled_pages_total"] = cli.get("pulled_pages", 0)
            out["kv_fabric_served_pages_total"] = srv.get("served_pages", 0)
            out["kv_fabric_received_pages_total"] = srv.get("received_pages", 0)
            out["kv_fabric_fallbacks_total"] = cli.get("fallbacks", 0)
            out["kv_fabric_corrupt_frames_total"] = (
                cli.get("corrupt_frames", 0) + srv.get("corrupt_frames", 0)
            )
            out["kv_fabric_stale_generation_pulls_total"] = srv.get(
                "stale_generation_pulls", 0
            )
            out["kv_fabric_breaker_opens_total"] = cli.get("breaker_opens", 0)
            out["kv_fabric_peer_probes_total"] = cli.get("probes", 0)
            out["kv_fabric_queue_depth"] = srv.get("queue_depth", 0)
        if self.warm is not None:
            out.update(self.warm.stats())
        return out
