"""ModelRunner — owns device state (params, KV page pools) and the jitted step.

One compiled program per (batch_bucket, chunk_bucket, pages_bucket) triple; the
scheduler quantizes work to those buckets so XLA never sees a new shape in
steady state. KV pools are donated every call, so XLA updates pages in place
(no pool-sized copies per token).

This is the layer the reference delegates to vLLM's model executor; the serving
contract above it (engine/api_server.py) matches the stack's expectations
(SURVEY.md §1 L4).
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from production_stack_tpu import models
from production_stack_tpu.engine import devicemon
from production_stack_tpu.engine.step_programs import (
    Preloader,
    StepProgramStore,
    abstract_args,
    program_key,
)
from production_stack_tpu.ops.attention import write_kv_pages_all_layers
from production_stack_tpu.ops.sampling import (
    apply_logit_bias,
    apply_penalties,
    sample,
    sample_with_logprobs,
)
from production_stack_tpu.parallel import shardings
from production_stack_tpu.parallel.mesh import make_mesh
from production_stack_tpu.tracing import get_flightrecorder, profiler
from production_stack_tpu.utils.logging import init_logger


logger = init_logger(__name__)

# TPU scalar memory, from the compiler's own refusal on v5e ("Ran out of
# memory in memory space smem. Used 1.10M of 1.00M smem"). The decode
# kernel's scalar-prefetch operands (page table + packed cell maps) all live
# there for the largest (batch, pages) bucket; 64 KiB stays free for the
# rest of the step program (the estimate tracks the compiler's count to ~1%).
_SMEM_BYTES = (1 << 20) - (64 << 10)


def _pow2_at_least(n: int) -> int:
    """Scheduler batch/page buckets are powers of two (engine/scheduler.py)."""
    return 1 << max(0, int(n) - 1).bit_length()


class ProgramBuildError(RuntimeError):
    """A step program failed the first time its shape was dispatched: trace,
    lowering, compile or first launch. Unlike a fault in a program that has
    run before, retrying cannot help — every later batch of that shape fails
    the same way — so the engine stops reporting healthy (engine.py)."""


@dataclasses.dataclass(frozen=True)
class AttnResolution:
    """What ``attn_impl`` resolved to, and why — reported by GET /stats."""

    requested: str
    impl: str     # the value threaded into the model config
    prefill: str  # "pallas" | "pallas_interpret" | "xla"
    decode: str   # "pallas" | "pallas_shard_map" | "pallas_interpret" | "xla"
    reason: str   # why a kernel is not selected ("" when both are)


def kernel_refusal(
    *, head_dim: int, kv_heads_per_shard: int, pool_itemsize: int,
    page_size: int = 64, max_batch: Optional[int] = None,
    max_pages: Optional[int] = None,
) -> Optional[str]:
    """Why the ragged kernels are not taken at this attention shape (None =
    they are). Both DMA whole pool pages, so head_dim must fill 128-lane
    tiles. The prefill kernel copies ``[page, KH, D]``, so KH must fill a
    sublane tile too; the decode kernel reads a page as ``[page * KH, D]``
    rows and compiles without that, but the two are chosen
    together and no chip run has checked it there, so the rule stands for
    both. The messages are the compiler's
    (tests/test_kernels_compile_v5e.py compiles every preset against this
    rule; PERF.md section 6 lists the excluded shapes)."""
    if head_dim % 128:
        return (
            f"head_dim {head_dim} is not a multiple of the 128-lane tile "
            "(Mosaic: 'Slice shape along dimension 4 must be aligned to "
            f"tiling (128), but is {head_dim}' at the page DMA)"
        )
    pack = max(1, 4 // pool_itemsize)  # rows packed per 32-bit sublane
    if kv_heads_per_shard % pack:
        return (
            f"{kv_heads_per_shard} kv head(s) per shard with "
            f"{pool_itemsize}-byte pool entries do not fill a sublane tile "
            "(Mosaic: 'Slice shape along dimension 3 must be aligned to "
            f"tiling ({pack}), but is {kv_heads_per_shard}' at the prefill "
            "kernel's page DMA)"
        )
    if max_batch and max_pages:
        from production_stack_tpu.ops.pallas.paged_attention import (
            decode_smem_bytes,
        )

        rows, pages = _pow2_at_least(max_batch), _pow2_at_least(max_pages)
        need = decode_smem_bytes(
            rows, pages, page_size, kv_heads_per_shard, head_dim, pool_itemsize
        )
        if need > _SMEM_BYTES:
            return (
                f"scalar-prefetch operands of the largest decode bucket "
                f"({rows} rows x {pages} pages) need {need} bytes of SMEM, "
                f"{_SMEM_BYTES} are budgeted (XLA: 'Ran out of memory in "
                "memory space smem')"
            )
    return None


def resolve_attn_impl(
    requested: str, *, platform: str, n_devices: int, fwd_takes_mesh: bool,
    num_heads: int, num_kv_heads: int, head_dim: int, tp: int,
    pool_itemsize: int, page_size: int = 64,
    max_batch: Optional[int] = None, max_pages: Optional[int] = None,
) -> AttnResolution:
    """THE rule that picks the attention implementation, from platform and
    shapes only. ``auto`` takes the kernels wherever they compile and says
    why not otherwise; an explicit kernel request that cannot compile is a
    ValueError here, at start-up, never a per-request failure."""
    multi = n_devices > 1
    if requested == "xla":
        return AttnResolution(requested, "xla", "xla", "xla", "requested")
    if requested == "pallas_interpret":
        return AttnResolution(
            requested, requested,
            "xla" if multi else "pallas_interpret", "pallas_interpret",
            "multi-device prefill stays on the XLA/ring path" if multi else "",
        )
    if requested not in ("auto", "pallas", "pallas_prefill"):
        raise ValueError(
            f"unknown attn_impl {requested!r}; options: auto, xla, pallas, "
            "pallas_prefill, pallas_interpret"
        )
    refusal = None
    if platform != "tpu":
        refusal = f"no TPU backend (platform={platform})"
    elif multi and not fwd_takes_mesh:
        # GSPMD cannot partition a pallas_call: every multi-device case must
        # reach the kernel through shard_map inside the model forward
        refusal = "model family has no shard_map decode path for a mesh"
    elif num_heads % tp or num_kv_heads % tp:
        # the sharded kernel's specs split heads over tp; uneven head counts
        # only work on the XLA/GSPMD gather path, which tolerates padding
        refusal = (
            f"heads ({num_heads} q / {num_kv_heads} kv) do not divide tp={tp}"
        )
    else:
        refusal = kernel_refusal(
            head_dim=head_dim, kv_heads_per_shard=num_kv_heads // tp,
            pool_itemsize=pool_itemsize, page_size=page_size,
            max_batch=max_batch, max_pages=max_pages,
        )
    if refusal is not None:
        if requested != "auto":
            raise ValueError(
                f"attn_impl={requested!r} cannot compile here: {refusal}"
            )
        return AttnResolution(requested, "xla", "xla", "xla", refusal)
    if multi:
        # decode runs the kernel per shard; multi-device prefill keeps the
        # XLA/ring path inside the model forward by design
        return AttnResolution(
            requested, "pallas", "xla", "pallas_shard_map",
            "multi-device prefill stays on the XLA/ring path (GSPMD cannot "
            "partition a pallas_call)",
        )
    if requested == "pallas":
        return AttnResolution(
            requested, "pallas", "xla", "pallas", "decode kernel requested"
        )
    return AttnResolution(requested, "pallas_prefill", "pallas", "pallas", "")


@dataclasses.dataclass
class StepInput:
    """Host-side batch description, already bucketed by the scheduler."""

    input_ids: Any      # [B, T] int32
    positions: Any      # [B, T] int32, -1 pad
    page_table: Any     # [B, max_pages] int32
    kv_lens: Any        # [B] int32 (including this step's tokens)
    temperature: Any    # [B] float32
    top_k: Any          # [B] int32
    top_p: Any          # [B] float32
    lora_ids: Any = None  # [B] int32 adapter slot (0 = base); None when LoRA off
    kv_limits: Any = None  # [B] int32 max kv_len (multi-step decode bound)
    # sampling penalties (set together when any row has penalties):
    history: Any = None      # [B, H] int32 prompt+output ids, position-indexed
    prompt_lens: Any = None  # [B] int32
    presence: Any = None     # [B] f32
    frequency: Any = None    # [B] f32
    repetition: Any = None   # [B] f32
    # OpenAI logit_bias (set together when any row has one):
    bias_ids: Any = None     # [B, K] int32 token ids, >= vocab_size = unused
    bias_vals: Any = None    # [B, K] f32 additive biases
    # [B] int32 slot of each row in the recurrent-state pool (a family with
    # ``init_state``; the null slot for padded rows)
    state_slots: Any = None
    # decode rows that take one step inside this prefill dispatch (a runner
    # whose ``rider_refusal`` is None): (ids [R, 1], positions [R, 1],
    # page_table [R, Pr], kv_lens [R], temperature [R], top_k [R], top_p [R],
    # state_slots [R] or None as ``state_slots`` above), a slot of fixed width
    # whose padded rows have position -1, kv_len 0 and the null state slot
    riders: Any = None


class ModelRunner:
    """Holds params + KV pools on device and runs jitted prefill/decode steps."""

    def __init__(
        self,
        cfg,
        *,
        mesh: Optional[Mesh] = None,
        params: Optional[dict] = None,
        num_pages: int = 512,
        page_size: int = 16,
        seed: int = 0,
        module=None,
        enable_lora: bool = False,
        max_loras: int = 4,
        max_lora_rank: int = 16,
        lora_targets: tuple[str, ...] = ("wq", "wk", "wv", "wo"),
        max_batch: Optional[int] = None,
        state_slots: Optional[int] = None,
    ):
        # ``max_batch``: the scheduler's largest decode batch, when the
        # caller knows it — sizes the kernel's SMEM check (kernel_refusal).
        # ``state_slots``: sequences that can run at once, for a family that
        # keeps recurrent state beside the pages (``init_state``): the state
        # pool holds one slot each and a null slot for padded rows
        self.module = module if module is not None else models.module_for_config(cfg)
        self.cfg = cfg
        self.page_size = page_size
        self.num_pages = num_pages
        self.mesh = mesh if mesh is not None else make_mesh()
        mesh_shape = dict(self.mesh.shape)
        self._sp = mesh_shape.get("sp", 1)
        self._pp = mesh_shape.get("pp", 1)
        import inspect

        fwd_takes_mesh = (
            "mesh" in inspect.signature(self.module.forward).parameters
        )
        # which mesh axes the family's forward actually implements (a mesh
        # kwarg alone doesn't imply ring attention / pipeline support)
        mesh_axes = getattr(
            self.module, "MESH_AXES",
            ("dp", "tp") if fwd_takes_mesh else (),
        )
        if (self._sp > 1 and "sp" not in mesh_axes) or (
            self._pp > 1 and "pp" not in mesh_axes
        ):
            raise ValueError(
                f"model family {self.module.__name__.rsplit('.', 1)[-1]!r} "
                "does not support sequence/pipeline parallelism"
            )
        if self._sp > 1 or self._pp > 1:
            if self._pp > 1 and cfg.num_layers % self._pp:
                raise ValueError(
                    f"pipeline_parallel_size={self._pp} must divide "
                    f"num_layers={cfg.num_layers}"
                )
        # KV cache dtype (ops/quant.py): "auto" = model dtype; "bf16"/"fp16"
        # pin an explicit fp pool dtype; "int8" stores quantized pages plus
        # per-page per-kv-head scales pools — half the decode byte stream,
        # double the effective pool capacity
        kvdt = str(getattr(cfg, "kv_cache_dtype", "auto") or "auto")
        known = {
            "auto": None, "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
            "fp16": jnp.float16, "float16": jnp.float16, "int8": jnp.int8,
        }
        if kvdt not in known:
            raise ValueError(
                f"unknown kv_cache_dtype {kvdt!r}; options: {sorted(known)}"
            )
        self.kv_quant = kvdt == "int8"
        self.kv_pool_dtype = known[kvdt] or getattr(cfg, "dtype", jnp.bfloat16)
        tp = mesh_shape.get("tp", 1)
        self.attn = resolve_attn_impl(
            cfg.attn_impl,
            platform=jax.default_backend(),
            n_devices=self.mesh.devices.size,
            fwd_takes_mesh=fwd_takes_mesh,
            num_heads=getattr(cfg, "num_heads", 1),
            num_kv_heads=getattr(cfg, "num_kv_heads", 1),
            head_dim=getattr(cfg, "head_dim", 128),
            tp=tp,
            pool_itemsize=np.dtype(self.kv_pool_dtype).itemsize,
            page_size=page_size,
            max_batch=max_batch,
            max_pages=-(-cfg.max_model_len // page_size),
        )
        logger.info(
            "attention: requested=%s prefill=%s decode=%s%s",
            self.attn.requested, self.attn.prefill, self.attn.decode,
            f" ({self.attn.reason})" if self.attn.reason else "",
        )
        if self.attn.impl != cfg.attn_impl:
            cfg = dataclasses.replace(cfg, attn_impl=self.attn.impl)
            self.cfg = cfg
        # the forward needs the mesh for sp/pp and for the sharded pallas
        # decode path on multi-device meshes
        needs_mesh = self._sp > 1 or self._pp > 1 or (
            cfg.attn_impl.startswith("pallas") and self.mesh.devices.size > 1
        )
        if needs_mesh and not fwd_takes_mesh:
            raise ValueError(
                f"model family {self.module.__name__.rsplit('.', 1)[-1]!r} "
                f"does not support attn_impl={cfg.attn_impl!r} on a "
                "multi-device mesh"
            )
        self._forward = (
            functools.partial(self.module.forward, mesh=self.mesh)
            if needs_mesh
            else self.module.forward
        )
        # deferred-scatter decode bursts (kv_burst): pools stay read-only
        # through the burst scan — requires post write mode and a family
        # whose forward takes the accumulator; pp relays KV stage-to-stage
        # and keeps the classic block-carry path
        self._kv_burst_ok = (
            "kv_burst" in inspect.signature(self.module.forward).parameters
            and getattr(cfg, "kv_write_mode", "pre") == "post"
            and self._pp == 1
        )

        # whether the family's prefill step takes running decode rows along
        # for one token each (``forward(riders=)``: models/llama.py,
        # models/nemotron_h.py), and what stands in the way here where it
        # does: the scheduler plans riders only where this is None (engine.py
        # hands it over)
        self.rider_refusal = (
            "family" if "riders" not in inspect.signature(
                self.module.forward).parameters
            else "mesh" if self.mesh.devices.size > 1
            else "kv_quant" if self.kv_quant
            else "lora" if enable_lora
            else "kv_write_mode" if getattr(cfg, "kv_write_mode", "pre") != "post"
            # one kind of attention for the chunk and for the riders: both
            # kernels (they read the stacked pools) or neither
            else "attn_impl" if self.attn.impl == "pallas"
            else None
        )

        # a family with recurrent state beside the pages (models/jamba.py):
        # the pool rides every step program next to the page pools, donated
        self.has_state = hasattr(self.module, "init_state")
        self.state = None
        self.state_slots = 0
        self.ssm_impl, self.ssm_reason = "", ""
        if self.has_state:
            family = self.module.__name__.rsplit(".", 1)[-1]
            # (what the family cannot serve with, parallel sizes and
            # speculation included, is refused with its reason at start-up:
            # engine._restrict_to_state_family)
            if not self._kv_burst_ok:
                raise ValueError(
                    f"model family {family!r} advances its recurrent state "
                    "inside the deferred decode burst: kv_write_mode='post'"
                )
            if hasattr(cfg, "ssm_impl"):
                # a family whose state is a selective scan's (models/jamba.py);
                # one whose only state is a convolution's tail has no scan
                # (models/lfm2.py) and ``ssm_impl`` stays ""
                from production_stack_tpu.ops.pallas.ssm_scan import (
                    resolve_ssm_impl,
                )

                self.ssm_impl, self.ssm_reason = (
                    resolve_ssm_impl(jax.default_backend())
                    if cfg.ssm_impl == "auto" else (cfg.ssm_impl, "requested")
                )
                cfg = dataclasses.replace(cfg, ssm_impl=self.ssm_impl)
                self.cfg = cfg
            self.state_slots = int(state_slots or max_batch or 8)
            report = self.state_report()
            logger.info(
                "recurrent state: %d seats (+ the null slot) of %d bytes = %d "
                "bytes; selective scan: %s%s",
                report["ssm_state_slots"], report["ssm_state_bytes_per_slot"],
                report["ssm_state_bytes"], self.ssm_impl or "none",
                f" ({self.ssm_reason})" if self.ssm_reason else "",
            )
        # int32 counters of what the device did in a dispatch (models/lfm2.py:
        # what the expert layers routed): a last output of every step program,
        # its host copy started at the dispatch and read by take_counters()
        self.num_counters = int(getattr(cfg, "step_counters", 0))
        self._counters: list = []

        if self.kv_quant:
            fwd_params = inspect.signature(self.module.forward).parameters
            if "kv_scales" not in fwd_params:
                raise ValueError(
                    f"model family {self.module.__name__.rsplit('.', 1)[-1]!r} "
                    "does not support kv_cache_dtype=int8"
                )
            if getattr(cfg, "kv_write_mode", "pre") != "post":
                raise ValueError(
                    "kv_cache_dtype=int8 requires kv_write_mode='post'"
                )
            if not self._kv_burst_ok:
                raise ValueError(
                    "kv_cache_dtype=int8 requires the deferred-burst decode "
                    "path (post write mode, kv_burst-capable family)"
                )
            if self._sp > 1 or self._pp > 1:
                raise ValueError(
                    "kv_cache_dtype=int8 does not compose with sp/pp meshes"
                )

        # sampled tokens come back fully replicated so the leader process can
        # fetch the whole batch in multi-host serving (each process can only
        # address its own shards); logits/pools keep their compiler-chosen or
        # donated layouts.
        self._rep = NamedSharding(self.mesh, P())
        self._steps: dict[bool, Any] = {}  # want_logprobs -> jitted step
        self._multi_steps: dict[tuple, Any] = {}  # (k, want_lp) -> jitted decode
        self._spec_fns: dict[tuple, Any] = {}   # (steps, k, n) -> jitted spec decode
        # exported step programs beside the compile cache (None: no cache
        # directory, so no store), how each jitted step was jitted (the
        # store's wrapper repeats it), and the program each (family, sig,
        # shapes) runs through once it has dispatched (None: the plain jit)
        self.step_store = StepProgramStore.beside_compile_cache()
        self._jit_kw: dict[Any, dict] = {}
        self._programs: dict[tuple, Any] = {}
        # every field of a step program's key that does not name the batch's
        # shape: the identity the store lists this process's programs by. The
        # sizes the runner was built with are in it (they decide the shapes
        # of the arguments it owns: another pool's programs are not ours)
        self._key_fields = {
            "module": self.module.__name__, "cfg": repr(self.cfg),
            "page_size": page_size,
            "pool_dtype": str(np.dtype(self.kv_pool_dtype)),
            "runner": [self._kv_burst_ok, self.kv_quant],
            "mesh": list(self.mesh.shape.items()),
            "processes": jax.process_count(),
            "sizes": [num_pages, self.state_slots, enable_lora and [
                max_loras, max_lora_rank, list(lora_targets)]],
        }
        # the executables of what the store lists for that identity are built
        # from here on, on threads of their own, while the weights are drawn
        # and the pools built below: a listed shape's first dispatch takes one
        self.preloaded = Preloader(
            self.step_store, self._key_fields, self.mesh, self._wrapper)

        if params is None:
            # seeded random weights, built under jit straight into their
            # shards (no device ever holds the whole tree)
            self.params = shardings.init_sharded(
                self.module.init_params, cfg, jax.random.key(seed),
                self.mesh, pp=self._pp > 1,
            )
        else:
            # host (numpy) leaves go leaf by leaf to their shards
            pspecs = shardings.param_specs_for(params, pp=self._pp > 1)
            self.params = shardings.shard_tree(params, pspecs, self.mesh)
        self._kv_init_kw = {} if kvdt == "auto" else {"dtype": known[kvdt]}
        self.reset_kv()
        self._rng = jax.random.key(seed)

        self.enable_lora = enable_lora
        self.max_loras = max_loras
        self.max_lora_rank = max_lora_rank
        self.lora_targets = tuple(lora_targets)
        self.lora = None
        if enable_lora:
            if not hasattr(self.module, "init_lora_buffers"):
                raise ValueError(
                    f"LoRA is not supported for model family "
                    f"{self.module.__name__.rsplit('.', 1)[-1]!r} (llama-family only)"
                )
            # slot-stacked adapter buffers, replicated (small; the deltas they
            # produce inherit the activations' sharding under GSPMD).
            # max_loras counts adapters; slot 0 is the base model, hence +1.
            buf = self.module.init_lora_buffers(
                cfg, max_loras + 1, max_lora_rank, self.lora_targets
            )
            rep = NamedSharding(self.mesh, P())
            self.lora = jax.tree.map(lambda x: jax.device_put(x, rep), buf)
            self._set_lora_fn = None  # built lazily in set_lora_slot

        self._row_sh = NamedSharding(self.mesh, shardings.BATCH_SPECS["input_ids"])
        self._vec_sh = NamedSharding(self.mesh, shardings.BATCH_SPECS["kv_lens"])
        # what first dispatches cost, by phase (engine stats() exports it)
        self.first_dispatch = {
            "count": 0, "seconds": 0.0,
            "trace": 0.0, "lower": 0.0, "compile": 0.0, "run": 0.0,
        }
        # the decode kernel's block per (batch, pages) bucket dispatched:
        # pages a grid cell consumes as one tile and blocks in its VMEM
        # ring, as ops/pallas/paged_attention.py derived them from the
        # shapes (engine stats() exports it as decode_kernel_blocks)
        self.decode_blocks: dict[str, dict] = {}
        # `with self.section("stage"):` books host->device staging into the
        # engine loop's section accounting; the engine sets it, a runner on
        # its own gets the profiler's span alone
        self.section = lambda name: profiler.span("pstpu.loop." + name)
        self._set_page_fn = None  # built lazily in set_page
        self._get_page_fn = None  # built lazily in get_page (multi-host)
        self._get_pages_fns = {}  # batched offload spill, per id-count bucket
        self._set_pages_fns = {}  # batched offload restore
        self._last_hist = None    # device history after a burst (chaining)
        self._params_host = None  # host copy during sleep level 2
        self._encode = None       # built lazily in encode (pooled embeddings)

    def _stage(self, inp: StepInput, with_limits: bool = False) -> dict:
        """Host→device staging shared by step/step_multi: split the RNG and
        device_put every input with the runner's shardings."""
        with self.section("stage"):
            return self._stage_inputs(inp, with_limits)

    def _stage_inputs(self, inp: StepInput, with_limits: bool) -> dict:
        self._rng, key = _next_key(self._rng)
        if self.mesh.devices.size == 1:
            # single chip: hand numpy straight to the jitted call — one
            # transfer batch instead of a device_put round trip per array.
            # Device arrays (burst chaining feeds the previous burst's
            # tokens back without a host fetch) pass through untouched.
            row = vec = lambda x, dt: (
                x if isinstance(x, jax.Array) else np.asarray(x, np.dtype(dt))
            )
        else:
            row = lambda x, dt: jax.device_put(jnp.asarray(x, dt), self._row_sh)
            vec = lambda x, dt: jax.device_put(jnp.asarray(x, dt), self._vec_sh)
        lora_ids = None
        if self.lora is not None:
            ids_arr = (
                inp.lora_ids
                if inp.lora_ids is not None
                else np.zeros(np.asarray(inp.kv_lens).shape, np.int32)
            )
            lora_ids = vec(ids_arr, jnp.int32)
        staged = dict(
            input_ids=row(inp.input_ids, jnp.int32),
            positions=row(inp.positions, jnp.int32),
            page_table=row(inp.page_table, jnp.int32),
            kv_lens=vec(inp.kv_lens, jnp.int32),
            temperature=vec(inp.temperature, jnp.float32),
            top_k=vec(inp.top_k, jnp.int32),
            top_p=vec(inp.top_p, jnp.float32),
            key=key,
            lora_ids=lora_ids,
        )
        if with_limits:
            B = np.asarray(inp.kv_lens).shape[0]
            limits = (
                inp.kv_limits
                if inp.kv_limits is not None
                else np.full((B,), np.iinfo(np.int32).max // 2, np.int32)
            )
            staged["kv_limits"] = vec(limits, jnp.int32)
        if inp.history is not None and inp.presence is not None:
            staged["pen"] = (
                row(inp.history, jnp.int32),
                vec(inp.prompt_lens, jnp.int32),
                vec(inp.presence, jnp.float32),
                vec(inp.frequency, jnp.float32),
                vec(inp.repetition, jnp.float32),
            )
        if inp.bias_ids is not None:
            staged["bias"] = (
                row(inp.bias_ids, jnp.int32),
                row(inp.bias_vals, jnp.float32),
            )
        if self.has_state:
            if inp.state_slots is None:
                raise ValueError(
                    "this model family keeps recurrent state: the batch needs "
                    "state_slots (the scheduler fills them in)"
                )
            staged["state_slots"] = vec(inp.state_slots, jnp.int32)
        if inp.riders is not None:
            ids, pos, table, lens, temp, top_k, top_p, slots = inp.riders
            staged["riders"] = (
                row(ids, jnp.int32), row(pos, jnp.int32), row(table, jnp.int32),
                vec(lens, jnp.int32), vec(temp, jnp.float32),
                vec(top_k, jnp.int32), vec(top_p, jnp.float32),
            )
            if self.has_state:
                if slots is None:
                    raise ValueError(
                        "this model family keeps recurrent state: the riders "
                        "need their state slots (the scheduler fills them in)"
                    )
                staged["riders"] += (vec(slots, jnp.int32),)
        return staged

    def _with_state(self, args: tuple, s: dict, scales_at: int) -> tuple:
        """``args`` with the state pool and the rows' slots behind them (the
        slot a quantised pool's scales would take stays empty)."""
        if not self.has_state:
            return args
        return args + (None,) * (scales_at - len(args)) + (
            self.state, s["state_slots"],
        )

    def _keep_counters(self, out: tuple) -> tuple:
        """Take a dispatch's counters off the end of its result and start
        their copy to the host: they arrive with the tokens."""
        if not self.num_counters:
            return out
        *out, counters = out
        counters.copy_to_host_async()
        self._counters.append(counters)
        return tuple(out)

    def take_counters(self):
        """Sum (int64 [num_counters]) of the counters of the dispatches that
        have ended since the last call, in order; those still running stay
        for the next call. None: nothing ended, or the family counts nothing."""
        total = None
        while self._counters and self._counters[0].is_ready():
            c = np.asarray(self._counters.pop(0), np.int64)
            total = c if total is None else total + c
        return total

    def _jit(self, program, donate: tuple, outs: tuple):
        """``jax.jit`` a step program and remember how: what comes back from
        the store is jitted the same way (_step_program)."""
        kw = {"donate_argnums": donate, "out_shardings": outs}
        fn = jax.jit(program, **kw)
        self._jit_kw[fn] = kw
        return fn

    def _wrapper(self, family: str, sig: tuple):
        """(name, what jits an exported call as this runner jits it) of the
        step program that ``family`` runs for ``sig``."""
        fn = {"step": self._get_step, "multi_step": self._get_multi_step,
              "spec_step": self._get_spec}[family](*sig)
        return fn.__name__, functools.partial(self._wrap, fn)

    def _wrap(self, fn, call):
        return jax.jit(_named_program(fn.__name__, call), **self._jit_kw[fn])

    def _program_key(self, fn, family: str, sig, args: tuple) -> str:
        kw = self._jit_kw[fn]
        return program_key(
            dict(
                self._key_fields,
                program=fn.__name__, family=family, sig=repr(sig),
                args=abstract_args(args, self.mesh.devices.size),
                donate=kw["donate_argnums"],
                outs=[o and f"{tuple(o.mesh.shape.items())}{o.spec}"
                      for o in kw["out_shardings"]],
            ),
            self.mesh.devices.flat[0],
        )

    def _step_program(self, fn, key, listed, args: tuple, refused=None):
        """What the first dispatch of a shape runs where nothing was
        preloaded for it, and how the store took part:
        ``jit(exported.call)`` over the module the store holds ("hit") or
        holds from now on ("write"; "error" where the file that was there had
        to be deleted: ``refused`` is what its call raised), the plain jit
        where there is no store ("off": no ``key``) or ``jax.export`` refuses
        the program ("error"; ``/stats`` names it). Cold or warm, XLA compiles
        the same module, so the persistent compile cache's key is the same
        too. ``listed``: what the store keeps for a later process's loader."""
        store = self.step_store
        if key is None:
            return fn, "off"
        try:
            if refused is not None:
                store.discard(key, f"{type(refused).__name__}: {refused}")
            exported, status = store.exported(key, fn, args, listed)
        except Exception as e:  # noqa: BLE001 - whatever jax.export refuses
            store.bypass(fn.__name__, f"{type(e).__name__}: {e}")
            return fn, "error"
        return self._wrap(fn, exported.call), "error" if refused is not None else status

    def _first_call(self, fn, family: str, sig, args: tuple):
        """(program, how the store took part, the batch's result) of a
        shape's first dispatch."""
        key = listed = None
        if self.step_store is not None and fn in self._jit_kw:
            key = self._program_key(fn, family, sig, args)
            listed = (self.preloaded.identity, {
                "program": fn.__name__, "family": family, "sig": list(sig),
                "order": self.first_dispatch["count"],
            })
            # an executable built at start-up from the same blob; one that
            # is not there, or refuses these arguments, is built below
            served = self.preloaded.call(key, args)
            if served is not None:
                return served[0], "preloaded", served[1]
        program, store = self._step_program(fn, key, listed, args)
        try:
            return program, store, jax.block_until_ready(program(*args))
        except Exception as e:  # noqa: BLE001 - judged by `store`
            if store != "hit":
                raise
            refused = e
        # the blob deserialised, but its call does not trace or lower
        # (nothing is donated before it does): once more from the step
        # function itself
        program, store = self._step_program(fn, key, listed, args, refused=refused)
        return program, store, jax.block_until_ready(program(*args))

    def _dispatch(self, fn, family: str, sig, s: dict, args: tuple):
        """Call a step program. The first call of each (family, sig, ids
        shape, pages shape, structure and shapes of the other batch
        arguments) resolves the program through the store, is timed to its
        result and split by phase; a failure there is a ProgramBuildError, not
        a per-batch fault."""
        batch_args, tree = jax.tree.flatten(args[3:])
        key = (family, sig, s["input_ids"].shape, s["page_table"].shape, tree,
               tuple(x.shape for x in batch_args))
        if key in self._programs:
            return (self._programs[key] or fn)(*args)
        devicemon.install_compile_listener()
        ids_shape, pages_shape = list(key[2]), list(key[3])
        t0 = time.perf_counter()
        try:
            with profiler.span(
                "pstpu.first_dispatch", family=family, sig=repr(sig),
                ids=str(ids_shape), pages=str(pages_shape),
            ), devicemon.capture_first_dispatch() as phases:
                program, store, out = _roomy(
                    self._first_call, fn, family, sig, args
                )
        except Exception as e:
            raise ProgramBuildError(
                f"{family}{sig} ids{key[2]} pages{key[3]}: "
                f"{type(e).__name__}: {str(e)[:2000]}"
            ) from e
        wall = time.perf_counter() - t0
        self._programs[key] = None if program is fn else program
        # what JAX reported on this thread for the call; the rest is the
        # first execution, the transfers and the executable's load
        split = {p: phases[p] for p in ("trace", "lower", "compile")}
        split["run"] = max(0.0, wall - sum(split.values()))
        fd = self.first_dispatch
        fd["count"] += 1
        fd["seconds"] += wall
        for p, secs in split.items():
            fd[p] += secs
        if ids_shape[1] == 1 and self.attn.decode != "xla":
            self._note_decode_block(*pages_shape)
        # ONE event per first dispatch ties the compile seconds (which the
        # jax.monitoring listener records without a shape) to the serving
        # shape that caused them. Steady-state serving records none: a stream
        # of them mid-traffic means the set-up or the bucketing missed shapes.
        get_flightrecorder().record(
            "compile", event="first_dispatch", family=family, sig=repr(sig),
            ids_shape=ids_shape, pages_shape=pages_shape,
            seconds=round(wall, 4),
            **{f"{p}_s": round(secs, 4) for p, secs in split.items()},
            cache=(
                "hit" if phases["cache_hits"]
                else "miss" if phases["cache_misses"]
                else "uncached" if phases["compile"] else "none"
            ),
            store=store,
        )
        logger.info(
            "first dispatch %s%s ids%s pages%s: %.2f s (trace %.2f, lower %.2f, "
            "compile or load %.2f, run %.2f), store %s", family, sig, ids_shape,
            pages_shape, wall, *(split[p] for p in ("trace", "lower", "compile", "run")),
            store,
        )
        return out

    def _note_decode_block(self, batch: int, max_pages: int) -> None:
        """Written once, at a decode bucket's first dispatch: the block the
        kernel's derivation chose for it."""
        from production_stack_tpu.ops.pallas.paged_attention import (
            decode_block_shape,
        )

        cfg = self.cfg
        n, ring = decode_block_shape(
            max_pages, self.page_size,
            cfg.num_kv_heads // dict(self.mesh.shape).get("tp", 1),
            cfg.head_dim, np.dtype(self.kv_pool_dtype).itemsize,
            getattr(cfg, "decode_pages_per_block", 0) or None,
            getattr(cfg, "decode_prefetch_pages", 0) or None,
        )
        self.decode_blocks[f"{batch}x{max_pages}"] = {
            "pages_per_block": n, "ring_blocks": ring,
        }

    def _get_step(self, want_lp: bool, want_pen: bool):
        sig = (want_lp, want_pen)
        if sig not in self._steps:
            rep, n = self._rep, None
            outs = (rep, n, rep, rep, rep, n, n) if want_lp else (rep, n, n, n)
            donate = (1, 2)
            if self.kv_quant:
                outs = outs + (n, n)  # updated scales pools
                donate = (1, 2, 15)   # kv_scales tuple rides at arg 15
            if self.has_state:
                outs = outs + (n,)    # the state pool, updated in place
                donate = (1, 2, 16)   # it rides at arg 16, its slots at 17
            if self.num_counters:
                outs = outs + (rep,)
            self._steps[sig] = self._jit(
                _named_program(
                    "pstpu_step" + _flags(want_lp, want_pen),
                    _step_fn, self._forward, self.cfg, want_lp, want_pen,
                ),
                donate, outs,
            )
        return self._steps[sig]

    def step(self, inp: StepInput, want_logprobs: bool = False):
        """Run one forward+sample step. Returns (token_ids [B], logits [B, V])
        or, with ``want_logprobs``, (ids, logits, (chosen_lp [B],
        top_ids [B, K], top_lp [B, K]))."""
        s = self._stage(inp)
        want_pen = "pen" in s
        args = (
            self.params, self.k_pages, self.v_pages,
            s["input_ids"], s["positions"], s["page_table"], s["kv_lens"],
            s["temperature"], s["top_k"], s["top_p"], s["key"],
            self.lora, s["lora_ids"], s.get("pen"), s.get("bias"),
        )
        if self.kv_quant:
            args = args + ((self.k_scales, self.v_scales),)
        args = self._with_state(args, s, 16)
        if "riders" in s:
            args = args + (None,) * (18 - len(args)) + (s["riders"],)
        out = self._dispatch(
            self._get_step(want_logprobs, want_pen), "step",
            (want_logprobs, want_pen), s, args,
        )
        out = self._keep_counters(out)
        if self.has_state:
            *out, self.state = out
        if self.kv_quant:
            *out, self.k_scales, self.v_scales = out
        if want_logprobs:
            ids, logits, lp, tids, tlp, self.k_pages, self.v_pages = out
            return ids, logits, (lp, tids, tlp)
        ids, logits, self.k_pages, self.v_pages = out
        return ids, logits

    def step_multi(self, inp: StepInput, k: int, want_logprobs: bool = False):
        """Run k fused decode steps in ONE device program (lax.scan feeding
        each sampled token back as the next input). Returns tokens [B, k] —
        or (tokens, (chosen_lp [B, k], top_ids [B, k, K], top_lp [B, k, K]))
        with ``want_logprobs``.

        Why: on serving hosts every dispatch pays host<->device latency (and
        per-call device_puts); at decode, compute per step is a few ms, so the
        round trip dominates. Fusing k steps amortizes it k-fold — the
        TPU-native answer to the reference's multi-step scheduling knob.
        Sequences that run out of budget mid-burst (EOS handling is host-side)
        are masked via ``kv_limits``: their positions go to -1, so KV writes
        drop and attention masks, and the host discards their surplus tokens.
        """
        if k == 1:
            if want_logprobs:
                ids, _, lps = self.step(inp, want_logprobs=True)
                lp, tids, tlp = lps
                return jnp.asarray(ids)[:, None], (
                    jnp.asarray(lp)[:, None],
                    jnp.asarray(tids)[:, None],
                    jnp.asarray(tlp)[:, None],
                )
            ids, _ = self.step(inp)
            return jnp.asarray(ids)[:, None]
        s = self._stage(inp, with_limits=True)
        want_pen = "pen" in s
        sig = (k, want_logprobs, want_pen)
        args = (
            self.params, self.k_pages, self.v_pages,
            s["input_ids"], s["positions"], s["page_table"], s["kv_lens"],
            s["kv_limits"], s["temperature"], s["top_k"], s["top_p"], s["key"],
            self.lora, s["lora_ids"], s.get("pen"), s.get("bias"),
        )
        if self.kv_quant:
            args = args + ((self.k_scales, self.v_scales),)
        args = self._with_state(args, s, 17)
        out = self._keep_counters(
            self._dispatch(self._get_multi_step(*sig), "multi_step", sig, s, args)
        )
        if self.has_state:
            *out, self.state = out
        if self.kv_quant:
            *out, self.k_scales, self.v_scales = out
        if want_logprobs:
            toks, lp, tids, tlp, hist_f, self.k_pages, self.v_pages = out
            self._last_hist = hist_f if want_pen else None
            return toks, (lp, tids, tlp)
        toks, hist_f, self.k_pages, self.v_pages = out
        self._last_hist = hist_f if want_pen else None
        return toks

    def _get_multi_step(self, k: int, want_logprobs: bool, want_pen: bool):
        sig = (k, want_logprobs, want_pen)
        if sig not in self._multi_steps:
            rep, n = self._rep, None
            outs = (
                (rep, rep, rep, rep, rep, n, n)
                if want_logprobs
                else (rep, rep, n, n)
            )
            fn = _multi_step_deferred_fn if self._kv_burst_ok else _multi_step_fn
            donate = (1, 2)
            if self.kv_quant:
                # int8 pools require the deferred-burst path (enforced at
                # construction): pools + scales stay scan constants, and the
                # single burst commit is the quantizer
                outs = outs + (n, n)
                donate = (1, 2, 16)
            if self.has_state:
                # the deferred burst (enforced at construction): the state
                # pool is the burst scan's carry
                outs = outs + (n,)
                donate = (1, 2, 17)
            if self.num_counters:
                outs = outs + (rep,)
            self._multi_steps[sig] = self._jit(
                _named_program(
                    f"pstpu_multi_step_k{k}" + _flags(want_logprobs, want_pen),
                    fn, self._forward, self.cfg, k, want_logprobs, want_pen,
                ),
                donate, outs,
            )
        return self._multi_steps[sig]

    def step_multi_pipelined(
        self,
        inp: StepInput,
        k: int,
        bursts: int,
        want_logprobs: bool = False,
        fetch_group: int = 0,
    ) -> list:
        """Dispatch ``bursts`` chained k-step decode bursts WITHOUT fetching
        between them; returns the per-burst device token arrays ([B, k] each)
        — or, with ``fetch_group`` g > 0 (and no logprobs), per-GROUP arrays
        ([B, <=g*k] each) whose on-device concatenation is enqueued right at
        the group boundary and whose host copy starts immediately.

        What it does: chaining feeds burst j+1's input token straight from
        burst j's device-resident output (toks[:, -1:]), so a chain of m
        bursts costs m*compute + 1 host fetch when the caller finally
        fetches, instead of m*(compute + fetch). Its value on a directly
        attached chip is not measured (ROADMAP D4 decides from the ledger).
        Grouped fetching goes further: because device programs execute in
        ENQUEUE order, a group's concat+copy enqueued at its boundary
        completes as soon as ITS bursts do — the transfer overlaps the later
        bursts' compute, so the caller can apply/emit group j while group
        j+1 still runs (a concat enqueued after the last burst would wait
        for the whole chain instead).

        The host mirrors the device's per-row activity rule exactly
        (_multi_step_fn body: emit; active = pos>=0 & lens<kv_limits;
        pos = active ? pos+1 : -1; lens += active) to derive each burst's
        positions/kv_lens, and passes pos=-1 for rows that went inactive so
        the seam step's KV writes drop instead of corrupting the last real
        token's page slot. Requires inp.kv_limits sized for the FULL
        bursts*k budget (scheduler plans this).
        """
        if bursts <= 1:
            res = self.step_multi(inp, k, want_logprobs)
            if fetch_group and not want_logprobs:
                res.copy_to_host_async()
            return [res]
        pos = np.asarray(inp.positions, np.int64)[:, 0].copy()
        lens = np.asarray(inp.kv_lens, np.int64).copy()
        limits = np.asarray(inp.kv_limits, np.int64)
        outs = []
        group: list = []

        def flush_group():
            if not group:
                return
            cat = group[0] if len(group) == 1 else jnp.concatenate(group, axis=1)
            cat.copy_to_host_async()
            outs.append(cat)
            group.clear()

        cur = inp
        for j in range(bursts):
            res = self.step_multi(cur, k, want_logprobs)
            toks = res[0] if want_logprobs else res
            if fetch_group and not want_logprobs:
                group.append(res)
                if len(group) >= fetch_group:
                    flush_group()
            else:
                outs.append(res)
            if j == bursts - 1:
                break
            for _ in range(k):  # exact mirror of the device scan
                active = (pos >= 0) & (lens < limits)
                pos = np.where(active, pos + 1, -1)
                lens = lens + active
            cur = dataclasses.replace(
                inp,
                input_ids=toks[:, -1:],
                positions=pos[:, None].astype(np.int32),
                kv_lens=lens.astype(np.int32),
                # penalties: the DEVICE history (with this burst's tokens
                # already recorded) feeds the next burst — the host copy
                # staged at chain start is stale past the seam
                history=(
                    self._last_hist if inp.history is not None else None
                ),
            )
        if fetch_group and not want_logprobs:
            flush_group()
        return outs

    def step_spec(
        self, inp: StepInput, history: Any, steps: int, spec_k: int, ngram: int
    ) -> jnp.ndarray:
        """Fused speculative decode: ``steps`` rounds of (n-gram draft →
        parallel verify → rejection-sample accept) in ONE device program.

        The draft model is prompt-lookup (vLLM's ngram speculator, TPU-native):
        the trailing ``ngram`` tokens are matched against the sequence's own
        token history *on device*, and the ``spec_k`` tokens that followed the
        most recent match become the draft. One forward over 1+spec_k
        positions scores them all; a sampled target token per position gives
        exact rejection-sampling acceptance (for a deterministic draft,
        "sample t ~ p, accept iff t == draft" IS the spec-sampling rule, and
        the first mismatching t is the correction token). Each round emits
        1..spec_k+1 tokens for one forward pass — decode becomes MXU-bound
        verify work instead of latency-bound single-token steps.

        Args:
          inp: decode-shaped StepInput ([B, 1] inputs; kv_limits REQUIRED —
               a row stays active while ``lens + spec_k <= kv_limits``).
          history: [B, H] int32 token ids (prompt + output so far), 0-padded.
        Returns tokens [B, steps, 1+spec_k] int32, -1 where nothing emitted.
        """
        if self.kv_quant:
            raise ValueError(
                "speculative decoding is not supported with "
                "kv_cache_dtype=int8 (the spec scan carries raw pool blocks)"
            )
        sig = (steps, spec_k, ngram)
        s = self._stage(inp, with_limits=True)
        hist = jax.device_put(jnp.asarray(history, jnp.int32), self._row_sh) \
            if self.mesh.devices.size > 1 else np.asarray(history, np.int32)
        toks, self.k_pages, self.v_pages = self._dispatch(
            self._get_spec(*sig), "spec_step", sig, s,
            (
                self.params, self.k_pages, self.v_pages, hist,
                s["input_ids"], s["positions"], s["page_table"],
                s["kv_lens"], s["kv_limits"], s["temperature"], s["top_k"],
                s["top_p"], s["key"], self.lora, s["lora_ids"],
            ),
        )
        return toks

    def _get_spec(self, steps: int, spec_k: int, ngram: int):
        sig = (steps, spec_k, ngram)
        if sig not in self._spec_fns:
            self._spec_fns[sig] = self._jit(
                _named_program(
                    f"pstpu_spec_s{steps}_k{spec_k}_n{ngram}",
                    _spec_fn, self._forward, self.cfg, steps, spec_k, ngram,
                ),
                (1, 2), (self._rep, None, None),
            )
        return self._spec_fns[sig]

    def encode(self, input_ids, positions) -> jnp.ndarray:
        """Pooled-embedding forward ([B, T] -> [B, H] unit vectors). Shapes
        must arrive bucketed (engine quantizes B and T)."""
        if self._encode is None:
            if not hasattr(self.module, "encode"):
                raise ValueError(
                    f"embeddings are not supported for model family "
                    f"{self.module.__name__.rsplit('.', 1)[-1]!r}"
                )
            self._encode = jax.jit(
                functools.partial(self.module.encode, cfg=self.cfg),
                out_shardings=self._rep,
            )
        row = lambda x: jax.device_put(jnp.asarray(x, jnp.int32), self._row_sh)
        return self._encode(
            params=self.params, input_ids=row(input_ids), positions=row(positions)
        )

    # -- LoRA slot management (engine/lora.py drives these) ------------------

    def set_lora_slot(self, slot: int, tensors: dict, scale: float) -> None:
        """Write one adapter's stacked weights into `slot` in place."""
        if self.lora is None:
            raise RuntimeError("runner built with enable_lora=False")
        if not 0 < slot <= self.max_loras:
            raise ValueError(f"slot must be in [1, {self.max_loras}], got {slot}")
        if self._set_lora_fn is None:
            def _set(layers, scale_vec, slot, new_layers, new_scale):
                layers = {
                    k: (v.at[:, slot].set(new_layers[k].astype(v.dtype))
                        if k in new_layers else v)
                    for k, v in layers.items()
                }
                return layers, scale_vec.at[slot].set(new_scale)

            self._set_lora_fn = jax.jit(_set, donate_argnums=(0, 1))
        self.lora["layers"], self.lora["scale"] = self._set_lora_fn(
            self.lora["layers"], self.lora["scale"], jnp.int32(slot),
            {k: jnp.asarray(v) for k, v in tensors.items()},
            jnp.float32(scale),
        )

    def clear_lora_slot(self, slot: int) -> None:
        if self.lora is None:
            raise RuntimeError("runner built with enable_lora=False")
        # per-slot leaf shape: [L, S, d1, d2] -> [L, d1, d2]
        zeros = {
            k: np.zeros((v.shape[0],) + v.shape[2:], np.float32)
            for k, v in self.lora["layers"].items()
        }
        self.set_lora_slot(slot, zeros, 0.0)

    def get_page(self, pid: int):
        """Fetch one page's K/V to host ([L, page_size, KH, D] each).

        Multi-host: a process can only address its own pool shards, so the
        page is first laid out fully-replicated by an SPMD program (the
        all-gather rides ICI/DCN) and the LOCAL replica is fetched. This is
        a REPLICATED dispatch (distributed.py) — every process runs the same
        program, the leader's host fetch sees the whole page — which is what
        makes KV offload tiers work under multi-host serving (the reference
        runs LMCache under multi-node vLLM the same leader-driven way,
        deployment-vllm-multi.yaml:202-331)."""
        if not self.k_pages.is_fully_addressable:
            if self._get_page_fn is None:
                rep = NamedSharding(self.mesh, P())
                self._get_page_fn = jax.jit(
                    lambda kp, vp, i: (kp[:, i], vp[:, i]),
                    out_shardings=(rep, rep),
                )
            k, v = self._get_page_fn(self.k_pages, self.v_pages, jnp.int32(pid))
            return jax.device_get((k, v))
        return jax.device_get((self.k_pages[:, pid], self.v_pages[:, pid]))

    def get_pages(self, pids: "list[int]"):
        """Fetch N pages' K/V in ONE host round trip.

        The per-page :meth:`get_page` costs a full host<->device round trip;
        an eviction storm spilling a long history page-by-page stalls the
        engine loop once per page.
        The page-id vector is bucketed to powers of two (padded by repeating
        the last id — an extra gather lane, harmless) so the program count
        stays bounded. Returns ``(ks, vs)``: per-page ``[L, page, KH, D]``
        host arrays."""
        n = len(pids)
        if n == 0:
            # REPLICATED multi-host dispatch surface: an unguarded empty call
            # would raise (pids[-1]) on whichever process hit it and desync
            # the follower set — return without touching the device
            return [], []
        bucket = 1
        while bucket < n:
            bucket <<= 1
        ids = jnp.asarray(
            np.asarray(list(pids) + [pids[-1]] * (bucket - n), np.int32)
        )
        fn = self._get_pages_fns.get(bucket)
        if fn is None:
            rep = NamedSharding(self.mesh, P())
            fn = jax.jit(
                lambda kp, vp, i: (kp[:, i], vp[:, i]),
                out_shardings=(rep, rep),
            )
            self._get_pages_fns[bucket] = fn
        k, v = jax.device_get(fn(self.k_pages, self.v_pages, ids))
        return [k[:, i] for i in range(n)], [v[:, i] for i in range(n)]

    def set_pages(self, pids: "list[int]", ks, vs) -> None:
        """Write N pages in ONE host->device upload + one scatter program
        (batched offload restore — see :meth:`get_pages` for why). ``ks``/
        ``vs`` are per-page ``[L, page, KH, D]`` arrays. Padding duplicates
        the last (id, data) lane, so the duplicate scatter rewrites the same
        value — deterministic."""
        n = len(pids)
        if n == 0:
            return  # see get_pages: empty calls must be no-ops, not errors
        bucket = 1
        while bucket < n:
            bucket <<= 1
        ids = np.asarray(list(pids) + [pids[-1]] * (bucket - n), np.int32)
        dt = self.k_pages.dtype
        k = np.stack(list(ks) + [ks[-1]] * (bucket - n), axis=1)
        v = np.stack(list(vs) + [vs[-1]] * (bucket - n), axis=1)
        fn = self._set_pages_fns.get(bucket)
        if fn is None:
            fn = jax.jit(
                lambda kp, vp, i, k, v: (
                    kp.at[:, i].set(k), vp.at[:, i].set(v)
                ),
                donate_argnums=(0, 1),
            )
            self._set_pages_fns[bucket] = fn
        rep = self._rep
        kd = jax.device_put(jnp.asarray(k, dt), rep)
        vd = jax.device_put(jnp.asarray(v, dt), rep)
        self.k_pages, self.v_pages = fn(
            self.k_pages, self.v_pages, jnp.asarray(ids), kd, vd
        )

    # -- quantized pools: the serde boundary moves int8 pages + scales -------
    # (KVOffloadConnector detects runner.kv_quant and uses these so blobs
    # ship the halved int8 byte stream end-to-end — ops/quant.py contract)

    def get_pages_quant(self, pids: "list[int]"):
        """Fetch N quantized pages + their scales in ONE host round trip.
        Returns (ks, vs, sks, svs): per-page ``[L, page, KH, D]`` int8 and
        ``[L, KH]`` f32 host arrays — the exact pool bytes, no dequant."""
        n = len(pids)
        if n == 0:
            return [], [], [], []
        bucket = 1
        while bucket < n:
            bucket <<= 1
        ids = jnp.asarray(
            np.asarray(list(pids) + [pids[-1]] * (bucket - n), np.int32)
        )
        fn = self._get_pages_fns.get(("q", bucket))
        if fn is None:
            rep = NamedSharding(self.mesh, P())
            fn = jax.jit(
                lambda kp, vp, ks, vs, i: (
                    kp[:, i], vp[:, i], ks[:, i], vs[:, i]
                ),
                out_shardings=(rep, rep, rep, rep),
            )
            self._get_pages_fns[("q", bucket)] = fn
        k, v, sk, sv = jax.device_get(
            fn(self.k_pages, self.v_pages, self.k_scales, self.v_scales, ids)
        )
        return (
            [k[:, i] for i in range(n)], [v[:, i] for i in range(n)],
            [sk[:, i] for i in range(n)], [sv[:, i] for i in range(n)],
        )

    def set_pages_quant(self, pids: "list[int]", ks, vs, sks, svs) -> None:
        """Write N quantized pages + scales in ONE upload + scatter (the
        restore twin of :meth:`get_pages_quant`).

        Validates the scales before touching the pools: transferred pages
        (disagg fabric frames, migration ships) arrive from another engine,
        and an int8 page scattered with missing or misshaped scales would
        dequantize to garbage silently — reject loudly instead so the
        transfer path takes its tier/recompute fallback."""
        n = len(pids)
        if n == 0:
            return
        ks, vs, sks, svs = list(ks), list(vs), list(sks), list(svs)
        if not (len(ks) == len(vs) == len(sks) == len(svs) == n):
            raise ValueError(
                f"set_pages_quant: {n} pids but "
                f"{len(ks)}/{len(vs)}/{len(sks)}/{len(svs)} pages/scales"
            )
        scale_shape = (self.k_scales.shape[0], self.k_scales.shape[2])
        for sk_i, sv_i in zip(sks, svs):
            for s in (sk_i, sv_i):
                a = np.asarray(s)
                if a.shape != scale_shape or not np.issubdtype(
                    a.dtype, np.floating
                ):
                    raise ValueError(
                        f"set_pages_quant: scale {a.shape}/{a.dtype} does "
                        f"not match pool scales {scale_shape}/float32 — a "
                        "quantized page arrived without usable per-kv-head "
                        "scales"
                    )
        bucket = 1
        while bucket < n:
            bucket <<= 1
        pad = bucket - n
        ids = np.asarray(list(pids) + [pids[-1]] * pad, np.int32)
        k = np.stack(list(ks) + [ks[-1]] * pad, axis=1)
        v = np.stack(list(vs) + [vs[-1]] * pad, axis=1)
        sk = np.stack(list(sks) + [sks[-1]] * pad, axis=1)
        sv = np.stack(list(svs) + [svs[-1]] * pad, axis=1)
        fn = self._set_pages_fns.get(("q", bucket))
        if fn is None:
            fn = jax.jit(
                lambda kp, vp, ksc, vsc, i, k, v, sk, sv: (
                    kp.at[:, i].set(k), vp.at[:, i].set(v),
                    ksc.at[:, i].set(sk), vsc.at[:, i].set(sv),
                ),
                donate_argnums=(0, 1, 2, 3),
            )
            self._set_pages_fns[("q", bucket)] = fn
        rep = self._rep
        put = lambda x, dt: jax.device_put(jnp.asarray(x, dt), rep)
        self.k_pages, self.v_pages, self.k_scales, self.v_scales = fn(
            self.k_pages, self.v_pages, self.k_scales, self.v_scales,
            jnp.asarray(ids),
            put(k, jnp.int8), put(v, jnp.int8),
            put(sk, jnp.float32), put(sv, jnp.float32),
        )

    def get_page_device(self, pid: int):
        """One page's K/V as SINGLE-DEVICE arrays (device 0), for the
        device-to-device transfer path: the pool may be kv-head-sharded over
        tp, but the XLA transfer service pulls whole single-shard buffers —
        the gather rides ICI, never the host."""
        sh = jax.sharding.SingleDeviceSharding(self.mesh.devices.flat[0])
        return (
            jax.device_put(self.k_pages[:, pid], sh),
            jax.device_put(self.v_pages[:, pid], sh),
        )

    def set_page(self, pid: int, k, v) -> None:
        """Write one page's K/V into the pools in place (offload restore /
        disaggregated-prefill KV injection). Accepts host arrays or device
        arrays from another mesh/device (device-to-device transfer staging) —
        those reshard onto this runner's mesh first, device-side."""
        if self._set_page_fn is None:
            self._set_page_fn = jax.jit(
                lambda kp, vp, i, k, v: (kp.at[:, i].set(k), vp.at[:, i].set(v)),
                donate_argnums=(0, 1),
            )
        dt = self.k_pages.dtype
        rep = self._rep  # replicated over this runner's mesh
        k = jax.device_put(jnp.asarray(k, dt), rep)
        v = jax.device_put(jnp.asarray(v, dt), rep)
        self.k_pages, self.v_pages = self._set_page_fn(
            self.k_pages, self.v_pages, jnp.int32(pid), k, v,
        )

    # -- multi-host device-to-device KV (disaggregated prefill over DCN) ------
    # Every method here is REPLICATED (distributed.py): the leader broadcasts
    # it over the step stream and each process acts on ITS shard/copy, so KV
    # bytes move device->device over the XLA transfer service — never through
    # the host or the (host-byte) step stream. Reference analogue: NIXL
    # GPU-direct between prefill and decode pods
    # (/root/reference helm/templates/deployment-vllm-multi.yaml:256-296).

    def _local_mesh_devices(self) -> list:
        return [
            d for d in self.mesh.devices.flat
            if d.process_index == jax.process_index()
        ]

    def _replicate_page(self, pid: int):
        """SPMD program laying one page out fully-replicated (the all-gather
        rides ICI/DCN); every process ends up with the whole page on each of
        its local devices."""
        if self._get_page_fn is None:
            rep = NamedSharding(self.mesh, P())
            self._get_page_fn = jax.jit(
                lambda kp, vp, i: (kp[:, i], vp[:, i]),
                out_shardings=(rep, rep),
            )
        return self._get_page_fn(self.k_pages, self.v_pages, jnp.int32(pid))

    def kv_endpoint_start(self) -> None:
        """Start this process's transfer-service endpoint and publish its
        address through the JAX coordination KV store (the same trust domain
        as the step-sync secret, distributed.py:resolve_sync_secret)."""
        if getattr(self, "kv_endpoint", None) is not None:
            return
        from production_stack_tpu.kvoffload.transfer import DeviceKVEndpoint

        # bind/advertise host is per-process (each pod has its own IP):
        # PSTPU_KV_EP_HOST is set per pod (fieldRef status.podIP in the
        # helm chart); loopback covers single-machine tests
        import os as os_mod

        host = (
            os_mod.environ.get("PSTPU_KV_EP_HOST")
            or getattr(self, "kv_endpoint_host", None)
            or "127.0.0.1"
        )
        self.kv_endpoint = DeviceKVEndpoint(self, host=host)
        self.kv_staged: dict[str, tuple] = {}
        try:
            from jax._src import distributed as jdist

            client = jdist.global_state.client
            if client is not None:
                client.key_value_set(
                    f"pstpu/kv_ep/{jax.process_index()}",
                    self.kv_endpoint.address,
                )
        except Exception:  # noqa: BLE001 - single-process: no coordination svc
            pass

    def kv_offer_page(self, pid: int, uuid_base: int, pullers: int) -> tuple:
        """Replicate one page, then offer this process's local copy for every
        consumer process assigned to it: consumer c pulls from producer
        c % P under uuid ``uuid_base + c``, so process i offers exactly
        {uuid_base + c : c % P == i}. Returns (shape, dtype) from the local
        copy (the leader's caller needs them for page_ready)."""
        self.kv_endpoint_start()
        k, v = self._replicate_page(pid)
        k_l = k.addressable_shards[0].data
        v_l = v.addressable_shards[0].data
        i, nproc = jax.process_index(), jax.process_count()
        for c in range(i, int(pullers), nproc):
            self.kv_endpoint.offer_fixed(int(uuid_base) + c, k_l, v_l)
        return list(k_l.shape), str(k_l.dtype)

    def kv_pull_page(
        self, assignments: list, shape, dtype, key: str
    ) -> int:
        """Pull this process's copy of a page from its assigned producer
        endpoint and stage it locally; returns the staged byte count (0 on
        failure — the leader's staging accounting needs the real size even
        when its budget reservation TTL'd out mid-pull). ``assignments`` has
        one (addr, uuid) per consumer process. A pull failure stages nothing
        but does NOT raise — the leader notices its own failure (or a later
        restore mismatch) and replicates kv_unstage_page so every process
        converges, then the producer falls back to TCP blobs for the page."""
        self.kv_endpoint_start()
        addr, uuid = assignments[jax.process_index() % len(assignments)]
        self._kv_staged_sweep()
        try:
            k_l, v_l = self.kv_endpoint.pull(addr, int(uuid), shape, dtype)
        except Exception as e:  # noqa: BLE001
            import logging

            logging.getLogger(__name__).warning("device kv pull failed: %s", e)
            return 0
        import time as time_mod

        # TTL is 2x the leader-side DeviceStaging ttl: the leader must always
        # give up on a page (and replicate kv_unstage_page) before any
        # follower's local sweep could drop it — else a leader restore would
        # find follower staging gone (fatal desync by design)
        self.kv_staged[key] = (k_l, v_l, time_mod.monotonic() + 240.0)
        return int(k_l.nbytes) * 2

    def kv_restore_page(self, key: str, pid: int) -> None:
        """Write a staged page into this process's pool shards. The device
        program is identical on every process (SPMD set_page); the staged
        copy is local, so no bytes cross the step stream. Missing staged
        state here is a desync bug — fatal by design (distributed.py
        failure model)."""
        entry = self.kv_staged.pop(key, None)
        if entry is None:
            raise RuntimeError(
                f"kv_restore_page: page {key!r} not staged on process "
                f"{jax.process_index()} — staging diverged from the leader"
            )
        k_l, v_l, _ = entry
        if self._set_page_fn is None:
            self._set_page_fn = jax.jit(
                lambda kp, vp, i, k, v: (kp.at[:, i].set(k), vp.at[:, i].set(v)),
                donate_argnums=(0, 1),
            )
        dt = self.k_pages.dtype
        k_l = jnp.asarray(k_l, dt)
        v_l = jnp.asarray(v_l, dt)
        if self.k_pages.is_fully_addressable:
            k_rep = jax.device_put(k_l, self._rep)
            v_rep = jax.device_put(v_l, self._rep)
        else:
            # assemble the replicated global operand from per-process local
            # copies: one single-device copy per local mesh device
            local = self._local_mesh_devices()
            k_rep = jax.make_array_from_single_device_arrays(
                k_l.shape, self._rep,
                [jax.device_put(k_l, d) for d in local],
            )
            v_rep = jax.make_array_from_single_device_arrays(
                v_l.shape, self._rep,
                [jax.device_put(v_l, d) for d in local],
            )
        self.k_pages, self.v_pages = self._set_page_fn(
            self.k_pages, self.v_pages, jnp.int32(pid), k_rep, v_rep,
        )

    def kv_unstage_page(self, key: str) -> None:
        """Drop a staged page on every process (leader-side staging expiry or
        a failed/partial pull). Host-side only — always symmetric-safe."""
        self.kv_staged.pop(key, None)

    def _kv_staged_sweep(self) -> None:
        """TTL cleanup for never-restored staged pages. Host-side dict work:
        divergent timing across processes cannot desync device state (the
        authoritative drop is the leader's replicated kv_unstage_page; this
        sweep only bounds worst-case device memory if that never arrives)."""
        import time as time_mod

        now = time_mod.monotonic()
        for k in [k for k, (_, _, d) in self.kv_staged.items() if d < now]:
            self.kv_staged.pop(k, None)

    def kv_pool_shard_layout(self) -> "list[tuple[str, int]]":
        """Static per-device KV pool footprint: ``(device_label, bytes)`` for
        every mesh device, k+v pools together.

        Computed from the pool SHARDING (shard_shape), not the live buffers —
        the live arrays are donated into every step, and a scrape racing the
        device thread would intermittently see a deleted buffer. With kv
        heads sharded over tp each chip holds ``total / (tp * pp)`` bytes
        (the per-chip pool the multichip serving path is sized by:
        docs/multichip-serving.md); a GQA pool that cannot split (KH % tp
        != 0) reports the full replicated footprint per device."""
        KH = getattr(self.cfg, "num_kv_heads", 1)
        shape = (
            self.cfg.num_kv_layers, self.num_pages, self.page_size,
            KH, self.cfg.head_dim,
        )
        sh = self._kv_sharding()
        per = 2 * int(np.prod(sh.shard_shape(shape)))
        per *= np.dtype(self.kv_pool_dtype).itemsize  # 1 under int8
        if self.kv_quant:
            ssh = self._kv_scales_sharding()
            per += 2 * 4 * int(
                np.prod(ssh.shard_shape((self.cfg.num_kv_layers, self.num_pages, KH)))
            )
        return [
            (f"{d.platform}:{d.id}", per) for d in self.mesh.devices.flat
        ]

    def _kv_sharding(self) -> NamedSharding:
        """Pool sharding for this mesh (pp shards the layer axis).

        KV heads shard over tp only when they divide evenly; a GQA model with
        fewer KV heads than the tp axis (e.g. 2 KV heads at tp=4) replicates
        the pool instead — the XLA attention path then reads it GSPMD-style
        (this is also why attn_impl=auto refuses pallas there)."""
        spec = shardings.KV_PAGES_SPEC_PP if self._pp > 1 else shardings.KV_PAGES_SPEC
        tp = dict(self.mesh.shape).get("tp", 1)
        if getattr(self.cfg, "num_kv_heads", 1) % tp:
            spec = P(*[None if ax == "tp" else ax for ax in spec])
        return NamedSharding(self.mesh, spec)

    def _kv_scales_sharding(self) -> NamedSharding:
        """Scales-pool sharding [L, P, KH]: the pool spec minus its
        page-slot and head-dim axes — the KH axis shards over tp exactly
        like the pages', so each chip holds its head-shard's scales."""
        spec = self._kv_sharding().spec
        return NamedSharding(self.mesh, P(spec[0], spec[1], spec[3]))

    def state_pool_bytes(self) -> int:
        """Bytes of the recurrent-state pool, the null slot included (0: the
        family keeps pages only)."""
        if not self.has_state:
            return 0
        return (self.state_slots + 1) * self.cfg.state_bytes_per_slot

    def state_report(self) -> dict:
        """Seats and bytes of the recurrent-state pool, for the start-up log
        line and ``/stats`` alike, every stateful family from the one number
        its configuration states (``state_bytes_per_slot``): the seats (one a
        running sequence: ``--max-num-seqs``, and where a slot is tens of MB
        it is the state's bytes that bound them), a seat's bytes, the pool's
        with its null slot. ``reset_kv`` holds the pools it allocates to it."""
        return {
            "ssm_state_slots": self.state_slots,
            "ssm_state_bytes_per_slot": int(self.cfg.state_bytes_per_slot),
            "ssm_state_bytes": self.state_pool_bytes(),
        }

    @property
    def conv_state_bytes(self) -> int:
        """Bytes of the convolution tails in the state pool, the null slot
        included (0: the family keeps none)."""
        conv = (self.state or {}).get("conv")
        return 0 if conv is None else int(conv.nbytes)

    def drop_kv_pools(self) -> None:
        """Release the KV pools' device memory (sleep level 1+)."""
        self.k_pages = None
        self.v_pages = None
        self.k_scales = None
        self.v_scales = None
        self.state = None

    def offload_params(self) -> None:
        """Move params to host RAM (sleep level 2). Each process fetches its
        own addressable shards, so this works on multi-host meshes as a
        REPLICATED dispatch — vLLM's sleep level 2 equivalent, per process.

        Shards replicated across local devices (dp/sp axes, or wholly
        replicated leaves) are fetched and stored ONCE, keyed by shard
        index — saving host RAM is the entire point of level 2."""
        def off(arr):
            bufs: dict = {}
            placements = []
            for s in arr.addressable_shards:
                key = repr(s.index)
                if key not in bufs:
                    bufs[key] = np.asarray(s.data)
                placements.append((s.device, key))
            return (arr.shape, arr.sharding, placements, bufs)

        # build the full host tree BEFORE dropping the device refs: a
        # mid-tree failure (host OOM is the at-risk case) must leave the
        # engine wakeable with its device params intact
        host = jax.tree.map(off, self.params)
        self._params_host = host
        self.params = None

    def restore_params(self) -> None:
        """Re-materialize params on device from the per-process host shards
        saved by offload_params (sleep level 2 wake)."""
        if self._params_host is None:
            return  # offload never completed; device params are still live

        def back(saved):
            shape, sharding, placements, bufs = saved
            locals_ = [
                jax.device_put(bufs[key], dev) for dev, key in placements
            ]
            return jax.make_array_from_single_device_arrays(
                shape, sharding, locals_
            )

        self.params = jax.tree.map(
            back, self._params_host, is_leaf=lambda x: isinstance(x, tuple)
        )
        self._params_host = None

    def reset_kv(self) -> None:
        """(Re)create zeroed page pools, each device building only its own
        shard (construction; sleep/wake frees and re-creates them)."""
        kv_sh = self._kv_sharding()
        dt = self._kv_init_kw.get("dtype")
        self.k_pages, self.v_pages = shardings.build_sharded(
            self.module.init_kv_pages,
            (self.cfg, self.num_pages, self.page_size, dt), (kv_sh, kv_sh),
        )
        if self.has_state:
            self.state = jax.jit(
                functools.partial(
                    self.module.init_state, self.cfg, self.state_slots
                ),
                # whole on the one device the family serves on
                out_shardings=NamedSharding(self.mesh, P()),
            )()
            held = sum(int(a.nbytes) for a in jax.tree.leaves(self.state))
            if held != self.state_pool_bytes():
                raise ValueError(
                    f"{self.module.__name__}.init_state allocated {held} "
                    f"bytes for {self.state_slots} seats + the null slot; its "
                    f"configuration states {self.cfg.state_bytes_per_slot} a "
                    "seat (state_bytes_per_slot)"
                )
        self.k_scales = self.v_scales = None
        if self.kv_quant:
            KH = getattr(self.cfg, "num_kv_heads", 1)
            sc_sh = self._kv_scales_sharding()
            self.k_scales, self.v_scales = shardings.build_sharded(
                _scales_pools, (self.cfg.num_kv_layers, self.num_pages, KH),
                (sc_sh, sc_sh),
            )


def _named_program(name: str, fn, *static):
    """``functools.partial(fn, *static)`` under a name: ``jax.jit`` calls the
    program ``jit_<name>`` in the profiler's trace and in compile logs (a bare
    partial is ``jit__unknown``). The name is part of the persistent compile
    cache's key, so it holds the family and its static signature and nothing
    that differs between processes (no id, no fingerprint)."""
    program = functools.partial(fn, *static)
    program.__name__ = program.__qualname__ = name
    return program


def _roomy(f, *args):
    """``f(*args)``, called from a frame of 512 KiB. CPython (3.11 on) keeps
    a thread's frames on a data stack of 16 KiB chunks and frees a chunk the
    moment its first frame returns, so a loop whose calls straddle a chunk
    boundary pays an mmap + munmap a call: 7.7 us against 0.05 us in the
    sandbox, ~25 us on the chip's sandboxed host. JAX traces 150-250 frames
    deep and, for most shapes, some boundary falls under one of its hot
    leaves: tracing the prefill step cost the engine 5-9 s where an idle
    process takes 1.0 s, every Python call of it 10-25 x slower (PERF.md
    section 6, PR 35). A frame larger than a chunk gets a chunk of its own
    (1 MiB here), and every frame called from it lives there."""
    return f(*args)


_roomy.__code__ = _roomy.__code__.replace(co_stacksize=1 << 16)


@jax.jit
def _next_key(rng):
    """Split the runner's key: (the key it keeps, the step's key as RAW key
    data). Every step program takes ``uint32[2]`` and wraps it inside: a typed
    key under a ``NamedSharding`` does not cross ``jax.export``'s boundary
    (jax 0.9.0: "'sdy.sharding_constraint' op sharding doesn't match tensor
    rank: 0 != 1")."""
    rng, key = jax.random.split(rng)
    return rng, jax.random.key_data(key)


def _flags(want_lp: bool, want_pen: bool) -> str:
    return ("_lp" if want_lp else "") + ("_pen" if want_pen else "")


def _scales_pools(num_layers: int, num_pages: int, num_kv_heads: int):
    """K and V scales pools: two buffers (both are donated every step)."""
    from production_stack_tpu.ops.quant import init_kv_scales

    return (
        init_kv_scales(num_layers, num_pages, num_kv_heads),
        init_kv_scales(num_layers, num_pages, num_kv_heads),
    )


def _split_counters(cfg, out: tuple):
    """A family whose configuration names ``step_counters`` returns that many
    int32 (what THIS call did, not a running sum) as the last element of its
    ``forward``'s result, whatever else the result holds: (the rest, the
    counters or None)."""
    if not getattr(cfg, "step_counters", 0):
        return out, None
    *out, counters = out
    return tuple(out), counters


def _multi_step_fn(forward, cfg, k, want_lp, want_pen, params, k_pages,
                   v_pages, input_ids, positions, page_table, kv_lens,
                   kv_limits, temperature, top_k, top_p, key, lora=None,
                   lora_ids=None, pen=None, bias=None):
    """k fused decode steps; see ModelRunner.step_multi. input_ids/positions
    are [B, 1] (decode shape).

    The scan carries only the batch's gathered KV block, NOT the whole pool:
    XLA double-buffers while-loop carries, so carrying a multi-GB pool through
    the scan 2-3x's KV memory and OOMs real chips. The block is a local pool
    of B*P pages indexed by an identity page table, so ``forward`` is reused
    unchanged; pages the burst wrote are scattered back afterwards."""
    B, P = page_table.shape
    pool_pages = k_pages.shape[1]
    page_size = k_pages.shape[2]
    flat = page_table.reshape(-1)
    with jax.named_scope("kv_gather"):
        k_blk = jnp.take(k_pages, flat, axis=1)  # [L, B*P, page, KH, D]
        v_blk = jnp.take(v_pages, flat, axis=1)
    local_pt = jnp.arange(B * P, dtype=jnp.int32).reshape(B, P)
    kw = {} if lora is None else {"lora": lora, "lora_ids": lora_ids}
    keys = jax.random.split(jax.random.wrap_key_data(key), k)
    if want_pen:
        hist0, plens, pres, freq, rep = pen
        H = hist0.shape[1]
        rows = jnp.arange(hist0.shape[0], dtype=jnp.int32)
    else:
        hist0 = jnp.zeros((input_ids.shape[0], 1), jnp.int32)  # inert carry

    def body(carry, key_i):
        ids, pos, lens, kp, vp, hist = carry
        logits, kp, vp = forward(
            params, cfg, ids, pos, kp, vp, local_pt, lens, **kw
        )
        with jax.named_scope("sample"):
            sample_from = logits
            if want_pen:
                sample_from = apply_penalties(
                    logits.astype(jnp.float32), hist, lens, plens, pres, freq, rep
                )
            if bias is not None:
                sample_from = apply_logit_bias(
                    sample_from.astype(jnp.float32), *bias
                )
            if want_lp:
                nxt, lp, tids, tlp = sample_with_logprobs(
                    logits, key_i, temperature, top_k, top_p,
                    sample_from=sample_from,
                )
                emit = (nxt, lp, tids, tlp)
            else:
                nxt = sample(sample_from, key_i, temperature, top_k, top_p)  # [B]
                emit = nxt
        if want_pen:
            # record this step's token at its absolute position so later
            # steps in the burst count it
            slot = jnp.where(pos[:, 0] >= 0, lens, H)
            hist = hist.at[rows, slot].set(nxt, mode="drop")
        # a row continues while it was active this step and has budget left
        active = (pos[:, 0] >= 0) & (lens < kv_limits)
        pos = jnp.where(active, pos[:, 0] + 1, -1)[:, None]
        lens = lens + active.astype(lens.dtype)
        ids = jnp.where(active, nxt, 0)[:, None]
        return (ids, pos, lens, kp, vp, hist), emit

    (_, _, lens_f, k_blk, v_blk, hist_f), emitted = jax.lax.scan(
        body, (input_ids, positions, kv_lens, k_blk, v_blk, hist0), keys
    )
    toks = emitted[0] if want_lp else emitted
    # scatter back only the logical pages the burst wrote
    # ([(lens0-1)//page, (lens_f-1)//page] per row): those are uniquely owned
    # by each row, so no duplicate indices; everything else in the block is an
    # unmodified copy (incl. shared prefix pages and padding), dropped via an
    # out-of-range index.
    with jax.named_scope("kv_commit"):
        p_idx = jnp.arange(P, dtype=jnp.int32)[None, :]
        first = (kv_lens - 1) // page_size
        last = (lens_f - 1) // page_size
        written = (p_idx >= first[:, None]) & (p_idx <= last[:, None])
        safe = jnp.where(written, page_table, pool_pages).reshape(-1)
        k_pages = k_pages.at[:, safe].set(k_blk, mode="drop")
        v_pages = v_pages.at[:, safe].set(v_blk, mode="drop")
    # hist_f returns so chained bursts can feed it forward device-side
    # (penalty counts must include THIS burst's tokens at the next seam)
    if want_lp:
        _, lp, tids, tlp = emitted  # [k, B], [k, B, K]
        return (toks.T, lp.T, jnp.swapaxes(tids, 0, 1),
                jnp.swapaxes(tlp, 0, 1), hist_f, k_pages, v_pages)
    return toks.T, hist_f, k_pages, v_pages  # [B, k]


def _multi_step_deferred_fn(forward, cfg, k, want_lp, want_pen, params,
                            k_pages, v_pages, input_ids, positions,
                            page_table, kv_lens, kv_limits, temperature,
                            top_k, top_p, key, lora=None, lora_ids=None,
                            pen=None, bias=None, kv_scales=None, state=None,
                            state_slots=None):
    """k fused decode steps with DEFERRED KV scatters (kv_burst mode).

    The classic _multi_step_fn gathers the batch's pages into a local block
    and carries it through the scan; every step's in-place write forces XLA
    to materialize block-sized copies (the dominant cost of a decode step on
    v5e — the pools/blocks are ~0.5 GB while the new KV per step is ~0.5 MB).
    Here the pools are scan CONSTANTS (read-only), each step appends its
    K/V to a tiny [L, B, k, KH, D] window that attention folds in via the
    kernel's masked multi-token k_cur, and ONE batched scatter commits the
    whole burst afterwards.

    A family with recurrent state (``state``: its pool, ``state_slots``: the
    rows' slots) advances it inside the burst: the pool is a carry of the
    scan, updated in place by every step, and comes back behind the page
    pools (with the burst's counters last, where the family counts)."""
    B = input_ids.shape[0]
    L, _, page_size, KH, D = k_pages.shape
    C = k
    quant = kv_scales is not None
    # int8 pools: the burst window holds the quantizer's fp INPUT (committed
    # once, below); only the read path touches int8
    acc_dt = cfg.dtype if quant else k_pages.dtype
    k_acc = jnp.zeros((L, B, C, KH, D), acc_dt)
    v_acc = jnp.zeros((L, B, C, KH, D), acc_dt)
    counts = jnp.zeros((B,), jnp.int32)
    pos0 = positions[:, 0]
    kw = {} if lora is None else {"lora": lora, "lora_ids": lora_ids}
    if quant:
        kw["kv_scales"] = kv_scales
    keys = jax.random.split(jax.random.wrap_key_data(key), k)
    if want_pen:
        hist0, plens, pres, freq, rep = pen
        H = hist0.shape[1]
        rows = jnp.arange(hist0.shape[0], dtype=jnp.int32)
    else:
        hist0 = jnp.zeros((B, 1), jnp.int32)  # inert carry

    def body(carry, key_i):
        ids, pos, lens, counts, ka, va, hist, st, work = carry
        # rows that ran out of budget (pos -1) leave their state as it is
        more = {} if st is None else {"state": st, "state_slots": state_slots}
        out, did = _split_counters(cfg, forward(
            params, cfg, ids, pos, k_pages, v_pages, page_table, lens,
            kv_burst=(ka, va, counts), **more, **kw
        ))
        if st is None:
            logits, ka_new, va_new = out
        else:
            logits, ka_new, va_new, st = out
        if did is not None:
            work = work + did
        with jax.named_scope("sample"):
            sample_from = logits
            if want_pen:
                sample_from = apply_penalties(
                    logits.astype(jnp.float32), hist, lens, plens, pres, freq, rep
                )
            if bias is not None:
                sample_from = apply_logit_bias(
                    sample_from.astype(jnp.float32), *bias
                )
            if want_lp:
                nxt, lp, tids, tlp = sample_with_logprobs(
                    logits, key_i, temperature, top_k, top_p,
                    sample_from=sample_from,
                )
                emit = (nxt, lp, tids, tlp)
            else:
                nxt = sample(sample_from, key_i, temperature, top_k, top_p)  # [B]
                emit = nxt
        if want_pen:
            slot = jnp.where(pos[:, 0] >= 0, lens, H)
            hist = hist.at[rows, slot].set(nxt, mode="drop")
        # adopt the appended window entry only for rows active this step —
        # an inactive row's slot write was garbage and must not stick
        act_now = pos[:, 0] >= 0
        sel = act_now[None, :, None, None, None]
        ka = jnp.where(sel, ka_new, ka)
        va = jnp.where(sel, va_new, va)
        counts = counts + act_now.astype(counts.dtype)
        active = act_now & (lens < kv_limits)
        pos = jnp.where(active, pos[:, 0] + 1, -1)[:, None]
        lens = lens + active.astype(lens.dtype)
        ids = jnp.where(active, nxt, 0)[:, None]
        return (ids, pos, lens, counts, ka, va, hist, st, work), emit

    n_work = getattr(cfg, "step_counters", 0)
    (_, _, _, counts_f, k_acc, v_acc, hist_f, state, work), emitted = jax.lax.scan(
        body,
        (input_ids, positions, kv_lens, counts, k_acc, v_acc, hist0, state,
         jnp.zeros((n_work,), jnp.int32) if n_work else None),
        keys,
    )
    tail = (() if state is None else (state,)) + (() if work is None else (work,))
    toks = emitted[0] if want_lp else emitted
    # one commit for the whole burst: window entry j of row b holds the
    # token at absolute position pos0 + j (valid for j < counts_f)
    jj = jnp.arange(C, dtype=jnp.int32)[None, :]
    commit_pos = jnp.where(
        (jj < counts_f[:, None]) & (pos0[:, None] >= 0),
        pos0[:, None] + jj,
        -1,
    )
    if quant:
        # the decode feedback write IS the quantizer (ops/quant.py): fresh
        # pages reset their scale, mid-page appends grow it and re-quantize
        from production_stack_tpu.ops.quant import (
            write_kv_pages_all_layers_quant,
        )

        k_scales, v_scales = kv_scales
        with jax.named_scope("kv_commit"):
            k_pages, v_pages, k_scales, v_scales = write_kv_pages_all_layers_quant(
                k_pages, v_pages, k_scales, v_scales, k_acc, v_acc,
                page_table, commit_pos,
            )
        if want_lp:
            _, lp, tids, tlp = emitted
            return (toks.T, lp.T, jnp.swapaxes(tids, 0, 1),
                    jnp.swapaxes(tlp, 0, 1), hist_f, k_pages, v_pages,
                    k_scales, v_scales)
        return toks.T, hist_f, k_pages, v_pages, k_scales, v_scales
    with jax.named_scope("kv_commit"):
        k_pages, v_pages = write_kv_pages_all_layers(
            k_pages, v_pages, k_acc, v_acc, page_table, commit_pos
        )
    if want_lp:
        _, lp, tids, tlp = emitted
        return (toks.T, lp.T, jnp.swapaxes(tids, 0, 1),
                jnp.swapaxes(tlp, 0, 1), hist_f, k_pages, v_pages, *tail)
    return (toks.T, hist_f, k_pages, v_pages, *tail)  # [B, k]


def _ngram_draft(buf, pos, n, k):
    """Prompt-lookup draft, vectorized: find the most recent earlier occurrence
    of the trailing n-gram ``buf[pos-n+1..pos]`` and return the k tokens that
    followed it. Falls back to repeating the current token (which verify will
    almost surely reject — costing nothing extra, since the verify forward has
    static width anyway).

    buf: [B, H] int32 token history; pos: [B] position of the current token.
    Returns [B, k] int32 draft tokens.
    """
    B, H = buf.shape
    S = H - n + 1
    tail_idx = jnp.clip(pos[:, None] + jnp.arange(-n + 1, 1), 0, H - 1)
    tail = jnp.take_along_axis(buf, tail_idx, axis=1)                    # [B, n]
    win_idx = jnp.arange(S)[:, None] + jnp.arange(n)[None, :]            # [S, n]
    wins = buf[:, win_idx]                                               # [B, S, n]
    match = jnp.all(wins == tail[:, None, :], axis=-1)                   # [B, S]
    starts = jnp.arange(S, dtype=jnp.int32)[None, :]
    # the match and its k following tokens must lie fully in known history
    # (this also excludes the trailing n-gram matching itself)
    ok = match & (starts + n + k - 1 <= pos[:, None])
    best = jnp.max(jnp.where(ok, starts, -1), axis=1)                    # [B]
    d_idx = jnp.clip(best[:, None] + n + jnp.arange(k), 0, H - 1)
    draft = jnp.take_along_axis(buf, d_idx, axis=1)                      # [B, k]
    cur = jnp.take_along_axis(buf, jnp.clip(pos, 0, H - 1)[:, None], axis=1)
    return jnp.where((best >= 0)[:, None], draft, cur)


def _spec_fn(forward, cfg, steps, k, n, params, k_pages, v_pages, history,
             input_ids, positions, page_table, kv_lens, kv_limits, temperature,
             top_k, top_p, key, lora=None, lora_ids=None):
    """``steps`` fused speculative rounds; see ModelRunner.step_spec.

    Like _multi_step_fn, the scan carries the batch's gathered KV block (plus
    the token-history buffer), not the whole pool. Rejected draft tokens leave
    stale KV beyond the accepted length; it is invisible (attention masks by
    kv_lens) and overwritten by the next round's writes.
    """
    B, P = page_table.shape
    pool_pages = k_pages.shape[1]
    page_size = k_pages.shape[2]
    H = history.shape[1]
    T = 1 + k
    flat = page_table.reshape(-1)
    with jax.named_scope("kv_gather"):
        k_blk = jnp.take(k_pages, flat, axis=1)
        v_blk = jnp.take(v_pages, flat, axis=1)
    local_pt = jnp.arange(B * P, dtype=jnp.int32).reshape(B, P)
    kw = {} if lora is None else {"lora": lora, "lora_ids": lora_ids}
    keys = jax.random.split(jax.random.wrap_key_data(key), steps)
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    j = jnp.arange(T, dtype=jnp.int32)[None, :]
    rep = lambda x: jnp.repeat(x, T, axis=0)  # [B] -> [B*T] row params

    def body(carry, key_i):
        buf, pos, lens, kp, vp = carry   # pos [B]: current token's position, -1 = done
        active = (pos >= 0) & (lens + k <= kv_limits)
        p0 = jnp.maximum(pos, 0)
        cur = jnp.take_along_axis(buf, p0[:, None], axis=1)              # [B, 1]
        draft = _ngram_draft(buf, p0, n, k)                              # [B, k]
        seq_in = jnp.concatenate([cur, draft], axis=1)                   # [B, T]
        pos_in = jnp.where(active[:, None], p0[:, None] + j, -1)
        lens_in = jnp.where(active, lens + k, 0)
        logits, kp, vp = forward(
            params, cfg, seq_in, pos_in, kp, vp, local_pt, lens_in,
            all_logits=True, **kw
        )                                                                # [B, T, V]
        with jax.named_scope("sample"):
            t = sample(
                logits.reshape(B * T, -1), key_i,
                rep(temperature), rep(top_k), rep(top_p),
            ).reshape(B, T)
        # exact rejection sampling for a deterministic draft: accept the
        # leading run of draft tokens the target also sampled; the first
        # mismatch IS the corrected token (and position k's sample is the
        # bonus token when everything was accepted)
        match = (t[:, :k] == draft).astype(jnp.int32)
        m = jnp.sum(jnp.cumprod(match, axis=1), axis=1)                  # [B] 0..k
        bonus = jnp.take_along_axis(t, m[:, None], axis=1)[:, 0]         # [B]
        upd = jnp.where(j == m[:, None], bonus[:, None],
                        jnp.concatenate([draft, cur], axis=1))           # [B, T]
        emit = active[:, None] & (j <= m[:, None])
        slots = jnp.where(emit, p0[:, None] + 1 + j, H)
        buf = buf.at[rows, slots].set(upd, mode="drop")
        toks = jnp.where(emit, upd, -1)                                  # [B, T]
        emitted = (m + 1) * active.astype(jnp.int32)
        pos = jnp.where(active, pos + emitted, -1)
        lens = lens + emitted
        return (buf, pos, lens, kp, vp), toks

    (_, _, lens_f, k_blk, v_blk), toks = jax.lax.scan(
        body, (history, positions[:, 0], kv_lens, k_blk, v_blk), keys
    )
    # scatter back the pages holding accepted tokens (stale tail beyond the
    # accepted length never needs to persist); same uniqueness argument as
    # _multi_step_fn: the written logical range covers only freshly-owned pages
    with jax.named_scope("kv_commit"):
        p_idx = jnp.arange(P, dtype=jnp.int32)[None, :]
        first = (kv_lens - 1) // page_size
        last = (lens_f - 1) // page_size  # padded rows: lens_f=0 -> last=-1 -> no write
        written = (p_idx >= first[:, None]) & (p_idx <= last[:, None])
        safe = jnp.where(written, page_table, pool_pages).reshape(-1)
        k_pages = k_pages.at[:, safe].set(k_blk, mode="drop")
        v_pages = v_pages.at[:, safe].set(v_blk, mode="drop")
    return jnp.transpose(toks, (1, 0, 2)), k_pages, v_pages  # [B, steps, T]


def _step_fn(forward, cfg, want_lp, want_pen, params, k_pages, v_pages,
             input_ids, positions, page_table, kv_lens, temperature, top_k,
             top_p, key, lora=None, lora_ids=None, pen=None, bias=None,
             kv_scales=None, state=None, state_slots=None, riders=None):
    """One forward and one sampled token a row. With ``riders`` (StepInput)
    the prefill chunk's rows are followed by the riding decode rows: in the
    logits, under the sampler and in the ids that come back ([B + R])."""
    kw = {} if lora is None else {"lora": lora, "lora_ids": lora_ids}
    key = jax.random.wrap_key_data(key)
    if kv_scales is not None:
        kw["kv_scales"] = kv_scales
    if state is not None:
        kw.update(state=state, state_slots=state_slots)
    if riders is not None:
        # what ``forward`` gets: ids, positions, page table, lengths and, for
        # a family with recurrent state, the riders' slots as a fifth entry
        r_temp, r_top_k, r_top_p = riders[4:7]
        kw["riders"] = riders[:4] + riders[7:]
        temperature = jnp.concatenate([temperature, r_temp])
        top_k = jnp.concatenate([top_k, r_top_k])
        top_p = jnp.concatenate([top_p, r_top_p])
    out, did = _split_counters(cfg, forward(
        params, cfg, input_ids, positions, k_pages, v_pages, page_table,
        kv_lens, **kw,
    ))
    # what follows the page pools in the result: the scales of int8 pools or
    # the state pool, then the counters
    logits, k_pages, v_pages, *tail = out
    if did is not None:
        tail.append(did)
    with jax.named_scope("sample"):
        sample_from = logits
        if want_pen:
            hist, plens, pres, freq, rep = pen
            sample_from = apply_penalties(
                logits.astype(jnp.float32), hist, kv_lens, plens, pres, freq, rep
            )
        if bias is not None:
            sample_from = apply_logit_bias(
                sample_from.astype(jnp.float32), *bias
            )
        if want_lp:
            # logprobs report the RAW distribution; penalties shape the draw only
            ids, lp, tids, tlp = sample_with_logprobs(
                logits, key, temperature, top_k, top_p, sample_from=sample_from
            )
            return (ids, logits, lp, tids, tlp, k_pages, v_pages, *tail)
        ids = sample(sample_from, key, temperature, top_k, top_p)
        return (ids, logits, k_pages, v_pages, *tail)
