"""Continuous-batching serving engine.

Replaces the fixed-batch loop (``launch.serve.FixedBatchServer``) with
request-level scheduling, the deployment path the paper's serving claim is
about: merged checkpoints route fewer, fuller expert groups through the
grouped kernel at identical arithmetic.

Design (decode dataflow details in DESIGN.md §7):

* **Slots.** The engine owns a persistent slotted KV cache
  (``[L, n_slots, s_max, nkv, hd]`` + per-slot ``pos``). A request occupies
  one slot from admission to completion; eviction just marks the slot free —
  stale rows are masked by the per-slot causal mask and overwritten in place
  by the next occupant (no copying, no reallocation).
* **Paged KV (``kv_layout='paged'``, DESIGN.md §11).** The dense slot cache
  is replaced by a flat pool of fixed-size KV blocks plus per-slot block
  tables owned by a host-side allocator (``serving.paging.PagedAllocator``):
  admission reserves a request's whole row budget
  (``prompt + max_new - 1``, plus ``spec_k`` verify headroom in spec mode)
  up front, full prompt blocks are shared copy-free between requests with
  identical prefixes (refcounted, LRU-evicted under pressure), eviction
  returns blocks to the pool, and admission DEFERS (FIFO head-of-line) when
  the pool cannot supply a reservation. ``kv_dtype='int8'`` stores the pool
  quantized with per-(row, head) fp32 scales — roughly half the decode KV
  stream of bf16. The bf16 paged engine is token-for-token IDENTICAL to the
  dense engine in every mode (plain / fused block / speculative); int8 is
  tolerance-gated instead (quantization perturbs logits).
* **Admission.** Pending requests sit in a heap ordered by
  ``(arrival_time, uid)`` (FIFO by arrival, O(log n) per op). At the top of
  every engine step each free slot claims the next due request, and all
  requests admitted together that share a prompt bucket are prefilled as ONE
  batch (padded to the next power of two to bound jit specializations) and
  inserted with one scatter — admission cost no longer scales with the burst
  size.
* **Decode.** The steady-state hot loop is DEVICE-RESIDENT: one jitted call
  runs ``decode_block`` (K) scanned decode steps with on-device sampling and
  per-slot stop flags; finished slots freeze in place and ride along. The
  host reads back one ``[K, B]`` token block per call instead of one token
  per step — host dispatches drop from ~2/token to ~2/(K·B) tokens.
  ``decode_block=1`` keeps the original step-at-a-time loop (the parity
  reference). With ``dispatch='gather'`` the decode-sized MoE layers skip
  the sort-based grouped path for the per-token gather kernel.
* **Stop conditions.** Per-request ``max_new_tokens`` and optional
  ``eos_token``, evaluated on device inside the fused block; freed slots
  admit at the next block boundary.
* **Speculative decoding.** With ``spec_draft`` (or direct
  ``draft_cfg``/``draft_params``) the engine runs dual-artifact
  draft-then-verify rounds (DESIGN.md §10): the MergeMoE-compressed draft
  proposes ``spec_k`` tokens per slot, the full model verifies them in one
  multi-position forward, and acceptance/rollback happens on device — all
  inside ONE jitted call per round. Committed tokens are always full-model
  samples, so spec mode is token-for-token identical to full-model decode
  at any temperature.

* **Resilience (DESIGN.md §12).** Requests carry optional deadlines/TTLs
  and terminate with an explicit ``status`` (``ok`` / ``shed`` /
  ``failed_numeric`` / ``failed``): expired pending requests are SHED with
  a reason (deferral-aware — a request stuck behind pool pressure sheds as
  ``pool_pressure``, not a bare timeout), the pending queue can be bounded
  with a reject-new or shed-expired-first backpressure policy, and a
  numeric-health sentinel rides the fused readback block as one extra
  lane (per-slot ``isfinite`` over the logits, zero additional host
  syncs) to QUARANTINE any slot that goes non-finite — evicted
  ``failed_numeric``, pages released, healthy slots bitwise untouched.
  A seeded ``serving.faults.FaultPlan`` injects NaN poisoning, transient
  device failures (bounded retry), and pool exhaustion deterministically,
  and ``Engine.snapshot()/restore()`` serialize the COMPLETE engine state
  (scheduler, allocator, prefix registry, KV pools, counters) so a
  mid-trace crash resumes token-for-token identical.

The clock is pluggable: ``clock='steps'`` interprets ``arrival_time`` in
decode-step units (deterministic — used by tests and the CPU benchmark),
``clock='wall'`` in seconds.

Sampling keys: every request gets the key ``fold_in(PRNGKey(seed+1), uid)``
at admission and tokens draw Gumbel noise indexed by their own sequence
position (``steps.sample_tokens``), so the sampled stream for a given
(seed, uid, prompt) is IDENTICAL across engine modes — step loop, fused
block, and speculative — and across scheduling differences between them.
"""
from __future__ import annotations

import dataclasses
import functools
import heapq
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core import errors as ERR
from repro.launch import steps as ST
from repro.models import model as MD
from repro.models.numerics import set_activation_mesh
from repro.serving.faults import FaultPlan
from repro.serving.paging import PagedAllocator
from repro.serving.spec import (build_slot_admit_spec,
                                build_slot_admit_spec_paged,
                                build_slot_decode_spec)


@dataclasses.dataclass
class Request:
    """One generation request plus its engine-filled result/telemetry."""
    uid: int
    prompt: np.ndarray                  # [prompt_len] int32
    max_new_tokens: int
    eos_token: Optional[int] = None
    arrival_time: float = 0.0           # steps or seconds, per engine clock
    # latest clock value at which admission may still start (inclusive);
    # ``ttl`` is the relative form (deadline = arrival_time + ttl) and is
    # ignored when ``deadline`` is set. None = wait forever (DESIGN.md §12).
    deadline: Optional[float] = None
    ttl: Optional[float] = None
    # engine-filled
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    t_admitted: Optional[float] = None
    t_first_token: Optional[float] = None
    t_finished: Optional[float] = None
    finish_reason: Optional[str] = None  # "length" | "eos" | "shed" | "numeric"
    # terminal status: "queued" until terminal, then "ok" | "shed" |
    # "failed_numeric" | "failed"
    status: str = "queued"
    shed_reason: Optional[str] = None    # "deadline" | "pool_pressure"
    # True once admission deferred this request for lack of pool blocks —
    # a later expiry sheds it as "pool_pressure" rather than "deadline"
    deferred: bool = False

    @property
    def n_prompt(self) -> int:
        return int(self.prompt.shape[0])

    @property
    def effective_deadline(self) -> Optional[float]:
        if self.deadline is not None:
            return self.deadline
        if self.ttl is not None:
            return self.arrival_time + self.ttl
        return None


@dataclasses.dataclass
class EngineConfig:
    arch: str = "qwen3-moe-30b-a3b"
    reduced: bool = True
    n_slots: int = 4
    s_max: int = 128                    # per-slot KV capacity
    prefill_buckets: Sequence[int] = (16, 32, 64)
    temperature: float = 0.0
    seed: int = 0
    # MoE dispatch for the serving path; "gather" = ragged with the decode
    # token counts specialized to the per-token gather kernel, "ragged"
    # forces the grouped kernel everywhere. None keeps the ModelConfig's.
    dispatch: Optional[str] = "gather"
    clock: str = "steps"                # "steps" | "wall"
    # fused decode block size K: decode steps per jitted call. 1 = the
    # step-at-a-time host loop (parity reference).
    decode_block: int = 8
    # prefill all due same-bucket requests as one batch (False = the
    # batch-of-1 admission loop, kept as the parity reference)
    batch_admission: bool = True
    # retrace/implicit-transfer guard mode (repro.analysis.trace_guard):
    # "count" surfaces violations in counters["retraces"] /
    # counters["implicit_transfers"], "strict" raises TraceGuardError,
    # "off" disables (plain jax.jit)
    trace_guard: str = "count"
    # self-speculative decoding (DESIGN.md §10): directory of a
    # ``save_compressed`` DRAFT artifact (the MergeMoE-merged model). None
    # disables spec mode; tests may instead hand (draft_cfg, draft_params)
    # straight to the Engine constructor.
    spec_draft: Optional[str] = None
    # draft proposals per verify round; each round commits 1..spec_k tokens
    spec_k: int = 4
    # KV cache layout: "dense" = the [L, n_slots, s_max, nkv, hd] slot
    # cache, "paged" = the block-pool layout (DESIGN.md §11)
    kv_layout: str = "dense"
    # paged layout knobs: KV rows per block (s_max must be a multiple),
    # pool size in blocks (0 = n_slots * s_max / kv_block, i.e. dense
    # capacity), pool storage dtype ("bf16" | "int8" — int8 carries
    # per-(row, head) fp32 scales), and copy-free prompt prefix sharing
    kv_block: int = 16
    kv_blocks: int = 0
    kv_dtype: str = "bf16"
    prefix_sharing: bool = True
    # ---- resilience (DESIGN.md §12) ----
    # numeric-health sentinel over the per-slot isfinite lane of the fused
    # readback block: "off" ignores the lane, "count" quarantines poisoned
    # slots (evict failed_numeric + counters["quarantined"]), "strict"
    # additionally raises NumericHealthError after quarantining — the same
    # mode ladder as trace_guard
    numeric_sentinel: str = "count"
    # bounded pending queue (0 = unbounded) + backpressure policy when
    # full: "reject_new" raises QueueFullError at submit; "shed_expired"
    # first sheds expired pending requests, then rejects if still full
    max_pending: int = 0
    backpressure: str = "reject_new"
    # bounded retry for transient device-step failures (injected by a
    # FaultPlan or, in the field, surfaced by the runtime): how many times
    # one step call may fail before DeviceStepError, and the exponential
    # backoff base between attempts (0 = retry immediately; tests keep 0)
    device_retries: int = 2
    retry_backoff_s: float = 0.0
    # ---- expert-parallel mesh serving (DESIGN.md §13) ----
    # mesh spec for sharded decode (``launch.mesh.parse_mesh_spec`` form,
    # e.g. "data=2,model=2"). None serves single-device (the default). The
    # "model" axis EP-shards the expert tables — MoE layers switch to the
    # all-to-all pair-exchange dispatch of ``models/moe_ep`` — and the
    # "data" axis shards slots + KV, so attention never crosses the wire.
    # Token-for-token identical to the single-device engine under the
    # default fp32 combine wire where both run the jnp oracles (CPU). On a
    # TPU they differ at bf16 level: the single-device prefill's grouped
    # kernel sums f in blocks, the EP gather kernel does not.
    mesh: Optional[str] = None
    # EP combine-wire dtype: "fp32" (bitwise-exact return all-to-all) or
    # "int8" (``distributed.compressed_psum`` of the pair-output table —
    # roughly 4x less combine wire, tolerance-gated instead of bitwise)
    combine_wire_dtype: str = "fp32"
    # ---- periodic background snapshots (§12) ----
    # > 0: persist :meth:`Engine.save_snapshot` to ``snapshot_dir`` every N
    # engine steps (as counted by the step clock), so a crash loses at most
    # N steps of committed work; 0 disables
    snapshot_every_steps: int = 0
    snapshot_dir: Optional[str] = None


class Engine:
    """Continuous-batching engine over a slotted KV cache."""

    def __init__(self, ec: EngineConfig, cfg=None, params=None,
                 draft_cfg=None, draft_params=None,
                 faults: Optional[FaultPlan] = None):
        self.ec = ec
        cfg = cfg if cfg is not None else (
            configs.get(ec.arch).reduced() if ec.reduced
            else configs.get(ec.arch))

        def _serve_dispatch(c):
            """Apply the engine's MoE dispatch override to a ModelConfig
            (shared by the full and draft configs so both artifacts serve
            under the same kernel policy)."""
            if c.moe is None or ec.dispatch is None:
                return c
            moe = dataclasses.replace(c.moe, dispatch=ec.dispatch)
            if ec.dispatch == "gather":
                # the gather ceiling must cover the decode token count
                # (T = n_slots) or big-slot engines would silently fall back
                # to ragged on every decode step
                moe = dataclasses.replace(
                    moe, gather_max_tokens=max(moe.gather_max_tokens,
                                               ec.n_slots))
            return c.replace(moe=moe)

        cfg = _serve_dispatch(cfg)
        if cfg.family not in ("dense", "moe"):
            raise NotImplementedError(
                f"continuous batching serves token-only families "
                f"(dense/moe), not {cfg.family}")
        if ec.decode_block < 1:
            raise ValueError("decode_block must be >= 1")
        if ec.numeric_sentinel not in ("off", "count", "strict"):
            raise ValueError(f"numeric_sentinel must be 'off', 'count' or "
                             f"'strict', got {ec.numeric_sentinel!r}")
        if ec.backpressure not in ("reject_new", "shed_expired"):
            raise ValueError(f"backpressure must be 'reject_new' or "
                             f"'shed_expired', got {ec.backpressure!r}")
        if ec.max_pending < 0 or ec.device_retries < 0:
            raise ValueError("max_pending and device_retries must be >= 0")
        if ec.combine_wire_dtype not in ("fp32", "int8"):
            raise ValueError(f"combine_wire_dtype must be 'fp32' or 'int8', "
                             f"got {ec.combine_wire_dtype!r}")
        if ec.snapshot_every_steps is None:    # None == 0 == disabled
            ec.snapshot_every_steps = 0
        if ec.snapshot_every_steps < 0:
            raise ValueError("snapshot_every_steps must be >= 0")
        if ec.snapshot_every_steps > 0 and not ec.snapshot_dir:
            raise ValueError("snapshot_every_steps > 0 requires snapshot_dir")
        self.cfg = cfg

        # ---- mesh-sharded serving (DESIGN.md §13) ----
        # ec.mesh builds an explicit (data, model) device mesh and swaps
        # every device program for its shard_map'd ``steps.make_*_mesh``
        # form. Activation sharding constraints (numerics.constrain) are
        # GSPMD-only and illegal inside shard_map bodies, so mesh mode
        # clears the activation mesh — the mesh programs manage layout
        # explicitly via their in/out specs. Without ec.mesh the engine is
        # single-device and sets no activation mesh either: a host-wide one
        # would make its programs GSPMD over every chip, and the TPU
        # compiler cannot partition the Pallas kernels inside them.
        self._mesh = None
        if ec.mesh is not None:
            from repro.launch.mesh import make_mesh, parse_mesh_spec
            self._mesh = make_mesh(*parse_mesh_spec(ec.mesh))
        set_activation_mesh(None)
        self._dp = (1 if self._mesh is None
                    else int(self._mesh.shape.get("data", 1)))
        if ec.n_slots % self._dp:
            raise ValueError(
                f"n_slots={ec.n_slots} must divide evenly over the mesh "
                f"'data' axis ({self._dp}): slots and their KV shard there")
        if self._mesh is None:
            self.params = params if params is not None else MD.init(
                cfg, jax.random.PRNGKey(ec.seed))
        else:
            # seeded params are built sharded by the init program itself, so
            # no device ever holds the whole model; given params only move.
            # The jitted init may round a rare element differently from the
            # eager one: engines meant to agree are handed the same params.
            from repro.launch import sharding as SH
            init = functools.partial(MD.init, cfg)
            key = jax.random.PRNGKey(ec.seed)
            shapes = params if params is not None else jax.eval_shape(
                init, key)
            SH.validate_ep_params(shapes, self._mesh)
            shardings = SH.named(SH.serve_param_pspecs(shapes, self._mesh),
                                 self._mesh)
            self.params = (jax.device_put(params, shardings)
                           if params is not None else
                           jax.jit(init, out_shardings=shardings)(key))

        # host<->device crossing telemetry: device_calls counts jitted
        # dispatches, host_syncs counts device->host readbacks, tokens_out
        # counts generated tokens (dispatches-per-token = their ratio);
        # tokens_drafted/accepted/rolled_back are spec-round bookkeeping
        # (zero outside spec mode); retraces/implicit_transfers are
        # maintained by the trace guard (DESIGN.md §9: both must stay 0
        # after warmup)
        self.counters: Dict[str, int] = {
            "device_calls": 0, "host_syncs": 0, "tokens_out": 0,
            "tokens_drafted": 0, "tokens_accepted": 0,
            "tokens_rolled_back": 0,
            # resilience telemetry (§12): all three stay 0 on a healthy,
            # uncontended trace — check_bench gates that on every
            # happy-path benchmark row
            "shed": 0, "quarantined": 0, "transient_retries": 0}
        from repro.analysis.trace_guard import TraceGuard
        self._guard = TraceGuard(ec.trace_guard, counters=self.counters)
        self._buckets = tuple(sorted(set(int(b) for b in ec.prefill_buckets)))
        # the ONLY prompt pad lengths admission may compile; bucket_for
        # fails closed on non-membership and admit_trace_budget counts this
        # same table, so the padding policy and the trace budget cannot
        # drift apart (steps.admit_pad_shapes is the single source of truth)
        self._pad_shapes = ST.admit_pad_shapes(self._buckets, ec.s_max)
        admit_budget = ST.admit_trace_budget(self._buckets, ec.s_max,
                                             ec.n_slots)

        # ---- KV layout: dense slot cache or paged block pool (§11) ----
        self._alloc: Optional[PagedAllocator] = None
        self._tab_dirty = False
        if ec.kv_layout == "paged":
            n_blocks = ec.kv_blocks if ec.kv_blocks > 0 else (
                ec.n_slots * ec.s_max // ec.kv_block)
            # the allocator validates s_max % kv_block (and, sharded, that
            # blocks and slots split evenly over the data axis so every
            # slot's reservation stays inside its shard's block range);
            # init_paged_cache validates kv_dtype
            self._alloc = PagedAllocator(
                n_slots=ec.n_slots, n_blocks=n_blocks,
                block_size=ec.kv_block, s_max=ec.s_max, n_shards=self._dp)
            self.cache = MD.init_paged_cache(
                cfg, ec.n_slots, ec.s_max, n_blocks=n_blocks,
                block_size=ec.kv_block, kv_dtype=ec.kv_dtype)
            self._tab_dirty = True
            admit_fn = (ST.make_slot_admit_paged(cfg)
                        if self._mesh is None else None)
        elif ec.kv_layout == "dense":
            if ec.kv_dtype != "bf16":
                raise ValueError(
                    f"kv_dtype={ec.kv_dtype!r} requires kv_layout='paged' "
                    f"(the dense slot cache stores the model dtype)")
            self.cache = MD.init_slot_cache(cfg, ec.n_slots, ec.s_max)
            admit_fn = (ST.make_slot_admit(cfg)
                        if self._mesh is None else None)
        else:
            raise ValueError(f"kv_layout must be 'dense' or 'paged', got "
                             f"{ec.kv_layout!r}")
        if self._mesh is not None:
            self.cache = self._place_cache(self.cache)
            admit_fn = (
                ST.make_slot_admit_paged_mesh(cfg, self._mesh, self.params,
                                              self.cache)
                if self._alloc is not None else
                ST.make_slot_admit_mesh(cfg, self._mesh, self.params,
                                        self.cache))
        # admission legitimately compiles one specialization per
        # (pad shape, pow2-group) pair; decode entry points get exactly ONE
        self._admit_step = self._guard.wrap_jit(
            "slot_admit", admit_fn, expected_traces=admit_budget)
        if self._mesh is not None:
            decode_fn = ST.make_slot_decode_mesh(
                cfg, self._mesh, self.params, self.cache,
                ec.combine_wire_dtype)
            multi_fn = ST.make_slot_decode_multi_mesh(
                cfg, ec.decode_block, ec.temperature, self._mesh,
                self.params, self.cache, ec.combine_wire_dtype)
        else:
            decode_fn = ST.make_slot_decode(cfg)
            multi_fn = ST.make_slot_decode_multi(cfg, ec.decode_block,
                                                 ec.temperature)
        self._decode = self._guard.wrap_jit(
            "slot_decode", decode_fn, expected_traces=1)
        self._decode_multi = self._guard.wrap_jit(
            "slot_decode_multi", multi_fn, expected_traces=1)

        # ---- speculative decoding (dual artifact, DESIGN.md §10) ----
        self.draft_artifact: Optional[dict] = None
        if ec.spec_draft is not None and draft_params is None:
            from repro.ckpt import checkpoint as CKPT
            draft_cfg, draft_params, self.draft_artifact = \
                CKPT.load_compressed(ec.spec_draft)
        self.spec = draft_params is not None
        self.draft_cfg = self.draft_params = None
        self.cache_draft = None
        if self.spec:
            if draft_cfg is None:
                raise ValueError("draft_params given without draft_cfg")
            draft_cfg = _serve_dispatch(draft_cfg)
            if draft_cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {draft_cfg.vocab_size} != full model vocab "
                    f"{cfg.vocab_size}: the draft must be a compression of "
                    f"the served model, not a different tokenizer")
            if ec.spec_k < 1:
                raise ValueError("spec_k must be >= 1")
            self.draft_cfg, self.draft_params = draft_cfg, draft_params
            if self._mesh is not None:
                from repro.launch import sharding as SH
                SH.validate_ep_params(self.draft_params, self._mesh)
                self.draft_params = jax.device_put(
                    self.draft_params,
                    SH.named(SH.serve_param_pspecs(self.draft_params,
                                                   self._mesh), self._mesh))
            if self._alloc is not None:
                # the draft pool mirrors the full pool's block geometry and
                # shares the ONE allocator table (paging.PagedAllocator
                # docstring): a prefix shared in the full pool is shared in
                # the draft pool at the same block ids
                self.cache_draft = MD.init_paged_cache(
                    draft_cfg, ec.n_slots, ec.s_max, n_blocks=self._alloc.nb,
                    block_size=ec.kv_block, kv_dtype=ec.kv_dtype)
                admit_spec_fn = build_slot_admit_spec_paged(
                    cfg, draft_cfg, ec.temperature)
            else:
                self.cache_draft = MD.init_slot_cache(draft_cfg, ec.n_slots,
                                                      ec.s_max)
                admit_spec_fn = build_slot_admit_spec(cfg, draft_cfg,
                                                      ec.temperature)
            # the builders are wrapped directly (not via the steps.make_*
            # aliases) so the lint analyzer's maker-root walk sees the
            # closure bodies; one spec round per trace, same budget as the
            # single-model entries
            if self._mesh is not None:
                self.cache_draft = self._place_cache(self.cache_draft)
                admit_spec_fn = (
                    ST.make_slot_admit_spec_paged_mesh(
                        cfg, draft_cfg, ec.temperature, self._mesh,
                        self.params, self.draft_params, self.cache,
                        self.cache_draft)
                    if self._alloc is not None else
                    ST.make_slot_admit_spec_mesh(
                        cfg, draft_cfg, ec.temperature, self._mesh,
                        self.params, self.draft_params, self.cache,
                        self.cache_draft))
                decode_spec_fn = ST.make_slot_decode_spec_mesh(
                    cfg, draft_cfg, ec.spec_k, ec.temperature, self._mesh,
                    self.params, self.draft_params, self.cache,
                    self.cache_draft, ec.combine_wire_dtype)
            else:
                decode_spec_fn = build_slot_decode_spec(
                    cfg, draft_cfg, ec.spec_k, ec.temperature)
            self._decode_spec = self._guard.wrap_jit(
                "slot_decode_spec", decode_spec_fn, expected_traces=1)
            self._admit_spec = self._guard.wrap_jit(
                "slot_admit_spec", admit_spec_fn,
                expected_traces=admit_budget)

        self._slot_req: List[Optional[Request]] = [None] * ec.n_slots
        self._last_tok = np.zeros((ec.n_slots,), np.int32)
        self._active = np.zeros((ec.n_slots,), bool)
        # heap of (arrival_time, uid, seq, Request): admission is FIFO by
        # arrival regardless of submission order, O(log n) per push/pop.
        # The monotonic ``seq`` breaks (arrival, uid) ties so heapq never
        # falls through to comparing Request objects. It is a plain int
        # counter (not itertools.count) so snapshot()/restore() can
        # serialize it.
        self._pending: List[Tuple[float, int, int, Request]] = []
        self._seq_n = 0
        self._next_uid = 0
        self._step_count = 0
        self._t0: Optional[float] = None
        # uids of every pending/active request: duplicates are rejected at
        # submission because the sampling key is fold_in(base, uid) — an
        # in-flight collision would alias two requests' Gumbel streams
        self._inflight: set = set()
        # requests shed at SUBMIT time (backpressure) waiting to be
        # returned from the next step's finished list, so run() reports
        # every terminal request exactly once
        self._done_early: List[Request] = []
        # seeded fault-injection plan (serving.faults); None serves clean
        self._faults = faults
        self._zero_poison = np.zeros((ec.n_slots,), bool)
        # per-slot sampling keys: fold_in(base, uid) assigned at admission,
        # so the key travels with the REQUEST — the sampled stream for a
        # (seed, uid, prompt) is identical across engine modes/scheduling
        self._key_base = jax.random.PRNGKey(ec.seed + 1)
        self._slot_keys = np.zeros((ec.n_slots, 2), np.uint32)
        # plan/report extras when booted via from_checkpoint
        self.artifact: Optional[dict] = None
        # step count at the last periodic snapshot (snapshot_every_steps)
        self._last_snap = 0

    def _place_cache(self, cache):
        """Device-place a KV cache tree on the engine mesh per the serve
        layout (slots on "data"; block table replicated)."""
        from repro.launch import sharding as SH
        return jax.device_put(cache, SH.named(
            SH.slot_cache_pspecs(cache, self._mesh), self._mesh))

    def _slot_inputs(self, *host_arrays):
        """Per-slot host arrays onto the device(s) the decode programs read
        them from — an explicit host->device put, made before the trace
        guard arms. In mesh mode they land sharded over "data" (the decode
        programs' in_specs): a put to one chip would leave the guarded call
        a device-to-device reshard, which the guard refuses."""
        if self._mesh is None:
            return tuple(jnp.asarray(a) for a in host_arrays)
        from jax.sharding import NamedSharding, PartitionSpec as P
        sh = NamedSharding(self._mesh, P("data"))
        return tuple(jax.device_put(a, sh) for a in host_arrays)

    @property
    def mesh(self):
        """The serving device mesh (None in single-device mode)."""
        return self._mesh

    # ------------------------------------------------------------------ API

    @classmethod
    def from_checkpoint(cls, directory, ec: Optional[EngineConfig] = None,
                        step: int | None = None) -> "Engine":
        """Boot an engine directly from a ``save_compressed`` artifact.

        The artifact's own ModelConfig (including per-layer merged-expert
        counts) and parameters are used verbatim; ``ec`` only controls
        serving knobs (slots, buckets, dispatch — gather by default). The
        executed plan and compression report are exposed as
        ``engine.artifact``."""
        from repro.ckpt import checkpoint as CKPT
        cfg, params, artifact = CKPT.load_compressed(directory, step=step)
        if ec is None:
            ec = EngineConfig(arch=cfg.name, reduced=False)
        eng = cls(ec, cfg=cfg, params=params)
        eng.artifact = artifact
        return eng

    # -------------------------------------------- snapshot / restore (§12)

    def _req_state(self, r: Request) -> Dict:
        return {
            "uid": int(r.uid), "prompt": [int(t) for t in r.prompt],
            "max_new_tokens": int(r.max_new_tokens),
            "eos_token": None if r.eos_token is None else int(r.eos_token),
            "arrival_time": float(r.arrival_time),
            "deadline": None if r.deadline is None else float(r.deadline),
            "ttl": None if r.ttl is None else float(r.ttl),
            "out_tokens": [int(t) for t in r.out_tokens],
            "t_admitted": r.t_admitted, "t_first_token": r.t_first_token,
            "status": r.status, "deferred": bool(r.deferred),
        }

    def snapshot(self) -> Dict:
        """Serialize the COMPLETE engine state: scheduler (pending heap +
        in-flight requests), slot occupancy, sampling keys, counters, the
        PagedAllocator (free list, refcounts, tables, prefix registry with
        LRU order), and both KV pools — everything needed for
        :meth:`restore` to finish the trace token-for-token identical to an
        uninterrupted run. The host part is JSON-safe; the ``arrays`` part
        holds np copies of the device caches (bf16 preserved exactly).
        Terminal requests are the caller's to keep — they are not engine
        state and are not serialized."""
        reqs: Dict[int, Request] = {}
        for _, _, _, r in self._pending:
            reqs[r.uid] = r
        for r in self._slot_req:
            if r is not None:
                reqs[r.uid] = r
        host = {
            "version": 1,
            "step_count": int(self._step_count),
            "next_uid": int(self._next_uid),
            "seq": int(self._seq_n),
            "counters": {k: int(v) for k, v in self.counters.items()},
            "requests": [self._req_state(r) for _, r in sorted(reqs.items())],
            "pending": [[float(a), int(u), int(s)]
                        for a, u, s, _ in self._pending],
            "slots": [None if r is None else int(r.uid)
                      for r in self._slot_req],
            "last_tok": [int(t) for t in self._last_tok],
            "active": [bool(a) for a in self._active],
            "slot_keys": self._slot_keys.tolist(),
            "alloc": (None if self._alloc is None
                      else self._alloc.state_dict()),
        }
        arrays = {"cache": jax.tree.map(
            lambda a: np.asarray(jax.device_get(a)), self.cache)}
        if self.cache_draft is not None:
            arrays["cache_draft"] = jax.tree.map(
                lambda a: np.asarray(jax.device_get(a)), self.cache_draft)
        return {"ec": dataclasses.asdict(self.ec), "host": host,
                "arrays": arrays}

    def save_snapshot(self, directory):
        """Persist :meth:`snapshot` through the checkpoint layer (atomic,
        COMMIT-marked, digest-verified on load). Returns the committed
        directory."""
        from repro.ckpt import checkpoint as CKPT
        snap = self.snapshot()
        ecd = dict(snap["ec"])
        ecd["prefill_buckets"] = list(ecd["prefill_buckets"])
        return CKPT.save(directory, self._step_count, snap["arrays"],
                         extras={"engine": {"ec": ecd,
                                            "host": snap["host"]}},
                         keep=0)

    def _maybe_snapshot(self) -> None:
        """Periodic background checkpointing (§12): with
        ``snapshot_every_steps > 0``, persist the full engine snapshot to
        ``snapshot_dir`` through the staged-commit checkpoint path whenever
        the step clock has advanced that far since the last one. Called at
        every step boundary, so a crash between snapshots loses at most one
        interval of committed work — :meth:`restore` on the directory
        resumes token-for-token."""
        every = self.ec.snapshot_every_steps
        if every > 0 and self._step_count - self._last_snap >= every:
            self.save_snapshot(self.ec.snapshot_dir)
            self._last_snap = self._step_count

    @classmethod
    def restore(cls, snap, cfg=None, params=None, draft_cfg=None,
                draft_params=None, faults: Optional[FaultPlan] = None,
                verify: bool = True) -> "Engine":
        """Rebuild an engine from :meth:`snapshot` output (dict) or a
        :meth:`save_snapshot` directory (path). Model parameters are NOT
        part of the snapshot — pass the same ``params``/``draft_params``
        the snapshotted engine served (or rely on the seeded ``MD.init``
        default for test-sized models). Disk restores verify the recorded
        ``tree_digest`` and refuse corrupted snapshots unless
        ``verify=False``."""
        if not isinstance(snap, dict):
            from repro.ckpt import checkpoint as CKPT
            arrays, extras = CKPT.load(snap, verify=verify)
            eng_x = extras.get("engine")
            if eng_x is None:
                raise ValueError(f"{snap} holds no engine snapshot "
                                 f"(missing 'engine' extras)")
            snap = {"ec": eng_x["ec"], "host": eng_x["host"],
                    "arrays": arrays}
        ecd = dict(snap["ec"])
        ecd["prefill_buckets"] = tuple(ecd["prefill_buckets"])
        eng = cls(EngineConfig(**ecd), cfg=cfg, params=params,
                  draft_cfg=draft_cfg, draft_params=draft_params,
                  faults=faults)
        eng._load_snapshot(snap)
        return eng

    def _load_snapshot(self, snap: Dict) -> None:
        host = snap["host"]
        if host.get("version") != 1:
            raise ValueError(f"unknown snapshot version "
                             f"{host.get('version')!r}")
        self._step_count = int(host["step_count"])
        self._next_uid = int(host["next_uid"])
        self._seq_n = int(host["seq"])
        self.counters.update({k: int(v)
                              for k, v in host["counters"].items()})
        reqs: Dict[int, Request] = {}
        for st in host["requests"]:
            r = Request(
                uid=int(st["uid"]),
                prompt=np.asarray(st["prompt"], np.int32),
                max_new_tokens=int(st["max_new_tokens"]),
                eos_token=(None if st["eos_token"] is None
                           else int(st["eos_token"])),
                arrival_time=float(st["arrival_time"]),
                deadline=(None if st["deadline"] is None
                          else float(st["deadline"])),
                ttl=None if st["ttl"] is None else float(st["ttl"]))
            r.out_tokens = [int(t) for t in st["out_tokens"]]
            r.t_admitted = st["t_admitted"]
            r.t_first_token = st["t_first_token"]
            r.status = st["status"]
            r.deferred = bool(st["deferred"])
            reqs[r.uid] = r
        self._pending = [(float(a), int(u), int(s), reqs[int(u)])
                         for a, u, s in host["pending"]]
        heapq.heapify(self._pending)
        self._slot_req = [None if u is None else reqs[int(u)]
                          for u in host["slots"]]
        self._last_tok = np.asarray(host["last_tok"], np.int32)
        self._active = np.asarray(host["active"], bool)
        self._slot_keys = np.asarray(host["slot_keys"], np.uint32)
        self._inflight = set(reqs)
        if self._alloc is not None:
            if host["alloc"] is None:
                raise ValueError("snapshot has no allocator state but the "
                                 "restored engine is paged")
            self._alloc.load_state(host["alloc"])
            self._tab_dirty = True
        arrays = snap["arrays"]
        self.cache = jax.tree.map(jnp.asarray, arrays["cache"])
        if self.cache_draft is not None:
            self.cache_draft = jax.tree.map(jnp.asarray,
                                            arrays["cache_draft"])
        if self._mesh is not None:
            self.cache = self._place_cache(self.cache)
            if self.cache_draft is not None:
                self.cache_draft = self._place_cache(self.cache_draft)
        # the restored step count is the new snapshot epoch — without this a
        # periodic-snapshot engine would re-snapshot at its very first step
        self._last_snap = self._step_count

    @property
    def n_active(self) -> int:
        return int(self._active.sum())

    @property
    def n_pending(self) -> int:
        return len(self._pending)

    @property
    def idle(self) -> bool:
        return (not self._pending and not self._active.any()
                and not self._done_early)

    @property
    def steps(self) -> int:
        """Decode steps taken so far (the 'steps' clock's current time)."""
        return self._step_count

    @property
    def host_dispatches_per_token(self) -> float:
        """Host<->device crossings (jit dispatches + readbacks) per
        generated token so far."""
        c = self.counters
        return (c["device_calls"] + c["host_syncs"]) / max(c["tokens_out"], 1)

    def _validate_request(self, prompt: np.ndarray,
                          max_new_tokens: int) -> None:
        """Reject requests that cannot be served, with the reason spelled
        out. A prompt must carry only real vocabulary ids (out-of-range ids
        would silently clamp at the embedding gather and serve garbage),
        must fit its prefill bucket AND leave generation room in the slot;
        anything longer used to be silently clamped by ``bucket_for`` and
        would corrupt the slot — now it is an error at SUBMISSION time (the
        only place the caller can react). All raises are typed
        (``core.errors``) and subclass ``ValueError`` for compatibility."""
        if prompt.size == 0:
            raise ERR.RequestValidationError("empty prompt")
        if max_new_tokens < 1:
            raise ERR.RequestValidationError("max_new_tokens must be >= 1")
        lo, hi = int(prompt.min()), int(prompt.max())
        if lo < 0 or hi >= self.cfg.vocab_size:
            raise ERR.InvalidTokenError(
                f"prompt token ids must lie in [0, {self.cfg.vocab_size}) "
                f"(vocab size of the served model); got ids spanning "
                f"[{lo}, {hi}]")
        big = min(max(self._buckets, default=1), self.ec.s_max)
        if prompt.size > self.ec.s_max:
            raise ERR.RequestValidationError(
                f"prompt length {prompt.size} cannot fit any prefill bucket: "
                f"the largest admissible bucket is capped by slot capacity "
                f"s_max={self.ec.s_max} (declared buckets "
                f"{tuple(self._buckets)} top out at {big}); shorten the "
                f"prompt or raise s_max")
        # a request consumes prompt + max_new - 1 KV rows: positions
        # 0 .. prompt+max_new-2 are written (the FINAL sampled token is
        # emitted but never fed back, so its KV row is never needed). The
        # bound is therefore s_max + 1, not s_max — the old check rejected
        # the exactly-fitting request at the boundary.
        if prompt.size + max_new_tokens > self.ec.s_max + 1:
            raise ERR.RequestValidationError(
                f"prompt ({prompt.size}) + max_new_tokens ({max_new_tokens}) "
                f"needs {prompt.size + max_new_tokens - 1} KV rows, more "
                f"than slot capacity s_max={self.ec.s_max} (the final "
                f"sampled token occupies no row, so the bound is "
                f"prompt + max_new <= s_max + 1)")
        # speculative verify writes up to spec_k lookahead rows past the
        # committed stream (rows pos0 .. pos0+spec_k with pos0 up to
        # prompt+max_new-2), so spec mode needs that much extra headroom —
        # without this check the last verify rounds of a capacity-filling
        # request scatter past s_max (dense: clipped into the last row,
        # paged: dropped at the sentinel), silently corrupting or staling
        # the KV its own acceptance then reads
        if self.spec and (prompt.size + max_new_tokens + self.ec.spec_k
                          > self.ec.s_max + 1):
            raise ERR.RequestValidationError(
                f"prompt ({prompt.size}) + max_new_tokens ({max_new_tokens}) "
                f"+ spec_k ({self.ec.spec_k}) exceeds s_max + 1 = "
                f"{self.ec.s_max + 1}: speculative verify needs spec_k KV "
                f"rows of lookahead headroom past the committed stream; "
                f"shorten the request, lower spec_k, or raise s_max")

    def submit(self, prompt, max_new_tokens: int, eos_token: int | None = None,
               arrival_time: float = 0.0, uid: int | None = None,
               deadline: float | None = None,
               ttl: float | None = None) -> Request:
        """Queue one request. ``deadline``/``ttl`` bound how long it may
        WAIT for admission (engine-clock units); past it the engine sheds
        the request with a reason instead of serving stale work. Raises
        typed errors (``core.errors``): RequestValidationError /
        InvalidTokenError for unservable requests, DuplicateUidError for an
        in-flight uid collision, QueueFullError when the bounded pending
        queue rejects under the backpressure policy."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        self._validate_request(prompt, max_new_tokens)
        if uid is not None and uid in self._inflight:
            raise ERR.DuplicateUidError(
                f"uid {uid} is already in flight (pending or active): "
                f"in-flight uids must be unique — the sampling key is "
                f"fold_in(base, uid), so a duplicate would alias two "
                f"requests' Gumbel noise streams (DESIGN.md §10/§12)")
        self._apply_backpressure()
        if uid is None:
            uid = self._next_uid
        self._next_uid = max(self._next_uid, uid) + 1
        req = Request(uid=uid, prompt=prompt, max_new_tokens=max_new_tokens,
                      eos_token=eos_token, arrival_time=arrival_time,
                      deadline=deadline, ttl=ttl)
        self._enqueue(req)
        return req

    def _enqueue(self, req: Request) -> None:
        if req.uid in self._inflight:
            raise ERR.DuplicateUidError(
                f"uid {req.uid} is already in flight (pending or active)")
        self._inflight.add(req.uid)
        self._seq_n += 1
        heapq.heappush(self._pending,
                       (req.arrival_time, req.uid, self._seq_n, req))

    def _apply_backpressure(self) -> None:
        """Enforce the bounded pending queue (§12 shed policy). With
        ``backpressure='shed_expired'`` a full queue first sheds every
        already-expired pending request (they could never be admitted
        anyway), making room without dropping live work; ``'reject_new'``
        — and a still-full queue after shedding — raises QueueFullError."""
        if not self.ec.max_pending \
                or len(self._pending) < self.ec.max_pending:
            return
        if self.ec.backpressure == "shed_expired":
            now = self._now()
            kept = []
            for entry in self._pending:
                r = entry[-1]
                dl = r.effective_deadline
                if dl is not None and now > dl:
                    self._shed(r, now,
                               "pool_pressure" if r.deferred else "deadline")
                    self._done_early.append(r)
                else:
                    kept.append(entry)
            if len(kept) < len(self._pending):
                self._pending = kept
                heapq.heapify(self._pending)
        if len(self._pending) >= self.ec.max_pending:
            raise ERR.QueueFullError(
                f"pending queue full "
                f"({len(self._pending)}/{self.ec.max_pending}) and "
                f"backpressure policy {self.ec.backpressure!r} could not "
                f"make room")

    def _shed(self, req: Request, now: float, reason: str) -> None:
        """Terminate a pending request without serving it (§12). Shed
        requests keep any tokens they never had (none — shedding only
        happens before admission), carry ``status='shed'`` plus the
        reason, and count toward ``counters['shed']``."""
        req.status = "shed"
        req.shed_reason = reason
        req.finish_reason = "shed"
        req.t_finished = now
        self.counters["shed"] += 1
        self._inflight.discard(req.uid)

    def step(self, now: float | None = None) -> List[Request]:
        """Admit due requests, run ONE decode step, evict finished.
        Returns the requests that finished during this step. This is the
        step-at-a-time reference loop; :meth:`step_block` is the fused
        production path (``run`` picks by ``decode_block``)."""
        now = self._now() if now is None else now
        finished = self._admit(now)
        quarantined: List[Request] = []
        if self._active.any():
            # host->device conversions happen HERE, before the guard arms:
            # inside the guarded call every argument is already device-side
            self._sync_tab()
            toks, act, poison = self._slot_inputs(
                self._last_tok, self._active, self._poison_mask(1))
            logits, aux, self.cache = self._with_retries(
                "decode", "slot_decode",
                lambda: self._guard.run("slot_decode", self._decode,
                                        self.params, self.cache, toks, act,
                                        poison))
            self.counters["device_calls"] += 1
            sentinel = self.ec.numeric_sentinel != "off"
            aux_np = None
            if self.ec.temperature <= 0.0:
                aux_np = np.asarray(aux)    # ONE readback: (greedy, finite)
                self.counters["host_syncs"] += 1
                next_toks = aux_np[:, 0]
            else:
                next_toks = self._sample(logits, None, self._slot_keys,
                                         self._positions())
                self.counters["host_syncs"] += 1
                if sentinel:
                    # reference-loop-only extra readback: the fused paths
                    # carry the sentinel inside their one block transfer
                    aux_np = np.asarray(aux)
                    self.counters["host_syncs"] += 1
            for slot in np.flatnonzero(self._active):
                req = self._slot_req[slot]
                if sentinel and aux_np is not None and not aux_np[slot, 1]:
                    quarantined.append(self._quarantine(slot, now))
                    finished.append(req)
                    continue
                tok = int(next_toks[slot])
                req.out_tokens.append(tok)
                self.counters["tokens_out"] += 1
                self._last_tok[slot] = tok
                if self._is_done(req, tok):
                    self._evict(slot, now)
                    finished.append(req)
        self._step_count += 1
        self._maybe_snapshot()
        self._raise_if_strict(quarantined)
        return finished

    def step_block(self, now: float | None = None) -> List[Request]:
        """Admit due requests, then run ``decode_block`` fused decode steps
        in ONE device call (DESIGN.md §7). Returns finished requests; their
        ``t_finished`` is the block-start clock plus the inner step they
        stopped at, so step accounting matches the per-step loop."""
        now = self._now() if now is None else now
        finished = self._admit(now)
        K = self.ec.decode_block
        if not self._active.any():
            # nothing to decode: advance one step so arrival admission keeps
            # fine-grained timing while the engine drains the future queue
            self._step_count += 1
            self._maybe_snapshot()
            return finished
        n = self.ec.n_slots
        rem = np.zeros((n,), np.int32)
        eos = np.full((n,), -1, np.int32)
        slots = np.flatnonzero(self._active)
        for s in slots:
            req = self._slot_req[s]
            rem[s] = req.max_new_tokens - len(req.out_tokens)
            eos[s] = -1 if req.eos_token is None else req.eos_token
        # convert np inputs OUTSIDE the guarded region (explicit H2D); the
        # guarded fused block itself must touch the host zero times
        self._sync_tab()
        args = (self.params, self.cache) + self._slot_inputs(
            self._last_tok, self._active, rem, eos, self._slot_keys,
            self._poison_mask(K))
        block, _, self.cache = self._with_retries(
            "decode", "slot_decode_multi",
            lambda: self._guard.run("slot_decode_multi", self._decode_multi,
                                    *args))
        self.counters["device_calls"] += 1
        # ONE readback: [K, B, (tok, emit, finite)] — the numeric sentinel
        # lane rides the same transfer (§12: zero additional host syncs)
        block_np = np.asarray(block)
        self.counters["host_syncs"] += 1
        sentinel = self.ec.numeric_sentinel != "off"
        quarantined: List[Request] = []
        for s in slots:
            req = self._slot_req[s]
            for j in range(K):
                if not block_np[j, s, 1]:
                    break
                t_j = now + j if self.ec.clock == "steps" else self._now()
                if sentinel and not block_np[j, s, 2]:
                    # tokens 0..j-1 already matched the fault-free stream;
                    # token j was sampled from non-finite logits — truncate
                    # there and quarantine the slot
                    quarantined.append(self._quarantine(s, t_j))
                    finished.append(req)
                    break
                tok = int(block_np[j, s, 0])
                req.out_tokens.append(tok)
                self.counters["tokens_out"] += 1
                self._last_tok[s] = tok
                if self._is_done(req, tok):
                    # steps clock: finish = block start + inner step. Wall
                    # clock has no per-inner-step timestamps (the block is
                    # one device call) — stamp the post-block wall time.
                    self._evict(s, t_j)
                    finished.append(req)
                    break
        self._step_count += K
        self._maybe_snapshot()
        self._raise_if_strict(quarantined)
        return finished

    def step_spec(self, now: float | None = None) -> List[Request]:
        """Admit due requests, then run ONE fused draft/verify round
        (DESIGN.md §10): ``spec_k`` draft-model decode steps, one full-model
        verify forward, acceptance/rollback — all in one device call.
        Returns finished requests. The step clock advances by ``spec_k``
        per round (the round's draft depth), so Poisson arrival traces in
        step units drain at the fused block's granularity, like §7."""
        now = self._now() if now is None else now
        finished = self._admit(now)
        K = self.ec.spec_k
        if not self._active.any():
            self._step_count += 1
            self._maybe_snapshot()
            return finished
        n = self.ec.n_slots
        rem = np.zeros((n,), np.int32)
        eos = np.full((n,), -1, np.int32)
        slots = np.flatnonzero(self._active)
        for s in slots:
            req = self._slot_req[s]
            rem[s] = req.max_new_tokens - len(req.out_tokens)
            eos[s] = -1 if req.eos_token is None else req.eos_token
        self._sync_tab()
        args = (self.params, self.draft_params, self.cache,
                self.cache_draft) + self._slot_inputs(
            self._last_tok, self._active, rem, eos, self._slot_keys,
            self._poison_mask(K))
        block, _, self.cache, self.cache_draft = self._with_retries(
            "decode", "slot_decode_spec",
            lambda: self._guard.run("slot_decode_spec", self._decode_spec,
                                    *args))
        self.counters["device_calls"] += 1
        # ONE readback: rows 0..K-1 = (token, emitted, finite) like
        # step_block (sentinel lane over the VERIFY logits), row K =
        # (accepted drafts, drafted, 1) per slot
        block_np = np.asarray(block)
        self.counters["host_syncs"] += 1
        sentinel = self.ec.numeric_sentinel != "off"
        quarantined: List[Request] = []
        for s in slots:
            req = self._slot_req[s]
            for j in range(K):
                if not block_np[j, s, 1]:
                    break
                t_j = now + j if self.ec.clock == "steps" else self._now()
                if sentinel and not block_np[j, s, 2]:
                    quarantined.append(self._quarantine(s, t_j))
                    finished.append(req)
                    break
                tok = int(block_np[j, s, 0])
                req.out_tokens.append(tok)
                self.counters["tokens_out"] += 1
                self._last_tok[s] = tok
                if self._is_done(req, tok):
                    self._evict(s, t_j)
                    finished.append(req)
                    break
            n_match = int(block_np[K, s, 0])
            drafted = int(block_np[K, s, 1])
            self.counters["tokens_drafted"] += drafted
            self.counters["tokens_accepted"] += n_match
            self.counters["tokens_rolled_back"] += drafted - n_match
        self._step_count += K
        self._maybe_snapshot()
        self._raise_if_strict(quarantined)
        return finished

    @property
    def acceptance_rate(self) -> float:
        """Fraction of draft proposals the full model accepted so far."""
        return (self.counters["tokens_accepted"]
                / max(self.counters["tokens_drafted"], 1))

    def run(self, requests: Sequence[Request] | None = None) -> List[Request]:
        """Drive until every pending/submitted request completes."""
        if requests:
            # externally built Request objects get the same admission
            # contract as submit() — an oversized prompt must fail here, not
            # deep inside a prefill scatter. Validate the WHOLE batch before
            # enqueuing anything, so a rejected call leaves the engine
            # exactly as it found it (no half-enqueued requests).
            seen = set()
            for r in requests:
                self._validate_request(np.asarray(r.prompt, np.int32),
                                       r.max_new_tokens)
                if r.uid in self._inflight or r.uid in seen:
                    raise ERR.DuplicateUidError(
                        f"uid {r.uid} is already in flight (or appears "
                        f"twice in this batch): in-flight uids must be "
                        f"unique — the sampling key is fold_in(base, uid)")
                seen.add(r.uid)
            for r in requests:
                self._enqueue(r)
        if self.spec:
            advance = self.step_spec
        elif self.ec.decode_block > 1:
            advance = self.step_block
        else:
            advance = self.step
        done: List[Request] = []
        while not self.idle:
            done.extend(advance())
        return sorted(done, key=lambda r: r.uid)

    def expert_weight_dtypes(self, params=None) -> Tuple[str, str]:
        """(prefix, suffix/uncompressed) expert-table storage dtypes,
        inferred from the parameter tree ('int8' when a stack carries the
        quantized ``qexp`` subtree, DESIGN.md §8). ``params`` defaults to
        the served model; pass ``self.draft_params`` for the draft."""
        params = self.params if params is None else params

        def one(stack_key):
            stack = params.get(stack_key)
            if stack is None or "moe" not in stack:
                return "bf16"
            return "int8" if "qexp" in stack["moe"] else "bf16"
        return one("stack"), one("stack_c" if "stack_c" in params
                                 else "stack")

    @property
    def kv_dtype_served(self) -> str:
        """KV storage dtype actually in the cache ('int8' only for the
        quantized paged pool)."""
        return ("int8" if self._alloc is not None
                and self.ec.kv_dtype == "int8" else "bf16")

    @property
    def paging_stats(self) -> Dict[str, int]:
        """Allocator telemetry (prefix hits/rows shared, deferrals, registry
        evictions, CoW copies, free blocks); empty in dense layout."""
        if self._alloc is None:
            return {}
        return dict(self._alloc.stats, free_blocks=self._alloc.free_blocks)

    def _bench_tab(self) -> jax.Array:
        """Scratch identity block table for the admission-bypassing
        benchmarks: block ``j`` of slot ``s`` maps to pool block
        ``(s*mb + j) % n_blocks`` (the default pool size makes the modulus a
        no-op; a smaller pool aliases blocks across slots, which is fine for
        a throughput measurement — the bytes moved per step are identical)."""
        n, mb, nb = self.ec.n_slots, self._alloc.mb, self._alloc.nb
        tab = np.full((n + 1, mb), nb, np.int32)
        tab[:n] = np.arange(n * mb, dtype=np.int32).reshape(n, mb) % nb
        return jnp.asarray(tab)

    def modeled_decode_traffic(self, pos: int | None = None) -> Dict[str, float]:
        """Analytic HBM bytes for one steady-state decode step of this
        engine (``launch.hlo_analysis.decode_traffic_model`` at the served
        config, weight dtypes read off the actual parameter tree, KV dtype
        off the cache layout). ``pos`` defaults to mid-cache, matching
        :meth:`bench_decode`'s scratch state."""
        from repro.launch.hlo_analysis import decode_traffic_model
        prefix_dt, suffix_dt = self.expert_weight_dtypes()
        return decode_traffic_model(
            self.cfg, n_slots=self.ec.n_slots,
            pos=self.ec.s_max // 2 if pos is None else pos,
            weight_dtype=suffix_dt, prefix_weight_dtype=prefix_dt,
            kv_dtype=self.kv_dtype_served, **self._mesh_model_kwargs())

    def bench_decode(self, iters: int = 50,
                     k_steps: int | None = None) -> Dict[str, float]:
        """Steady-state decode throughput with every slot active, bypassing
        admission — isolates the jitted fused loop from scheduler overhead.

        Runs ``iters`` fused ``k_steps``-step blocks (default: the engine's
        ``decode_block``) on a scratch copy of the cache and returns
        ``{"tok_per_s", "dispatches_per_s", "host_dispatches_per_token",
        "k_steps"}`` — tokens/sec AND host dispatches/sec, since the fused
        loop improves the latter even where CPU model math dominates the
        former — plus the MODELED HBM traffic of the served config
        (``hbm_bytes_per_token``, ``moe_expert_bytes_per_token``) and the
        bandwidth-roofline ceiling it implies
        (``roofline_tok_per_s = 1/max(t_memory, t_compute)`` from
        ``hlo_analysis.roofline_terms``, with ``roofline_fraction`` = the
        measured tok/s against it; on CPU that fraction is noise — the
        modeled bytes are the portable signal). The ``pos`` reset needed to
        keep the scratch cache in bounds is fused INTO the jitted block (no
        host-side clamp op inside the timed loop, which previously added a
        dispatch per iteration and skewed the measurement)."""
        K = int(self.ec.decode_block if k_steps is None else k_steps)
        n = self.ec.n_slots
        s_max = self.ec.s_max
        if K >= s_max // 2:
            raise ValueError(f"k_steps={K} too large for s_max={s_max}")
        multi = (ST.make_slot_decode_multi_mesh(
                     self.cfg, K, self.ec.temperature, self._mesh,
                     self.params, self.cache, self.ec.combine_wire_dtype)
                 if self._mesh is not None else
                 ST.make_slot_decode_multi(self.cfg, K, self.ec.temperature))

        def block(params, cache, toks, act, rem, eos, keys, poison):
            # keep pos in bounds ON DEVICE: reset to mid-cache before the
            # scanned steps would run past the last slot row
            pos = cache["pos"]
            pos = jnp.where(pos + K >= s_max, s_max // 2, pos)
            return multi(params, dict(cache, pos=pos), toks, act, rem, eos,
                         keys, poison)

        fn = jax.jit(block)
        cache = jax.tree.map(jnp.copy, self.cache)
        cache["pos"] = jnp.full((n,), s_max // 2, jnp.int32)
        if self._alloc is not None:
            cache["tab"] = self._bench_tab()
        toks = jnp.zeros((n,), jnp.int32)
        act = jnp.ones((n,), bool)
        rem = jnp.full((n,), np.iinfo(np.int32).max // 2, jnp.int32)
        eos = jnp.full((n,), -1, jnp.int32)
        poison = jnp.zeros((n,), bool)
        # seeded like every other sampled path (EngineConfig.seed), so a
        # temperature>0 benchmark decode is reproducible run to run
        keys = jax.random.split(jax.random.PRNGKey(self.ec.seed), n)
        out, _, cache = fn(self.params, cache, toks, act, rem, eos, keys,
                           poison)
        jax.block_until_ready(out)                                   # warm
        # the timed loop runs under transfer_guard("disallow"): a benchmark
        # number that silently included an implicit host transfer per block
        # would overstate dispatch savings — better to fail loudly here
        with jax.transfer_guard("disallow"):
            t0 = time.perf_counter()
            for _ in range(iters):
                out, _, cache = fn(self.params, cache, toks, act, rem, eos,
                                   keys, poison)
            jax.block_until_ready(out)
            dt = time.perf_counter() - t0
        tok_per_s = n * K * iters / dt
        from repro.launch.hlo_analysis import roofline_terms
        traffic = self.modeled_decode_traffic()
        terms = roofline_terms(traffic["flops_per_token"],
                               traffic["bytes_per_token"],
                               traffic["interconnect_bytes_per_token"])
        roof = 1.0 / max(terms["t_memory_s"], terms["t_compute_s"],
                         terms["t_collective_s"], 1e-30)
        return {
            "tok_per_s": tok_per_s,
            "dispatches_per_s": iters / dt,
            # 1 jitted call + 1 readback per block — same crossings-counting
            # definition as Engine.host_dispatches_per_token
            "host_dispatches_per_token": 2.0 / (n * K),
            "k_steps": K,
            # modeled traffic (TPU roofline target, not a host measurement)
            "hbm_bytes_per_token": traffic["bytes_per_token"],
            "moe_expert_bytes_per_token":
                traffic["moe_expert_bytes_per_token"],
            "interconnect_bytes_per_token":
                traffic["interconnect_bytes_per_token"],
            "roofline_tok_per_s": roof,
            "roofline_fraction": tok_per_s / roof,
        }

    def modeled_spec_decode_traffic(self, mean_committed: float,
                                    pos: int | None = None,
                                    n_slots: int | None = None
                                    ) -> Dict[str, float]:
        """Analytic HBM bytes per COMMITTED token for one draft/verify
        round of this engine (``hlo_analysis.spec_decode_traffic_model``,
        weight dtypes read off both parameter trees). ``mean_committed``
        is the measured tokens committed per slot per round — acceptance
        is an empirical property of the (draft, model) pair, so the model
        takes it as input rather than guessing. ``n_slots`` lets callers
        re-model the same artifacts at deployment batch sizes (the
        expert-stream saturation point moves with it, DESIGN.md §10)."""
        from repro.launch.hlo_analysis import spec_decode_traffic_model
        prefix_dt, suffix_dt = self.expert_weight_dtypes()
        d_prefix_dt, d_suffix_dt = self.expert_weight_dtypes(
            self.draft_params)
        return spec_decode_traffic_model(
            self.cfg, self.draft_cfg, k_draft=self.ec.spec_k,
            n_slots=self.ec.n_slots if n_slots is None else n_slots,
            pos=self.ec.s_max // 2 if pos is None else pos,
            mean_committed=mean_committed,
            weight_dtype=suffix_dt, prefix_weight_dtype=prefix_dt,
            draft_weight_dtype=d_suffix_dt,
            draft_prefix_weight_dtype=d_prefix_dt,
            kv_dtype=self.kv_dtype_served, **self._mesh_model_kwargs())

    def _mesh_model_kwargs(self) -> Dict[str, float]:
        """EP/DP degrees of the serving mesh for the analytic traffic
        models (1/1 when single-device)."""
        if self._mesh is None:
            return {}
        return dict(ep_degree=int(self._mesh.shape.get("model", 1)),
                    dp_degree=int(self._mesh.shape.get("data", 1)),
                    combine_wire_dtype=self.ec.combine_wire_dtype)

    def bench_spec_decode(self, iters: int = 50) -> Dict[str, float]:
        """Steady-state speculative throughput with every slot active,
        bypassing admission — the spec-mode sibling of :meth:`bench_decode`.

        Runs ``iters`` fused draft/verify rounds on scratch copies of both
        caches. The next round's input token (the last committed verify
        sample) is computed ON DEVICE inside the jitted wrapper, so the
        timed loop has zero host readbacks — the per-round blocks are
        collected device-side and summed after the clock stops. Returns
        measured committed tok/s, per-round acceptance telemetry, and the
        modeled spec traffic of the served artifact pair at the MEASURED
        acceptance (``spec_bytes_per_token``, ``modeled_speedup`` vs the
        full-model decode roofline; on CPU the measured tok/s is
        FLOPs-bound and the modeled bytes are the portable signal, same
        stance as :meth:`bench_decode`)."""
        if not self.spec:
            raise ValueError("bench_spec_decode requires spec mode "
                             "(spec_draft / draft_params)")
        K = self.ec.spec_k
        n = self.ec.n_slots
        s_max = self.ec.s_max
        if K + 1 >= s_max // 2:
            raise ValueError(f"spec_k={K} too large for s_max={s_max}")
        spec = (ST.make_slot_decode_spec_mesh(
                    self.cfg, self.draft_cfg, K, self.ec.temperature,
                    self._mesh, self.params, self.draft_params, self.cache,
                    self.cache_draft, self.ec.combine_wire_dtype)
                if self._mesh is not None else
                ST.make_slot_decode_spec(self.cfg, self.draft_cfg, K,
                                         self.ec.temperature))

        def round_(params, dparams, cache, dcache, toks, act, rem, eos,
                   keys, poison):
            # keep pos in bounds ON DEVICE; both caches share one pos by
            # construction, so reset both from the full model's
            pos = cache["pos"]
            pos = jnp.where(pos + K + 1 >= s_max, s_max // 2, pos)
            block, _, cache, dcache = spec(
                params, dparams, dict(cache, pos=pos), dict(dcache, pos=pos),
                toks, act, rem, eos, keys, poison)
            # next input token = last committed verify sample, computed on
            # device so the timed loop never reads the block back
            emit = block[:K, :, 1]
            n_c = jnp.sum(emit, axis=0)
            last = jnp.take_along_axis(
                block[:K, :, 0], jnp.maximum(n_c - 1, 0)[None, :], axis=0)[0]
            toks = jnp.where(n_c > 0, last, toks)
            return block, toks, cache, dcache

        fn = jax.jit(round_)
        cache = jax.tree.map(jnp.copy, self.cache)
        cache["pos"] = jnp.full((n,), s_max // 2, jnp.int32)
        dcache = jax.tree.map(jnp.copy, self.cache_draft)
        if self._alloc is not None:
            cache["tab"] = dcache["tab"] = self._bench_tab()
        toks = jnp.zeros((n,), jnp.int32)
        act = jnp.ones((n,), bool)
        rem = jnp.full((n,), np.iinfo(np.int32).max // 2, jnp.int32)
        eos = jnp.full((n,), -1, jnp.int32)
        poison = jnp.zeros((n,), bool)
        keys = jax.random.split(jax.random.PRNGKey(self.ec.seed), n)
        block, toks, cache, dcache = fn(self.params, self.draft_params,
                                        cache, dcache, toks, act, rem, eos,
                                        keys, poison)
        jax.block_until_ready(block)                                 # warm
        blocks = []
        with jax.transfer_guard("disallow"):
            t0 = time.perf_counter()
            for _ in range(iters):
                block, toks, cache, dcache = fn(
                    self.params, self.draft_params, cache, dcache, toks,
                    act, rem, eos, keys, poison)
                blocks.append(block)
            jax.block_until_ready(block)
            dt = time.perf_counter() - t0
        committed = drafted = accepted = 0
        for b in blocks:
            bn = np.asarray(b)
            committed += int(bn[:K, :, 1].sum())
            accepted += int(bn[K, :, 0].sum())
            drafted += int(bn[K, :, 1].sum())
        mean_committed = committed / (iters * n)
        traffic = self.modeled_spec_decode_traffic(mean_committed)
        return {
            "tok_per_s": committed / dt,
            "rounds_per_s": iters / dt,
            "acceptance_rate": accepted / max(drafted, 1),
            "mean_committed_per_round": mean_committed,
            # 1 jitted call + 1 readback per round
            "host_dispatches_per_token": 2.0 * iters / max(committed, 1),
            "k_draft": K,
            "spec_bytes_per_token": traffic["bytes_per_token"],
            "baseline_bytes_per_token": traffic["baseline_bytes_per_token"],
            "modeled_speedup": traffic["modeled_speedup"],
        }

    # ------------------------------------------------------------ internals

    def _now(self) -> float:
        if self.ec.clock == "steps":
            return float(self._step_count)
        if self._t0 is None:
            self._t0 = time.perf_counter()
        return time.perf_counter() - self._t0

    def bucket_for(self, n: int) -> int:
        """Prefill pad length for an ``n``-token prompt (the jit
        specialization it will compile into): the smallest member of
        ``steps.admit_pad_shapes`` covering ``n``. Lengths beyond ``s_max``
        have no admissible shape and raise (``submit`` rejects them up front
        with the full context — this is the fail-closed backstop for callers
        probing bucket shapes directly). FAILS CLOSED on table
        non-membership too: returning any length outside the table would
        silently blow the trace budget the guard enforces, so drift between
        the two is an error here, never a retrace later."""
        if n > self.ec.s_max:
            raise ValueError(
                f"no prefill bucket fits {n} tokens (s_max={self.ec.s_max})")
        for b in self._pad_shapes:
            if n <= b:
                return b
        raise AssertionError(
            f"admission pad-shape table {self._pad_shapes} covers no "
            f"length <= s_max={self.ec.s_max}; steps.admit_pad_shapes "
            f"broke its own invariant")

    def _positions(self) -> np.ndarray:
        """Sequence position the NEXT sampled token will occupy, per slot —
        the host-side mirror of the device loops' post-step ``cache['pos']``
        (prompt length + tokens generated so far)."""
        q = np.zeros((self.ec.n_slots,), np.int32)
        for s in np.flatnonzero(self._active):
            req = self._slot_req[s]
            q[s] = req.n_prompt + len(req.out_tokens)
        return q

    def _sample(self, logits, greedy, keys, positions) -> np.ndarray:
        """Host-side sampling fallback for the step-at-a-time loop and
        (non-spec) admission. Runs the SAME ``steps.sample_tokens`` the
        fused device loops run, on the same (key, position) pairs, so
        host- and device-sampled streams agree bitwise at any
        temperature."""
        if self.ec.temperature <= 0.0:
            return np.asarray(greedy)
        toks = ST.sample_tokens(jnp.asarray(logits), self.ec.temperature,
                                jnp.asarray(keys), jnp.asarray(positions))
        return np.asarray(toks)

    def _is_done(self, req: Request, tok: int) -> bool:
        if req.eos_token is not None and tok == req.eos_token:
            req.finish_reason = "eos"
            return True
        if len(req.out_tokens) >= req.max_new_tokens:
            req.finish_reason = "length"
            return True
        return False

    def _sync_tab(self) -> None:
        """Ship the allocator's host-side block table to the device cache(s)
        when it changed. This is an EXPLICIT host->device transfer issued
        outside the guarded jitted calls — the table rides into them as an
        ordinary device argument, so the trace guard's implicit-transfer
        check stays clean. Both pools (full + draft) share the one table.
        It lands with the sharding of the table it replaces: a device
        program's output carries its mesh in its type, and an argument
        whose type flips between calls would retrace the program."""
        if self._alloc is None or not self._tab_dirty:
            return
        tab = jax.device_put(self._alloc.tab, self.cache["tab"].sharding)
        self.cache = dict(self.cache, tab=tab)
        if self.cache_draft is not None:
            self.cache_draft = dict(self.cache_draft, tab=tab)
        self._tab_dirty = False

    def _reserve_rows(self, req: Request) -> int:
        """KV rows a request must own for its whole lifetime: every written
        position (``prompt + max_new - 1``, see ``_validate_request``) plus
        ``spec_k`` verify-lookahead rows in speculative mode. Reserved in
        FULL at admission so decode/verify never allocate mid-flight and
        speculative rollback is a pure position rewind over owned blocks."""
        return (req.n_prompt + req.max_new_tokens - 1
                + (self.ec.spec_k if self.spec else 0))

    def _admit(self, now: float) -> List[Request]:
        """Fill free slots with due pending requests (prefill + insert +
        first token), batching same-bucket admissions. Returns requests that
        finish AT admission (e.g. max_new_tokens == 1).

        Paged layout: each claim first reserves its block budget with the
        allocator, adopting any registered prefix chain (the returned shared
        row count shrinks the prompt suffix that is actually forwarded). A
        failed reservation DEFERS the FIFO head — nothing behind it may jump
        the queue — until eviction returns blocks to the pool.

        Deadlines (§12): a due request whose effective deadline has passed
        is SHED here instead of admitted — with reason ``pool_pressure``
        when an earlier cycle deferred it (it waited on blocks, not on the
        clock), else ``deadline``. Shed requests ride the finished list so
        ``run()`` returns every terminal request."""
        finished: List[Request] = []
        if self._done_early:
            finished.extend(self._done_early)
            self._done_early.clear()
        free = [s for s in range(self.ec.n_slots) if not self._active[s]]
        claimed: List[Tuple[Request, int, int]] = []
        while self._pending and self._pending[0][0] <= now:
            req = self._pending[0][-1]
            dl = req.effective_deadline
            if dl is not None and now > dl:
                heapq.heappop(self._pending)
                self._shed(req, now,
                           "pool_pressure" if req.deferred else "deadline")
                finished.append(req)
                continue
            if not free:
                break
            shared = 0
            if self._faults is not None \
                    and self._faults.exhausted(self._step_count):
                # injected pool exhaustion: defer the head exactly like a
                # real failed reservation (works in dense layout too)
                req.deferred = True
                break
            if self._alloc is not None:
                shared = self._alloc.admit(free[0], req.prompt,
                                           self._reserve_rows(req))
                if shared is None:
                    req.deferred = True
                    break                       # pool exhausted: defer head
                self._tab_dirty = True
            heapq.heappop(self._pending)
            claimed.append((req, free.pop(0), shared))
        if not claimed:
            return finished
        if self.ec.batch_admission:
            # paged grouping buckets by the SUFFIX length (the tokens the
            # admission forward actually runs); dense shared is always 0,
            # so this is the full prompt length there
            groups: Dict[int, List[Tuple[Request, int, int]]] = {}
            for req, slot, shared in claimed:
                groups.setdefault(self.bucket_for(req.n_prompt - shared),
                                  []).append((req, slot, shared))
            for bucket in sorted(groups):
                self._admit_group(bucket, groups[bucket], now, finished)
        else:
            for req, slot, shared in claimed:
                self._admit_group(self.bucket_for(req.n_prompt - shared),
                                  [(req, slot, shared)], now, finished)
        return finished

    def _admit_group(self, bucket: int,
                     group: List[Tuple[Request, int, int]],
                     now: float, finished: List[Request]) -> None:
        """Prefill + insert + first token for one bucket's admissions as a
        single fused device call (``steps.make_slot_admit`` /
        ``make_slot_admit_paged``).

        The batch is padded to the next power of two so admission compiles
        at most ``len(pad_shapes) * (log2(n_slots)+1)`` specializations
        instead of one per (bucket, group-size) pair; pad rows carry an
        out-of-bounds slot index, which JAX scatter semantics drop (paged:
        the sentinel table row), so they never touch the cache. Paged rows
        forward only the prompt SUFFIX past their shared-prefix rows; new
        prefix chains are registered for sharing only AFTER the device call
        that wrote the rows (a same-cycle sharer must never adopt unwritten
        blocks)."""
        B = len(group)
        Bp = 1
        while Bp < B:
            Bp *= 2
        toks = np.zeros((Bp, bucket), np.int32)
        lengths = np.ones((Bp,), np.int32)
        slots = np.full((Bp,), self.ec.n_slots, np.int32)   # pads: OOB, dropped
        pos0 = np.zeros((Bp,), np.int32)
        keys = np.zeros((Bp, 2), np.uint32)
        for i, (req, slot, shared) in enumerate(group):
            suffix = req.prompt[shared:]
            toks[i, :suffix.size] = suffix
            lengths[i] = suffix.size
            slots[i] = slot
            pos0[i] = shared
            # the request's sampling key, derived from its uid so the
            # sampled stream is scheduling-independent (module docstring)
            self._slot_keys[slot] = np.asarray(
                jax.random.fold_in(self._key_base, req.uid), np.uint32)
            keys[i] = self._slot_keys[slot]
        self._sync_tab()
        paged_args = ((jnp.asarray(pos0),) if self._alloc is not None
                      else ())
        if self.spec:
            logits, first_dev, self.cache, self.cache_draft = \
                self._with_retries(
                    "admit", "slot_admit_spec",
                    lambda: self._admit_spec(
                        self.params, self.draft_params, self.cache,
                        self.cache_draft, jnp.asarray(toks),
                        jnp.asarray(lengths), jnp.asarray(slots),
                        *paged_args, jnp.asarray(keys)))
            self.counters["device_calls"] += 1
            first = np.asarray(first_dev[:B])
        else:
            logits, greedy, self.cache = self._with_retries(
                "admit", "slot_admit",
                lambda: self._admit_step(
                    self.params, self.cache, jnp.asarray(toks),
                    jnp.asarray(lengths), jnp.asarray(slots), *paged_args))
            self.counters["device_calls"] += 1
            # the first token occupies position ``n_prompt`` (= shared
            # prefix rows + suffix length) — same noise index the device
            # paths use for it
            first = self._sample(logits[:B], greedy[:B], keys[:B],
                                 pos0[:B] + lengths[:B])
        self.counters["host_syncs"] += 1
        if self._alloc is not None and self.ec.prefix_sharing:
            # AFTER the device call: the rows now exist. Sharing begins at
            # the NEXT admission cycle — every cycle's allocator
            # reservations (lookup_prefix) run in _admit before any group's
            # device call, so same-cycle duplicates never adopt each other
            for req, slot, shared in group:
                self._alloc.register_prefix(slot, req.prompt)
        for i, (req, slot, shared) in enumerate(group):
            tok = int(first[i])
            req.out_tokens.append(tok)
            self.counters["tokens_out"] += 1
            req.t_admitted = now
            req.t_first_token = now
            self._slot_req[slot] = req
            self._last_tok[slot] = tok
            self._active[slot] = True
            if self._is_done(req, tok):
                self._evict(slot, now)
                finished.append(req)

    def _evict(self, slot: int, now: float, status: str = "ok") -> None:
        req = self._slot_req[slot]
        if req is not None:
            req.t_finished = now
            req.status = status
            self._inflight.discard(req.uid)
        self._slot_req[slot] = None
        self._active[slot] = False
        if self._alloc is not None:
            # blocks return to the pool (registry pins keep shared prefix
            # chains alive); the slot's table row goes to the sentinel so
            # any write the frozen slot still issues on device is dropped
            self._alloc.release(slot)
            self._tab_dirty = True

    # ------------------------------------------------- resilience (§12)

    def _poison_mask(self, k: int) -> np.ndarray:
        """Fault-injection NaN mask for the decode block starting at the
        current step and spanning ``k`` steps; all-False without a plan
        (a bitwise no-op inside the jitted block)."""
        if self._faults is None:
            return self._zero_poison
        return self._faults.poison_mask(self._step_count, k,
                                        self.ec.n_slots)

    def _with_retries(self, site: str, name: str, call: Callable):
        """Run one device-step call through the fault plan's transient-
        failure site with the engine's bounded retry/backoff budget. Each
        injected failure consumes one retry; exceeding
        ``EngineConfig.device_retries`` raises DeviceStepError. Without a
        plan (or when nothing fires) this is a plain passthrough."""
        fails = (self._faults.transient_failures(site, self._step_count)
                 if self._faults is not None else 0)
        for attempt in range(fails):
            if attempt >= self.ec.device_retries:
                raise ERR.DeviceStepError(
                    f"{name} at site {site!r}, step {self._step_count}: "
                    f"still failing after {attempt} retries (budget "
                    f"device_retries={self.ec.device_retries})")
            self.counters["transient_retries"] += 1
            if self.ec.retry_backoff_s > 0:
                time.sleep(self.ec.retry_backoff_s * (2 ** attempt))
        return call()

    def _quarantine(self, slot: int, now: float) -> Request:
        """Evict a slot whose sentinel lane reported non-finite logits: its
        request terminates ``failed_numeric`` with its tokens truncated at
        the poisoned step (everything before it matches the fault-free
        stream bitwise), and its pages return to the pool. Healthy slots
        are untouched — their computation is batch-independent."""
        req = self._slot_req[slot]
        req.finish_reason = "numeric"
        self.counters["quarantined"] += 1
        self._evict(slot, now, status="failed_numeric")
        return req

    def _raise_if_strict(self, quarantined: List[Request]) -> None:
        """Strict sentinel mode: raise AFTER the replay loop finished, so
        the engine state (evictions, counters, pages) is consistent and the
        caller can snapshot or continue with the healthy slots."""
        if quarantined and self.ec.numeric_sentinel == "strict":
            raise ERR.NumericHealthError(
                f"non-finite logits quarantined uid(s) "
                f"{sorted(r.uid for r in quarantined)} at step "
                f"{self._step_count}; slots evicted failed_numeric")


# ---------------------------------------------------------------------------
# arrival traces
# ---------------------------------------------------------------------------

def poisson_trace(n_requests: int, rate: float, seed: int = 0) -> np.ndarray:
    """Cumulative Poisson-process arrival times (rate = requests per clock
    unit: decode steps or seconds, matching the engine clock)."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / max(rate, 1e-9), size=n_requests)
    return np.cumsum(gaps)
