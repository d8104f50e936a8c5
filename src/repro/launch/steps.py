"""Step functions: train_step / serve_prefill / serve_step builders.

These close over the ModelConfig and Optimizer, take pure pytrees, and are
what ``launch.train`` / ``launch.serve`` / ``launch.dryrun`` jit with explicit
in/out shardings.
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models import model as MD
from repro.optim import Optimizer, apply_updates

F32 = jnp.float32


def make_train_step(cfg: ModelConfig, opt: Optimizer,
                    microbatches: int = 1,
                    grad_transform: Optional[Callable] = None,
                    grad_dtype: str = "bfloat16") -> Callable:
    """Returns train_step(params, opt_state, batch, step) ->
    (params, opt_state, loss, metrics).

    microbatches > 1 = gradient accumulation via lax.scan (collectives fire
    once per step instead of once per microbatch).
    grad_transform: optional hook applied to the averaged grads (e.g. the
    int8 error-feedback compressor from repro.distributed.compression).
    grad_dtype: dtype of the gradients as they cross the data-parallel
    all-reduce. bf16 halves the dW collective volume (§Perf iteration A6);
    the optimizer still accumulates fp32 states. Set "float32" to disable.
    """
    gdt = jnp.dtype(grad_dtype)

    def _cast_grads(grads, params):
        if gdt == jnp.float32:
            return grads
        casted = jax.tree.map(
            lambda g, p: g if (g.dtype == jax.dtypes.float0
                               or not jnp.issubdtype(p.dtype, jnp.floating))
            else g.astype(gdt), grads, params)
        # the barrier stops XLA's excess-precision pass from cancelling the
        # bf16 downcast against the optimizer's fp32 upcast (which would
        # silently put the DP grad all-reduce back at fp32 width)
        leaves, tdef = jax.tree_util.tree_flatten(casted)
        leaves = list(jax.lax.optimization_barrier(tuple(leaves)))
        return jax.tree_util.tree_unflatten(tdef, leaves)

    def loss_fn(params, batch):
        return MD.loss(cfg, params, batch)

    def train_step(params, opt_state, batch, step):
        if microbatches == 1:
            (l, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True, allow_int=True)(params, batch)
        else:
            mb = jax.tree.map(
                lambda a: a.reshape((microbatches, a.shape[0] // microbatches)
                                    + a.shape[1:]), batch)

            def acc(carry, mbatch):
                g_acc, l_acc = carry
                (l, m), g = jax.value_and_grad(
                    loss_fn, has_aux=True, allow_int=True)(params, mbatch)
                return (jax.tree.map(jnp.add, g_acc, g), l_acc + l), m

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
            (grads, l_sum), ms = jax.lax.scan(acc, (g0, jnp.zeros((), F32)), mb)
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            l = l_sum / microbatches
            metrics = jax.tree.map(lambda a: jnp.mean(a), ms)
        grads = _cast_grads(grads, params)
        if grad_transform is not None:
            grads = grad_transform(grads)
        updates, opt_state = opt.update(grads, opt_state, params, step)
        params = apply_updates(params, updates)
        return params, opt_state, l, metrics

    return train_step


def make_serve_prefill(cfg: ModelConfig, s_max: Optional[int] = None) -> Callable:
    def serve_prefill(params, batch):
        return MD.prefill(cfg, params, batch, s_max=s_max)
    return serve_prefill


def make_serve_step(cfg: ModelConfig) -> Callable:
    def serve_step(params, cache, token):
        return MD.decode_step(cfg, params, cache, token)
    return serve_step


# ---------------------------------------------------------------------------
# continuous-batching (slotted) serving
# ---------------------------------------------------------------------------

def admit_pad_shapes(buckets, s_max: int) -> Tuple[int, ...]:
    """The ONLY prompt pad lengths admission may compile, ascending.

    Single source of truth for the padding policy: the declared buckets
    clamped to ``s_max`` plus the big-bucket multiples used for overflow
    prompts (also clamped). ``Engine.bucket_for`` maps a length to the
    smallest member covering it and FAILS CLOSED on non-membership, and
    :func:`admit_trace_budget` counts this same set — so the shape table the
    engine pads to and the trace budget the guard enforces can never drift
    apart. The largest member is always ``s_max``, so every admissible
    prompt (``len <= s_max``) has a pad shape."""
    declared = sorted({min(int(b), int(s_max)) for b in buckets}) or [1]
    big = declared[-1]
    shapes = set(declared)
    m = 1
    while m * big < s_max:
        m += 1
        shapes.add(min(m * big, s_max))
    return tuple(sorted(shapes))


def admit_trace_budget(buckets, s_max: int, n_slots: int) -> int:
    """Upper bound on legitimate jit specializations of ``slot_admit``.

    The engine pads every admission group to (pad shape, pow2 group size);
    pad shapes come from :func:`admit_pad_shapes` (the same table
    ``Engine.bucket_for`` draws from), and group sizes are the powers of two
    up to the next pow2 >= ``n_slots``. Anything beyond this product is a
    RETRACE — some shape leaked past the padding policy (the trace guard
    counts it)."""
    shapes = admit_pad_shapes(buckets, s_max)
    sizes, p = 1, 1
    while p < n_slots:
        p *= 2
        sizes += 1
    return len(shapes) * sizes


def make_slot_decode(cfg: ModelConfig) -> Callable:
    """slot_decode(params, cache, token [B], active [B], poison [B] bool) ->
    (logits [B, V], aux [B, 2] int32, cache) with ``aux[b] = (greedy,
    finite)``. The greedy argmax AND the numeric-health flag (all-logits-
    finite per slot, DESIGN.md §12) are computed on-device and packed into
    one array, so a temperature-0 engine still does exactly one readback
    per step. ``poison`` is the fault-injection mask (serving.faults):
    True rows get their logits NaN-poisoned AFTER the forward — an
    all-False mask is a bitwise no-op (``where`` selects the untouched
    logits), so fault-free traces are unchanged."""
    def slot_decode(params, cache, token, active, poison):
        logits, cache = MD.decode_step_slots(cfg, params, cache, token, active)
        logits = jnp.where(poison[:, None], jnp.nan, logits)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        finite = jnp.all(jnp.isfinite(logits), axis=-1).astype(jnp.int32)
        return logits, jnp.stack([greedy, finite], axis=-1), cache
    return slot_decode


def make_slot_admit(cfg: ModelConfig) -> Callable:
    """Fused admission: prefill + slot insert + first-token argmax in ONE
    jitted call (one dispatch per admission group instead of three).

    slot_admit(params, cache, tokens [B, S_bucket], lengths [B], slots [B])
    -> (logits [B, V], greedy [B] int32, cache). Rows may be padding (the
    engine pads groups to a power of two to bound jit specializations):
    their ``slots`` entry is set OUT OF BOUNDS (>= n_slots), and JAX's
    default scatter semantics DROP out-of-bounds updates, so pad rows'
    garbage KV and lengths never land in the cache — the engine just ignores
    their logits rows."""
    def slot_admit(params, cache, tokens, lengths, slots):
        logits, k_new, v_new = MD.prefill_slots(cfg, params, tokens, lengths)
        cache = MD.insert_slots(cache, slots, k_new, v_new, lengths)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return logits, greedy, cache
    return slot_admit


def make_slot_admit_paged(cfg: ModelConfig) -> Callable:
    """Fused admission into the PAGED KV pool (DESIGN.md §11).

    slot_admit_paged(params, cache, tokens [B, S_bucket], lengths [B],
    slots [B], pos0 [B]) -> (logits [B, V], greedy [B] int32, cache).

    ``tokens`` holds each request's SUFFIX (prompt minus any shared-prefix
    rows) padded to a bucket length; ``pos0`` is the per-row shared prefix
    length in rows (all zero without sharing). Pad rows carry
    ``slots >= n_slots``, which indexes the sentinel block-table row — their
    KV scatters and ``pos`` writes all drop, the ``make_slot_admit``
    contract carried over to the paged layout. With ``pos0 = 0`` the logits
    and pool rows written are bitwise the dense prefill+insert admission's
    (bf16 pools)."""
    def slot_admit_paged(params, cache, tokens, lengths, slots, pos0):
        logits, cache = MD.admit_slots_paged(cfg, params, cache, tokens,
                                             lengths, slots, pos0)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return logits, greedy, cache
    return slot_admit_paged


def sample_tokens(logits: jax.Array, temperature: float, keys: jax.Array,
                  positions: jax.Array) -> jax.Array:
    """Sample one token per row of ``logits`` [B, V] -> [B] int32.

    ``temperature <= 0``: greedy argmax (``keys``/``positions`` unused).
    ``temperature > 0``: Gumbel-max with a POSITION-INDEXED key schedule —
    the noise added to row ``b``'s logits is
    ``gumbel(fold_in(keys[b], positions[b]))`` where ``positions[b]`` is
    the sequence position the sampled token will OCCUPY in slot ``b``'s
    cache. The noise therefore depends only on (sampling key, token
    position), never on which program computes it or how the engine
    scheduled the request. Both sampling contracts hang off that one
    property (DESIGN.md §10):

    * device == host: the fused decode loops and the engine's host-side
      fallback (``Engine._sample``) run this same function on the same
      (key, position) pairs, so they agree bitwise;
    * draft == verify (speculative decoding): the draft model proposing
      the token at position ``q`` and the full model verifying position
      ``q`` add IDENTICAL noise to their own logits, so a draft proposal
      is accepted exactly when the full model would have sampled the same
      token — accepted tokens are bitwise the full model's samples.

    keys: [B, 2] uint32 per-slot PRNG keys (the engine derives them from
    the request uid, so they travel with the request across slots and
    engine modes); positions: [B] int32.
    """
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    vocab = logits.shape[-1]

    def noise(key, q):
        return jax.random.gumbel(jax.random.fold_in(key, q), (vocab,), F32)

    g = jax.vmap(noise)(keys, positions)
    return jnp.argmax(logits.astype(F32) / temperature + g,
                      axis=-1).astype(jnp.int32)


def make_slot_decode_multi(cfg: ModelConfig, k_steps: int,
                           temperature: float = 0.0) -> Callable:
    """Fused K-step decode: the device, not Python, drives steady-state
    decode (DESIGN.md §7).

    slot_decode_multi(params, cache, token [B], active [B], remaining [B],
    eos [B], keys [B, 2], poison [B] bool) -> (block [K, B, 3] int32,
    active [B] bool, cache), where ``block[s, b] = (token, emitted,
    finite)`` — tokens, their emitted flags, and the numeric-health
    sentinel lane (all-logits-finite per step and slot, DESIGN.md §12) are
    PACKED into one array so the engine's per-block device->host readback
    is a single transfer; the sentinel costs ZERO additional host syncs.
    ``poison`` is the fault-injection mask (``serving.faults``): True rows
    get their logits NaN-poisoned after each scanned forward. An all-False
    mask is a bitwise no-op, so fault-free decode is unchanged.

    ``lax.scan`` runs ``k_steps`` decode steps inside ONE jitted call:
    sampling (:func:`sample_tokens` — greedy argmax, or Gumbel-max at
    ``temperature`` > 0 under the position-indexed key schedule) happens
    on device, and per-slot stop flags freeze finished slots in place — a
    slot whose sampled token hits its ``eos`` entry (-1 = none) or
    exhausts ``remaining`` stops advancing ``pos`` and stops emitting, but
    rides along in the batch (static shapes). ``emitted[s, b]`` marks
    which of the K tokens are real; the host replays only those. When
    every slot is frozen the remaining scan tail skips the forward
    entirely (``lax.cond``), so an early-finishing block costs control
    flow, not FLOPs. Host syncs drop from one per token to one per K
    tokens."""
    def slot_decode_multi(params, cache, token, active, remaining, eos, keys,
                          poison):
        def step(carry):
            cache, tok, act, rem = carry
            logits, cache = MD.decode_step_slots(cfg, params, cache, tok, act)
            logits = jnp.where(poison[:, None], jnp.nan, logits)
            finite = jnp.all(jnp.isfinite(logits), axis=-1)
            # cache["pos"] already advanced for active slots = the position
            # the sampled token will occupy (frozen rows sample garbage
            # that is never emitted)
            nxt = sample_tokens(logits, temperature, keys, cache["pos"])
            emitted = act
            rem = rem - act.astype(jnp.int32)
            done = (nxt == eos) | (rem <= 0)
            act = act & ~done
            tok = jnp.where(emitted, nxt, tok)
            return (cache, tok, act, rem), (nxt, emitted, finite)

        def body(carry, _):
            cache, tok, act, rem = carry
            return jax.lax.cond(
                jnp.any(act),
                lambda c: step(c),
                # skipped tail steps emit nothing; their sentinel lane
                # reports healthy (no forward ran, nothing to flag)
                lambda c: (c, (c[1], jnp.zeros_like(c[2]),
                               jnp.ones_like(c[2]))),
                (cache, tok, act, rem))

        (cache, tok, act, rem), (toks, emits, fins) = jax.lax.scan(
            body, (cache, token, active, remaining), None, length=k_steps)
        block = jnp.stack([toks, emits.astype(jnp.int32),
                           fins.astype(jnp.int32)], axis=-1)
        return block, act, cache
    return slot_decode_multi


# ---------------------------------------------------------------------------
# self-speculative decoding (draft = MergeMoE-compressed, verify = full)
# ---------------------------------------------------------------------------

def make_slot_decode_spec(cfg: ModelConfig, draft_cfg: ModelConfig,
                          k_draft: int, temperature: float = 0.0) -> Callable:
    """One fused draft/verify round (DESIGN.md §10): the compressed model
    proposes ``k_draft`` tokens per slot, the full model scores every
    proposal in ONE multi-position forward, and accept/rollback happens on
    device. Built in ``repro.serving.spec`` (the import is lazy so the
    serving package can keep importing ``launch.steps``)."""
    from repro.serving.spec import build_slot_decode_spec
    return build_slot_decode_spec(cfg, draft_cfg, k_draft, temperature)


def make_slot_admit_spec(cfg: ModelConfig, draft_cfg: ModelConfig,
                         temperature: float = 0.0) -> Callable:
    """Fused dual-model admission for speculative serving: both prefills +
    both slot inserts + the full model's first token in one jitted call."""
    from repro.serving.spec import build_slot_admit_spec
    return build_slot_admit_spec(cfg, draft_cfg, temperature)


def make_slot_admit_spec_paged(cfg: ModelConfig, draft_cfg: ModelConfig,
                               temperature: float = 0.0) -> Callable:
    """Paged-pool sibling of :func:`make_slot_admit_spec`: both models admit
    the same suffix group into their own block pools (one shared table)."""
    from repro.serving.spec import build_slot_admit_spec_paged
    return build_slot_admit_spec_paged(cfg, draft_cfg, temperature)


# ---------------------------------------------------------------------------
# expert-parallel mesh serving (DESIGN.md §13)
# ---------------------------------------------------------------------------
#
# The ``*_mesh`` builders wrap the single-device slot programs above in a
# ``shard_map`` over the engine mesh: expert tables partitioned on "model"
# (the MoE layers switch to the all-to-all pair-exchange dispatch of
# ``models/moe_ep.py``), slots + KV partitioned on "data" so attention never
# crosses the wire. Per-slot vectors arrive sharded; admission groups arrive
# replicated and localize their slot ids in-program; the paged block table
# is host-written in GLOBAL block ids and localized in-program on the way in
# (never written by the device, so the wrappers hand the original back out).


def ep_serve_cfg(cfg: ModelConfig, mesh,
                 combine_wire_dtype: str = "fp32") -> ModelConfig:
    """Config view for traces INSIDE the decode shard_map: bakes the EP
    degree/axis (and combine wire dtype) into ``cfg.moe`` so the lazily
    traced model functions pick the EP dispatch without any global state.
    Identity for dense models and 1-wide "model" axes."""
    if cfg.moe is None:
        return cfg
    ep = int(mesh.shape.get("model", 1))
    if ep <= 1:
        return cfg
    import dataclasses
    return cfg.replace(moe=dataclasses.replace(
        cfg.moe, ep_axis="model", ep_degree=ep,
        combine_wire_dtype=combine_wire_dtype))


def _localize_slots(slots: jax.Array, n_local: int):
    """Global slot ids -> this data shard's local ids. Foreign (and pad)
    rows map to ``n_local`` — out of bounds for the local cache, so their
    scatters drop; as a paged-table row index it is the local sentinel row.
    Returns (local_slots, mine_mask)."""
    d0 = jax.lax.axis_index("data") * n_local
    mine = (slots >= d0) & (slots < d0 + n_local)
    return jnp.where(mine, slots - d0, n_local).astype(slots.dtype), mine


def _localize_paged_tab(cache: dict, dp: int):
    """Global block table -> this data shard's local view.

    The allocator partitions blocks so a shard's slots reference ONLY its
    own block range ``[di*nb_l, (di+1)*nb_l)`` (serving.paging, n_shards);
    entries rebase to local ids, the global sentinel (>= nb_global) maps to
    the local one (nb_l), and a local sentinel row is appended for foreign/
    pad slot ids. Dense caches pass through. Returns (cache, original_tab —
    None when nothing was localized)."""
    if "kp" not in cache:
        return cache, None
    tab = cache["tab"]                              # [n_slots + 1, mb]
    nb_l = cache["kp"].shape[1]
    n_local = cache["pos"].shape[0]
    di = jax.lax.axis_index("data")
    rows = jax.lax.dynamic_slice_in_dim(tab, di * n_local, n_local, axis=0)
    loc = jnp.where(rows >= nb_l * dp, nb_l, rows - di * nb_l)
    loc = jnp.concatenate(
        [loc, jnp.full((1, tab.shape[1]), nb_l, tab.dtype)], axis=0)
    return dict(cache, tab=loc.astype(tab.dtype)), tab


def _restore_tab(cache: dict, tab0):
    return cache if tab0 is None else dict(cache, tab=tab0)


def _mesh_specs(mesh, params, cache):
    from jax.sharding import PartitionSpec as P
    from repro.launch import sharding as SH
    return (SH.serve_param_pspecs(params, mesh),
            SH.slot_cache_pspecs(cache, mesh),
            P("data"), P())


def make_slot_decode_mesh(cfg: ModelConfig, mesh, params, cache,
                          combine_wire_dtype: str = "fp32") -> Callable:
    """Mesh form of :func:`make_slot_decode` — same signature and contract,
    args per-slot-sharded over "data" (``params``/``cache`` are template
    trees used only for spec derivation)."""
    cfg_l = ep_serve_cfg(cfg, mesh, combine_wire_dtype)
    pspec, cspec, v, rep = _mesh_specs(mesh, params, cache)
    dp = int(mesh.shape.get("data", 1))

    def fn(params, cache, token, active, poison):
        cache, tab0 = _localize_paged_tab(cache, dp)
        logits, cache = MD.decode_step_slots(cfg_l, params, cache, token,
                                             active)
        logits = jnp.where(poison[:, None], jnp.nan, logits)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        finite = jnp.all(jnp.isfinite(logits), axis=-1).astype(jnp.int32)
        return logits, jnp.stack([greedy, finite], axis=-1), \
            _restore_tab(cache, tab0)

    return jax.shard_map(fn, mesh=mesh, in_specs=(pspec, cspec, v, v, v),
                         out_specs=(v, v, cspec), check_vma=False)


def make_slot_decode_multi_mesh(cfg: ModelConfig, k_steps: int,
                                temperature: float, mesh, params, cache,
                                combine_wire_dtype: str = "fp32") -> Callable:
    """Mesh form of :func:`make_slot_decode_multi`. The scan's early-exit
    ``lax.cond`` predicate is data-row-consistent (``active`` is sharded on
    "data", replicated across "model"), and every EP collective runs on the
    "model" axis only — so all members of a collective group always take
    the same branch."""
    from jax.sharding import PartitionSpec as P
    cfg_l = ep_serve_cfg(cfg, mesh, combine_wire_dtype)
    inner = make_slot_decode_multi(cfg_l, k_steps, temperature)
    pspec, cspec, v, rep = _mesh_specs(mesh, params, cache)
    dp = int(mesh.shape.get("data", 1))

    def fn(params, cache, token, active, remaining, eos, keys, poison):
        cache, tab0 = _localize_paged_tab(cache, dp)
        block, act, cache = inner(params, cache, token, active, remaining,
                                  eos, keys, poison)
        return block, act, _restore_tab(cache, tab0)

    return jax.shard_map(fn, mesh=mesh,
                         in_specs=(pspec, cspec, v, v, v, v, v, v),
                         out_specs=(P(None, "data"), v, cspec),
                         check_vma=False)


def make_slot_admit_mesh(cfg: ModelConfig, mesh, params, cache) -> Callable:
    """Mesh form of :func:`make_slot_admit`: the group's tokens arrive
    REPLICATED (every shard runs the same prefill — the forward reads
    nothing from the cache, so its logits are exact everywhere, EP splitting
    the MoE work across "model"); only the KV/pos scatters are localized so
    each data shard keeps its own slots."""
    cfg_l = ep_serve_cfg(cfg, mesh)
    pspec, cspec, v, rep = _mesh_specs(mesh, params, cache)

    def fn(params, cache, tokens, lengths, slots):
        logits, k_new, v_new = MD.prefill_slots(cfg_l, params, tokens,
                                                lengths)
        slots_l, _ = _localize_slots(slots, cache["pos"].shape[0])
        cache = MD.insert_slots(cache, slots_l, k_new, v_new, lengths)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return logits, greedy, cache

    return jax.shard_map(fn, mesh=mesh,
                         in_specs=(pspec, cspec, rep, rep, rep),
                         out_specs=(rep, rep, cspec), check_vma=False)


def make_slot_admit_paged_mesh(cfg: ModelConfig, mesh, params,
                               cache) -> Callable:
    """Mesh form of :func:`make_slot_admit_paged`. Unlike dense admission,
    the paged forward READS the pool (shared-prefix rows at ``pos0 > 0``),
    which only the slot-owning data shard holds — foreign shards compute
    finite garbage for those rows. Owner rows are masked in, summed over
    "data" (adding exact fp zeros), and the greedy lane recomputed from the
    reconciled logits."""
    cfg_l = ep_serve_cfg(cfg, mesh)
    pspec, cspec, v, rep = _mesh_specs(mesh, params, cache)
    dp = int(mesh.shape.get("data", 1))

    def fn(params, cache, tokens, lengths, slots, pos0):
        cache, tab0 = _localize_paged_tab(cache, dp)
        slots_l, mine = _localize_slots(slots, cache["pos"].shape[0])
        logits, cache = MD.admit_slots_paged(cfg_l, params, cache, tokens,
                                             lengths, slots_l, pos0)
        if dp > 1:
            logits = jax.lax.psum(
                jnp.where(mine[:, None], logits, 0.0), "data")
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return logits, greedy, _restore_tab(cache, tab0)

    return jax.shard_map(fn, mesh=mesh,
                         in_specs=(pspec, cspec, rep, rep, rep, rep),
                         out_specs=(rep, rep, cspec), check_vma=False)


def make_slot_decode_spec_mesh(cfg: ModelConfig, draft_cfg: ModelConfig,
                               k_draft: int, temperature: float, mesh,
                               params, draft_params, cache, draft_cache,
                               combine_wire_dtype: str = "fp32") -> Callable:
    """Mesh form of :func:`make_slot_decode_spec`: one fused draft/verify
    round with BOTH models' expert tables EP-sharded (draft included — the
    compressed tables divide the same way) and both caches sharded with the
    slots. The shared paged block table is localized once per cache."""
    from jax.sharding import PartitionSpec as P
    from repro.serving.spec import build_slot_decode_spec
    cfg_l = ep_serve_cfg(cfg, mesh, combine_wire_dtype)
    dcfg_l = ep_serve_cfg(draft_cfg, mesh, combine_wire_dtype)
    inner = build_slot_decode_spec(cfg_l, dcfg_l, k_draft, temperature)
    pspec, cspec, v, rep = _mesh_specs(mesh, params, cache)
    from repro.launch import sharding as SH
    dpspec = SH.serve_param_pspecs(draft_params, mesh)
    dcspec = SH.slot_cache_pspecs(draft_cache, mesh)
    dp = int(mesh.shape.get("data", 1))

    def fn(params, draft_params, cache, draft_cache, token, active,
           remaining, eos, keys, poison):
        cache, tab0 = _localize_paged_tab(cache, dp)
        draft_cache, dtab0 = _localize_paged_tab(draft_cache, dp)
        block, still, cache, draft_cache = inner(
            params, draft_params, cache, draft_cache, token, active,
            remaining, eos, keys, poison)
        return (block, still, _restore_tab(cache, tab0),
                _restore_tab(draft_cache, dtab0))

    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(pspec, dpspec, cspec, dcspec, v, v, v, v, v, v),
        out_specs=(P(None, "data"), v, cspec, dcspec), check_vma=False)


def make_slot_admit_spec_mesh(cfg: ModelConfig, draft_cfg: ModelConfig,
                              temperature: float, mesh, params, draft_params,
                              cache, draft_cache) -> Callable:
    """Mesh form of :func:`make_slot_admit_spec` (dense caches): replicated
    dual prefill, localized scatters — the :func:`make_slot_admit_mesh`
    story applied to both models."""
    from repro.serving.spec import build_slot_admit_spec
    cfg_l = ep_serve_cfg(cfg, mesh)
    dcfg_l = ep_serve_cfg(draft_cfg, mesh)
    pspec, cspec, v, rep = _mesh_specs(mesh, params, cache)
    from repro.launch import sharding as SH
    dpspec = SH.serve_param_pspecs(draft_params, mesh)
    dcspec = SH.slot_cache_pspecs(draft_cache, mesh)

    def fn(params, draft_params, cache, draft_cache, tokens, lengths, slots,
           keys):
        n_local = cache["pos"].shape[0]
        slots_l, _ = _localize_slots(slots, n_local)
        logits, k_new, v_new = MD.prefill_slots(cfg_l, params, tokens,
                                                lengths)
        cache = MD.insert_slots(cache, slots_l, k_new, v_new, lengths)
        dlogits, dk, dv = MD.prefill_slots(dcfg_l, draft_params, tokens,
                                           lengths)
        del dlogits
        draft_cache = MD.insert_slots(draft_cache, slots_l, dk, dv, lengths)
        first = sample_tokens(logits, temperature, keys, lengths)
        return logits, first, cache, draft_cache

    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(pspec, dpspec, cspec, dcspec, rep, rep, rep, rep),
        out_specs=(rep, rep, cspec, dcspec), check_vma=False)


def make_slot_admit_spec_paged_mesh(cfg: ModelConfig, draft_cfg: ModelConfig,
                                    temperature: float, mesh, params,
                                    draft_params, cache,
                                    draft_cache) -> Callable:
    """Mesh form of :func:`make_slot_admit_spec_paged`: both pools admit the
    localized suffix group; logits reconcile over "data" (the
    :func:`make_slot_admit_paged_mesh` masking) and the first token is
    re-sampled from the reconciled logits so it is exact on every shard."""
    from repro.serving.spec import build_slot_admit_spec_paged
    cfg_l = ep_serve_cfg(cfg, mesh)
    dcfg_l = ep_serve_cfg(draft_cfg, mesh)
    pspec, cspec, v, rep = _mesh_specs(mesh, params, cache)
    from repro.launch import sharding as SH
    dpspec = SH.serve_param_pspecs(draft_params, mesh)
    dcspec = SH.slot_cache_pspecs(draft_cache, mesh)
    dp = int(mesh.shape.get("data", 1))

    def fn(params, draft_params, cache, draft_cache, tokens, lengths, slots,
           pos0, keys):
        cache, tab0 = _localize_paged_tab(cache, dp)
        draft_cache, dtab0 = _localize_paged_tab(draft_cache, dp)
        slots_l, mine = _localize_slots(slots, cache["pos"].shape[0])
        logits, cache = MD.admit_slots_paged(cfg_l, params, cache, tokens,
                                             lengths, slots_l, pos0)
        _dl, draft_cache = MD.admit_slots_paged(
            dcfg_l, draft_params, draft_cache, tokens, lengths, slots_l,
            pos0)
        del _dl
        if dp > 1:
            logits = jax.lax.psum(
                jnp.where(mine[:, None], logits, 0.0), "data")
        first = sample_tokens(logits, temperature, keys, pos0 + lengths)
        return (logits, first, _restore_tab(cache, tab0),
                _restore_tab(draft_cache, dtab0))

    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(pspec, dpspec, cspec, dcspec, rep, rep, rep, rep, rep),
        out_specs=(rep, rep, cspec, dcspec), check_vma=False)
