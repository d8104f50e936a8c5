"""Mixture-of-Experts layer.

Three dispatch paths:

* ``dense``  — GShard/GSPMD-style capacity-based one-hot dispatch. Static
  shapes, partitions cleanly under pjit (tokens on the ``data`` axis, experts
  on the ``model`` axis -> XLA inserts the all-to-all). Used by train/dry-run.
* ``ragged`` — sort-by-expert grouped matmul (single-device / serving path;
  the Pallas grouped-matmul kernel plugs in here).
* ``gather`` — ragged that specializes decode-SHAPED calls (one token per
  sequence, at most ``gather_max_tokens`` of them) to a per-token
  weight-row gather kernel (``kernels/decode_moe.py``): no
  argsort/bincount/scatter, no per-expert segment padding. Selection is on
  static shapes at trace time; prefill buckets (S > 1) keep the grouped
  kernel.

Compressed (merged) models keep the ORIGINAL router ``[d, N]`` and add an
int32 ``remap`` table ``[N] -> [M]`` (the paper's matrix ``A``, stored as the
index form); expert tables then hold ``M`` merged experts. This reproduces the
paper's implicit-A trick (App. B) with an XLA-friendly gather.

Calibration capture: ``moe_apply(..., capture=True)`` additionally returns
the expert-input activations and per-expert usage counts that
``repro.core`` consumes to build the merge.

Quantized expert tables (DESIGN.md §8): a layer whose params carry a
``qexp`` subtree instead of ``wg/wu/wd`` stores the tables as int8 plus
per-expert-per-output-channel fp32 scales
(:class:`repro.core.quant.QuantizedExpertTables`). All three dispatch paths
accept it — ragged and gather route through the int8 kernels (dequant fused
in-kernel), dense dequantizes up front (train/dry-run path, not
bandwidth-bound). Routing, remap, and the §5 live-masking are untouched:
quantization changes the bits under the expert tables, never the dispatch.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models.numerics import ein, ein32, dot as _ndot, constrain

from repro.models.config import ModelConfig
from repro.models.layers import _dense_init, mlp_init, mlp_apply

F32 = jnp.float32


class MoEOutput(NamedTuple):
    y: jax.Array                       # [B, S, d]
    aux_loss: jax.Array                # scalar load-balance loss
    # capture (zeros-shaped when capture=False to keep pytree static)
    expert_inputs: Optional[jax.Array]   # [B, S, d] inputs fed to experts
    usage_counts: Optional[jax.Array]    # [N] how often each ORIGINAL expert was picked
    topk_idx: Optional[jax.Array]        # [B, S, k] original-expert indices


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def moe_init(cfg: ModelConfig, key, n_real: int | None = None) -> dict:
    """n_real: number of physically stored experts (M after MergeMoE
    compression); router/remap always span the ORIGINAL n_experts.

    ``live`` counts the routable rows of the expert tables. Heterogeneous
    plans pad every suffix layer's tables to the plan's max M, and
    ``live`` < n_real marks the pad rows; :func:`route` masks the router
    logits of any original expert whose remap lands on a pad row, so the
    zero-filled padding is unreachable (DESIGN.md §5)."""
    m = cfg.moe
    d, f, E = cfg.d_model, m.d_ff_expert, m.n_experts
    R = n_real or E
    dt = cfg.param_dtype
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    p = {
        "router": _dense_init(kr, (d, E), F32),  # router kept fp32 (tiny)
        "wg": _dense_init(kg, (R, d, f), dt),
        "wu": _dense_init(ku, (R, d, f), dt),
        "wd": _dense_init(kd, (R, f, d), dt),
        # identity remap = uncompressed; [N]->[M] after merging.
        "remap": jnp.arange(E, dtype=jnp.int32) % R,
        "live": jnp.asarray(R, jnp.int32),
    }
    if m.n_shared_experts:
        p["shared"] = mlp_init(d, m.n_shared_experts * f, dt, ks)
    return p


#: the expert-table leaves of a MoE layer: plain ``wg/wu/wd`` or the int8
#: ``qexp`` subtree
TABLE_KEYS = ("wg", "wu", "wd", "qexp")


def n_real_experts(p: dict) -> int:
    """Number of physically stored experts (M after compression, else N)."""
    if "qexp" in p:
        return p["qexp"]["wg"].shape[-3]
    return p["wg"].shape[-3]


def own_tables(p: dict) -> dict:
    """``p`` with its own layer's ``[E, ...]`` expert tables.

    A decode stack hands each layer the whole stacked ``[L, E, ...]``
    tables plus its index ``layer`` (``transformer._scan_layers``): the
    gather kernel reads them in place, every other path indexes its layer
    out here — the per-layer slice the scan itself would have made."""
    if "layer" not in p:
        return p
    pick = lambda a: jax.lax.dynamic_index_in_dim(  # noqa: E731
        a, p["layer"], keepdims=False)
    return {k: jax.tree.map(pick, v) if k in TABLE_KEYS else v
            for k, v in p.items() if k != "layer"}


def _quant_tables(p: dict):
    """The layer's ``QuantizedExpertTables`` view, or None when the tables
    are plain bf16/f32 leaves."""
    if "qexp" not in p:
        return None
    from repro.core.quant import QuantizedExpertTables
    return QuantizedExpertTables.from_tree(p["qexp"])


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------

def _topk_iterative(probs: jax.Array, k: int):
    """Partition-friendly top-k: k argmax/mask passes (elementwise over the
    token dims, so GSPMD never gathers the token axis — lax.top_k lowers to a
    variadic sort that forced [B,S,E] all-gathers; §Perf iteration A1)."""
    E = probs.shape[-1]
    ws, ids = [], []
    cur = probs
    iota = jax.lax.broadcasted_iota(jnp.int32, probs.shape, probs.ndim - 1)
    for _ in range(k):
        w = jnp.max(cur, axis=-1)
        i = jnp.argmax(cur, axis=-1).astype(jnp.int32)
        ws.append(w)
        ids.append(i)
        cur = jnp.where(iota == i[..., None], -jnp.inf, cur)
    return jnp.stack(ws, axis=-1), jnp.stack(ids, axis=-1)


def route(cfg: ModelConfig, p: dict, x: jax.Array):
    """Returns (topk_weights [.., k] fp32, topk_idx [.., k] int32 in ORIGINAL
    expert space, probs [.., N])."""
    m = cfg.moe
    logits = ein32("...d,de->...e", x.astype(F32), p["router"])
    if "live" in p:
        # Router-logit masking: an original expert whose remap target is a
        # pad row (>= live, possible only in heterogeneous-M suffix layers)
        # can never win top-k. No-op for valid remaps — every entry already
        # points below ``live`` — so masked and unmasked routing agree
        # exactly; the mask guarantees the zero-padded tables stay
        # unreachable even under a corrupted remap (DESIGN.md §5).
        logits = jnp.where(p["remap"] >= p["live"], -jnp.inf, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = _topk_iterative(probs, m.top_k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)  # renormalize among top-k
    return w, idx, probs


def route_infer(cfg: ModelConfig, p: dict, x: jax.Array):
    """Inference-only routing: (topk_weights [.., k] fp32, topk_idx [.., k]).

    Selects top-k directly on the (live-masked) router LOGITS — softmax is
    strictly monotone, so the selection matches :func:`route` — and computes
    the combine weights as a softmax over just the k selected logits:
    ``exp(l_i) / Σ_topk exp(l_j)``, the same value :func:`route` reaches by
    renormalizing the full softmax. Skips materializing the [.., N] ``probs``
    tensor entirely; it exists only to feed :func:`balance_loss`, which
    decode throws away every step. Training/capture keep :func:`route`."""
    m = cfg.moe
    logits = ein32("...d,de->...e", x.astype(F32), p["router"])
    if "live" in p:
        # same fail-closed pad-row mask as route() (DESIGN.md §5)
        logits = jnp.where(p["remap"] >= p["live"], -jnp.inf, logits)
    lw, idx = _topk_iterative(logits, m.top_k)
    return jax.nn.softmax(lw, axis=-1), idx


def balance_loss(cfg: ModelConfig, probs: jax.Array, idx: jax.Array) -> jax.Array:
    """Switch-style auxiliary load-balancing loss over ORIGINAL experts."""
    E = cfg.moe.n_experts
    me = jnp.mean(probs.reshape(-1, E), axis=0)                      # mean prob
    sel = jax.nn.one_hot(idx.reshape(-1, cfg.moe.top_k), E, dtype=F32)
    ce = jnp.mean(jnp.sum(sel, axis=1), axis=0)                      # tokens/expert
    return E * jnp.sum(me * ce) / cfg.moe.top_k


# ---------------------------------------------------------------------------
# dense (capacity) dispatch — GShard style, group-local
# ---------------------------------------------------------------------------

def _capacity(m, G: int, E: int) -> int:
    c = int(m.top_k * G * m.capacity_factor / E)
    return max(4, -(-c // 4) * 4)  # round up to multiple of 4


def capacity_experts(cfg: ModelConfig, p: dict) -> int:
    """Expert count used to SIZE dense-dispatch capacity (shapes are static,
    so this must come from the config, not the traced ``live`` leaf).

    For a heterogeneous compressed suffix the tables are padded to max-M but
    a layer may route all its traffic onto as few as min(live) rows; sizing
    capacity by the padded width would under-provision those layers and drop
    tokens an unpadded model would keep. Sizing by the SMALLEST live count
    gives every suffix layer at least the per-expert slots its own unpadded
    model would compute (DESIGN.md §5).

    Suffix tables are identified by their width (``moe_merged``). When a
    plan's max M equals the original N the prefix stack matches too and is
    conservatively sized by min(live) as well — over-provisioned capacity is
    wasted slots, never extra drops."""
    E = n_real_experts(p)
    if (cfg.moe_merged_layers is not None
            and E == cfg.moe_merged):        # suffix-width expert tables
        return min(cfg.moe_merged_layers)
    return E


def _dispatch_tensors(cfg: ModelConfig, w, idx, E: int, C: int):
    """Build combine [G, E, C] fp32 and dispatch [G, E, C] bool per group.

    w, idx: [G, k]. Tokens beyond capacity are dropped (standard GShard).
    """
    m = cfg.moe
    G = w.shape[0]
    counts = jnp.zeros((E,), jnp.int32)
    combine = jnp.zeros((G, E, C), F32)
    for j in range(m.top_k):
        mj = jax.nn.one_hot(idx[:, j], E, dtype=jnp.int32)           # [G, E]
        loc = jnp.cumsum(mj, axis=0) - mj + counts[None, :]          # position
        counts = counts + jnp.sum(mj, axis=0)
        keep = (loc < C) & (mj > 0)
        slot = jax.nn.one_hot(jnp.where(keep, loc, C), C, dtype=F32)  # OOB -> 0
        combine = combine + w[:, j, None, None] * mj[..., None] * slot
    dispatch = combine > 0.0
    return combine, dispatch


def _moe_dense_groups(cfg: ModelConfig, p: dict, x2: jax.Array, w, idx):
    """x2: [n_groups, G, d]; w/idx: [n_groups, G, k] (idx already remapped to
    REAL experts). Returns [n_groups, G, d]."""
    m = cfg.moe
    E = n_real_experts(p)
    G = x2.shape[1]
    # capacity sized by the LIVE expert count (== E except in heterogeneous
    # suffixes): merged experts absorb their whole cluster's traffic, so
    # per-expert slots scale up as N/M automatically.
    C = _capacity(m, G, capacity_experts(cfg, p))

    combine, dispatch = jax.vmap(
        lambda wg, ig: _dispatch_tensors(cfg, wg, ig, E, C))(w, idx)

    dt = x2.dtype
    qt = _quant_tables(p)
    if qt is not None:
        # dense dispatch is the train/dry-run path — not bandwidth-bound, so
        # a one-shot dequant to the activation dtype before the einsum keeps
        # it simple (ragged/gather stream int8 through the kernels instead).
        wg_t, wu_t, wd_t = qt.dequant(dt)
        p = dict(p, wg=wg_t, wu=wu_t, wd=wd_t)
    # dispatched tokens: groups stay on the batch axes, experts go to "model"
    # (expert parallelism; GSPMD realizes the reshard as an all-to-all)
    xe = ein("gtec,gtd->gecd", dispatch.astype(dt), x2).astype(dt)           # [g,E,C,d]
    xe = constrain(xe, "DP", "M", None, None)
    h_g = ein("gecd,edf->gecf", xe, p["wg"])
    h_u = ein("gecd,edf->gecf", xe, p["wu"])
    h = (jax.nn.silu(h_g) * h_u).astype(dt)
    ye = ein("gecf,efd->gecd", h, p["wd"]).astype(dt)           # [g,E,C,d]
    ye = constrain(ye, "DP", "M", None, None)
    y = ein("gtec,gecd->gtd", combine.astype(dt), ye).astype(dt)
    # NOTE: deliberately unconstrained — the combine contraction is partial
    # over the expert ("model") axis, and the caller's sequence-parallel
    # residual constraint pulls a reduce-scatter through here. An explicit
    # replicated-token constraint at this point forced a 2x-cost all-reduce
    # (§Perf iteration A2).
    return y


# ---------------------------------------------------------------------------
# ragged (sort-based) dispatch — serving / kernel path
# ---------------------------------------------------------------------------

def _moe_ragged(cfg: ModelConfig, p: dict, xf: jax.Array, w, idx):
    """xf: [T, d]; w/idx: [T, k] (idx in REAL expert space). Dropless."""
    m = cfg.moe
    E = n_real_experts(p)
    T, d = xf.shape
    k = m.top_k
    flat_idx = idx.reshape(-1)                       # [T*k]
    order = jnp.argsort(flat_idx)
    tok_of = order // k                              # source token per slot
    xs = jnp.take(xf, tok_of, axis=0)                # [T*k, d] sorted by expert
    group_sizes = jnp.bincount(flat_idx, length=E).astype(jnp.int32)

    from repro.kernels import ops as kops
    qt = _quant_tables(p)
    if qt is not None:
        ys = kops.grouped_swiglu_q(xs, qt, group_sizes)
    else:
        ys = kops.grouped_swiglu(xs, p["wg"], p["wu"], p["wd"], group_sizes)

    wf = w.reshape(-1)[order].astype(F32)            # weight per sorted slot
    out = jnp.zeros((T, d), F32).at[tok_of].add(ys.astype(F32) * wf[:, None])
    return out.astype(xf.dtype)


# ---------------------------------------------------------------------------
# gather dispatch — decode-mode (tiny T) kernel path
# ---------------------------------------------------------------------------

def _moe_gather(cfg: ModelConfig, p: dict, xf: jax.Array, w, idx):
    """xf: [T, d]; w/idx: [T, k] (idx in REAL expert space). Dropless.

    Per-token weight-row gather + fused SwiGLU: no argsort/bincount/scatter,
    no per-expert segment padding — the decode-mode specialization
    (``kernels/decode_moe.py``). Per-row arithmetic and the fp32 combine
    match :func:`_moe_ragged` exactly. Stacked tables (``layer`` in ``p``)
    go to the kernel whole, with the layer index."""
    from repro.kernels import ops as kops
    qt = _quant_tables(p)
    layer = p.get("layer", 0)
    if qt is not None:
        y = kops.gather_swiglu_q(xf, qt, idx, w.astype(F32), layer)
    else:
        y = kops.gather_swiglu(xf, p["wg"], p["wu"], p["wd"], idx,
                               w.astype(F32), layer)
    return y.astype(xf.dtype)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

def moe_apply(cfg: ModelConfig, p: dict, x: jax.Array,
              capture: bool = False, need_aux: bool = True) -> MoEOutput:
    """x: [B, S, d] (or [B, 1, d] for decode).

    ``need_aux=False`` (serving prefill/decode): routing goes through
    :func:`route_infer` — no [.., N] probs materialization, no
    :func:`balance_loss` — and ``aux_loss`` is a constant zero. Training and
    calibration capture keep the full :func:`route` path."""
    m = cfg.moe
    B, S, d = x.shape
    if capture or need_aux:
        w, idx, probs = route(cfg, p, x)
        aux = balance_loss(cfg, probs, idx)
    else:
        w, idx = route_infer(cfg, p, x)
        aux = jnp.zeros((), F32)
    ridx = jnp.take(p["remap"], idx)                 # original -> real experts

    T = B * S
    xf = x.reshape(T, d)
    wf = w.reshape(T, m.top_k)
    rf = ridx.reshape(T, m.top_k)

    if m.ep_axis is not None and m.ep_degree > 1 \
            and m.dispatch in ("gather", "ragged"):
        # Expert-parallel dispatch (DESIGN.md §13): tables are sharded over
        # the ``ep_axis`` mesh axis and this trace is inside a shard_map.
        # The combine-mode selection mirrors the single-device rule below
        # (T here is the per-data-shard slice — smaller than the global
        # count, so a single-device gather-shaped call stays gather-shaped).
        from repro.models.moe_ep import moe_apply_ep
        gather_mode = (m.dispatch == "gather" and S == 1
                       and T <= m.gather_max_tokens)
        y = moe_apply_ep(cfg, p, xf, wf, rf, gather_mode)
    elif m.dispatch == "gather":
        # trace-time selection (shapes are static, so each jit
        # specialization picks exactly one path): gather only for
        # decode-SHAPED calls — one token per sequence (S == 1) and at most
        # ``gather_max_tokens`` of them. Prefill buckets (S > 1) always
        # keep the sort-based grouped kernel, whatever their token count
        # (DESIGN.md §7).
        if S == 1 and T <= m.gather_max_tokens:
            y = _moe_gather(cfg, p, xf, wf, rf)
        else:
            y = _moe_ragged(cfg, own_tables(p), xf, wf, rf)
    elif m.dispatch == "ragged":
        y = _moe_ragged(cfg, own_tables(p), xf, wf, rf)
    else:
        G = min(m.group_size, T)
        n_groups = -(-T // G)
        pad = n_groups * G - T
        if pad:
            xf = jnp.pad(xf, ((0, pad), (0, 0)))
            wf = jnp.pad(wf, ((0, pad), (0, 0)))
            rf = jnp.pad(rf, ((0, pad), (0, 0)))
        y = _moe_dense_groups(cfg, own_tables(p),
                              xf.reshape(n_groups, G, d),
                              wf.reshape(n_groups, G, m.top_k),
                              rf.reshape(n_groups, G, m.top_k))
        y = y.reshape(n_groups * G, d)[:T]

    y = y.reshape(B, S, d)
    if m.n_shared_experts:
        y = y + mlp_apply(p["shared"], x)

    if capture:
        counts = jnp.sum(
            jax.nn.one_hot(idx.reshape(-1, m.top_k), m.n_experts, dtype=F32),
            axis=(0, 1))
        return MoEOutput(y, aux, x, counts, idx)
    return MoEOutput(y, aux, None, None, None)
