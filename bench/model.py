"""The served model's configuration, built from a configuration file.

The file holds the published ``config.json`` keys (cut where ``reduced``
says) plus a ``mergemoe`` entry for a MergeMoE artifact. This module maps
them onto the program's ``ModelConfig`` and refuses what the program cannot
serve as stated.
"""
from __future__ import annotations

import dataclasses

#: published keys the program has no switch for; the values it serves
_FIXED = {"hidden_act": "silu", "norm_topk_prob": True,
          "decoder_sparse_step": 1, "mlp_only_layers": [],
          "use_sliding_window": False}


def widths(conf: dict) -> dict:
    """The sizes the benchmark's own arithmetic needs, by short name."""
    m = conf.get("mergemoe") or {}
    L = int(conf["num_hidden_layers"])
    merged = int(m.get("merged_experts", 0))
    split = int(0.6 * L) if merged else L
    E = int(conf["num_experts"])
    return dict(
        L=L, d=int(conf["hidden_size"]), nq=int(conf["num_attention_heads"]),
        nkv=int(conf["num_key_value_heads"]), hd=int(conf["head_dim"]),
        E=E, k=int(conf["num_experts_per_tok"]),
        f=int(conf["moe_intermediate_size"]), V=int(conf["vocab_size"]),
        eps=float(conf["rms_norm_eps"]), theta=float(conf["rope_theta"]),
        merged=merged, split=split,
        # live experts of each layer, in layer order
        live=[E] * split + [merged] * (L - split))


def program_config(conf: dict):
    """The program's ``ModelConfig`` for ``conf`` (merged per the program's
    own plan when ``conf['mergemoe']`` is set)."""
    from repro import configs
    from repro.core import plan as PLAN

    for key, want in _FIXED.items():
        if conf.get(key, want) != want:
            raise SystemExit(f"{key}={conf[key]!r}: the program serves "
                             f"only {want!r}")
    if conf.get("sliding_window") not in (None,):
        raise SystemExit("sliding_window: the program has no window")
    w = widths(conf)
    base = configs.get(conf["program_arch"])
    moe = dataclasses.replace(base.moe, n_experts=w["E"], top_k=w["k"],
                              d_ff_expert=w["f"], n_shared_experts=0)
    cfg = base.replace(
        n_layers=w["L"], d_model=w["d"], n_heads=w["nq"],
        n_kv_heads=w["nkv"], head_dim=w["hd"], vocab_size=w["V"],
        rope_theta=w["theta"], norm_eps=w["eps"],
        qkv_bias=bool(conf["attention_bias"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        dtype=conf["torch_dtype"], moe=moe, d_ff=0, remat="none")
    if w["merged"]:
        plan = PLAN.uniform(cfg, merged_experts=w["merged"])
        cfg = plan.apply_to(cfg)
        assert cfg.moe_split == w["split"], (cfg.moe_split, w["split"])
    return cfg
