"""Composable decoder / encoder-decoder LM covering all assigned archs.

Layer stacks are *scan-stacked* (leading ``L`` dim) to keep HLO size and
compile time bounded at 38–61 layers.  Three forward modes:

- **train**:  TinyTrain sparse-update mode.  The stack is compiled into
  segments from a static :class:`~repro.core.policy.SparseUpdatePolicy`:
  layers below the backprop horizon run inside ``stop_gradient`` (no saved
  activations, no backward FLOPs — paper Appendix A.4 B3/B4), unselected
  layers in the backprop span run in scanned runs, and each selected layer is
  unrolled with its channel deltas.
- **probe**:  Fisher-information probe.  Every unit's activation is scaled by
  a ones-valued *tap*; ``grad(loss, taps)`` yields exactly
  ``u_{n,o} = Σ_d a_nd·g_nd`` (Eq. 2's inner sum) without storing activation
  gradients — an O(B·C) memory footprint instead of O(B·S·C).
- **serve**:  prefill/decode with stacked KV/SSM caches scanned through.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import layers as L
from . import overlay as OV
from . import ssm as S
from .api import ArchConfig

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Per-layer unit map (what TinyTrain can select)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class UnitDesc:
    """One selectable unit: (layer, kind) with its channel axis size."""

    layer: int
    kind: str  # mlp | attn | moe | ssm
    n_channels: int
    n_params: int
    macs_per_token: int


def block_kind(cfg: ArchConfig, layer: int) -> str:
    """Mixer kind of a decoder layer."""
    if cfg.family == "ssm":
        return "ssm"
    if cfg.family == "hybrid":
        return "ssm"
    if cfg.mla:
        return "mla"
    return "attn"


def ffn_kind(cfg: ArchConfig, layer: int) -> str:
    if cfg.family == "ssm" or cfg.family == "hybrid":
        return "none"
    if cfg.n_experts and layer >= cfg.moe_start_layer:
        return "moe"
    return "mlp"


def unit_descs(cfg: ArchConfig) -> List[UnitDesc]:
    """Enumerate selectable units with parameter and MAC costs (Eq. 3 terms)."""
    out: List[UnitDesc] = []
    d = cfg.d_model
    for i in range(cfg.n_layers):
        bk, fk = block_kind(cfg, i), ffn_kind(cfg, i)
        if bk in ("attn", "mla"):
            if cfg.mla:
                np_ = (
                    d * cfg.q_lora_rank
                    + cfg.q_lora_rank * cfg.n_heads * (cfg.qk_nope_dim + cfg.qk_rope_dim)
                    + d * cfg.kv_lora_rank
                    + cfg.kv_lora_rank * cfg.n_heads * (cfg.qk_nope_dim + cfg.v_head_dim)
                    + d * cfg.qk_rope_dim
                    + cfg.n_heads * cfg.v_head_dim * d
                )
                out.append(UnitDesc(i, "attn", cfg.n_heads, np_, np_))
            else:
                np_ = d * (cfg.q_dim * 2 + cfg.kv_dim * 2)
                out.append(UnitDesc(i, "attn", cfg.n_heads, np_, np_))
            if cfg.is_encoder_decoder:
                # decoder cross-attention is selectable per head like self
                # attention (same projection shapes; K/V over enc tokens)
                np_x = d * (cfg.q_dim * 2 + cfg.kv_dim * 2)
                out.append(UnitDesc(i, "xattn", cfg.n_heads, np_x, np_x))
        elif bk == "ssm":
            di, n = cfg.d_inner, cfg.ssm_state
            np_ = d * (2 * di + 2 * n + cfg.n_ssm_heads) + di * d
            out.append(UnitDesc(i, "ssm", cfg.n_ssm_heads, np_, np_))
        if fk == "mlp":
            f = cfg.dense_d_ff if (cfg.n_experts and i < cfg.moe_start_layer) else cfg.d_ff
            mult = 3 if cfg.act in ("swiglu", "geglu") else 2
            np_ = mult * d * f
            out.append(UnitDesc(i, "mlp", f, np_, np_))
        elif fk == "moe":
            np_ = cfg.n_experts * 3 * d * cfg.d_expert
            macs = cfg.top_k * 3 * d * cfg.d_expert  # active-expert MACs
            out.append(UnitDesc(i, "moe", cfg.n_experts, np_, macs))
    return out


# ---------------------------------------------------------------------------
# Parameter initialisation
# ---------------------------------------------------------------------------


def _layer_init(cfg: ArchConfig, key, layer: int, dtype) -> Params:
    ks = iter(jax.random.split(key, 8))
    p: Params = {"norm1": L.norm_init(cfg.norm, cfg.d_model, dtype)}
    bk, fk = block_kind(cfg, layer), ffn_kind(cfg, layer)
    if bk == "mla":
        p["attn"] = L.mla_init(next(ks), cfg, dtype)
    elif bk == "attn":
        p["attn"] = L.attention_init(next(ks), cfg, dtype)
    else:
        p["ssm"] = S.ssd_init(next(ks), cfg, dtype)
    if fk == "mlp":
        f = cfg.dense_d_ff if (cfg.n_experts and layer < cfg.moe_start_layer) else cfg.d_ff
        p["norm2"] = L.norm_init(cfg.norm, cfg.d_model, dtype)
        p["mlp"] = L.mlp_init(next(ks), cfg.d_model, f, cfg.act, dtype)
    elif fk == "moe":
        p["norm2"] = L.norm_init(cfg.norm, cfg.d_model, dtype)
        p["moe"] = L.moe_init(next(ks), cfg, dtype)
    return p


def _stack_init(cfg: ArchConfig, key, layer_ids: Sequence[int], dtype) -> Params:
    """Init a homogeneous stack of layers with a leading L dim."""
    keys = jax.random.split(key, len(layer_ids))
    per_layer = [_layer_init(cfg, keys[j], lid, dtype) for j, lid in enumerate(layer_ids)]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_layer)


def _enc_layer_init(cfg: ArchConfig, key, dtype) -> Params:
    ks = jax.random.split(key, 2)
    return {
        "norm1": L.norm_init(cfg.norm, cfg.d_model, dtype),
        "attn": L.attention_init(ks[0], cfg, dtype),
        "norm2": L.norm_init(cfg.norm, cfg.d_model, dtype),
        "mlp": L.mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg.act, dtype),
    }


def _dec_xattn_layer_init(cfg: ArchConfig, key, dtype) -> Params:
    ks = jax.random.split(key, 3)
    p = _enc_layer_init(cfg, ks[0], dtype)
    p["norm_x"] = L.norm_init(cfg.norm, cfg.d_model, dtype)
    p["xattn"] = L.attention_init(ks[1], cfg, dtype)
    return p


def stack_groups(cfg: ArchConfig) -> List[Tuple[str, List[int]]]:
    """Partition decoder layers into homogeneous scan groups."""
    groups: List[Tuple[str, List[int]]] = []
    for i in range(cfg.n_layers):
        sig = block_kind(cfg, i) + "/" + ffn_kind(cfg, i)
        if cfg.n_experts and i < cfg.moe_start_layer:
            sig += "/dense_head"
        if groups and groups[-1][0] == sig:
            groups[-1][1].append(i)
        else:
            groups.append((sig, [i]))
    return groups


def init_params(cfg: ArchConfig, key) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    ks = iter(jax.random.split(key, 16))
    p: Params = {"embed": L.embed_init(next(ks), cfg.vocab, cfg.d_model, dtype)}
    groups = stack_groups(cfg)
    p["stacks"] = {}
    for gi, (_, ids) in enumerate(groups):
        p["stacks"][f"g{gi}"] = _stack_init(cfg, next(ks), ids, dtype)
    if cfg.family == "hybrid" and cfg.hybrid_attn_every:
        # one weight-shared attention+MLP block (zamba2)
        p["shared_attn"] = {
            "norm1": L.norm_init(cfg.norm, cfg.d_model, dtype),
            "attn": L.attention_init(next(ks), cfg, dtype),
            "norm2": L.norm_init(cfg.norm, cfg.d_model, dtype),
            "mlp": L.mlp_init(next(ks), cfg.d_model, cfg.d_ff, cfg.act, dtype),
        }
    if cfg.is_encoder_decoder:
        enc_keys = jax.random.split(next(ks), cfg.n_enc_layers)
        enc = [_enc_layer_init(cfg, k, dtype) for k in enc_keys]
        p["encoder"] = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *enc)
        p["enc_norm"] = L.norm_init(cfg.norm, cfg.d_model, dtype)
        # decoder layers get cross attention
        dec_keys = jax.random.split(next(ks), cfg.n_layers)
        dec = [_dec_xattn_layer_init(cfg, k, dtype) for k in dec_keys]
        p["stacks"] = {"g0": jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *dec)}
    if cfg.family == "vlm":
        p["img_proj"] = L.dense_init(next(ks), cfg.img_embed_dim, cfg.d_model, dtype)
    p["final_norm"] = L.norm_init(cfg.norm, cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        p["unembed"] = L.dense_init(next(ks), cfg.d_model, cfg.vocab, dtype)
    if cfg.mtp:
        p["mtp"] = {
            "proj": L.dense_init(next(ks), 2 * cfg.d_model, cfg.d_model, dtype),
            "block": _stack_init(cfg, next(ks), [cfg.n_layers - 1], dtype),
            "norm": L.norm_init(cfg.norm, cfg.d_model, dtype),
        }
    return p


# ---------------------------------------------------------------------------
# Block application
# ---------------------------------------------------------------------------


def _apply_block(
    cfg: ArchConfig,
    p: Params,
    x: jax.Array,
    positions: jax.Array,
    layer: int,
    *,
    cache: Optional[Params] = None,
    enc_out: Optional[jax.Array] = None,
    deltas: Optional[Dict[str, Params]] = None,
    chan_idx: Optional[Dict[str, np.ndarray]] = None,
    taps: Optional[Dict[str, jax.Array]] = None,
    valid: Optional[jax.Array] = None,
    drop_free: bool = False,
    overlay: Optional[Dict[str, Tuple[Any, Any]]] = None,
) -> Tuple[jax.Array, Optional[Params], jax.Array]:
    """One decoder layer.  Returns (x, new_cache, moe_aux).

    ``valid`` (B, S) enables the mixers' block-prefill cache mode (per-slot
    multi-token cache writes with ragged-tail masking); ``drop_free`` sizes
    MoE expert queues so routed tokens are never dropped (serving parity).

    ``overlay`` maps this layer's policy unit kinds to slot-stacked
    ``(delta_pack, channel_idx)`` pairs (leaves carry a leading slot axis):
    the affected weights are replaced with per-slot effective weights
    ``W ⊕ scatter(ΔW_b, idx_b)`` via the unit-kind overlay registry — the
    serving engine's per-user personalisation path (``deltas``/``chan_idx``
    are the adaptation path; the two are not combined).
    """
    bk, fk = block_kind(cfg, layer), ffn_kind(cfg, layer)
    aux = jnp.zeros((), jnp.float32)
    deltas = deltas or {}
    chan_idx = chan_idx or {}
    taps = taps or {}
    ov = overlay or {}
    new_cache: Optional[Params] = dict(cache) if cache is not None else None

    def eff(kind: str, key: str) -> Params:
        if kind in ov:
            d_stk, i_stk = ov[kind]
            return OV.slot_params(cfg, kind, p[key], d_stk, i_stk)
        return p[key]

    h = L.apply_norm(cfg.norm, p["norm1"], x)
    if bk == "mla":
        y, c = L.mla_apply(
            eff("attn", "attn"), h, cfg, positions=positions,
            cache=cache.get("attn") if cache else None,
            delta=deltas.get("attn"), head_idx=chan_idx.get("attn"),
            valid=valid,
        )
        if new_cache is not None:
            new_cache["attn"] = c
    elif bk == "attn":
        y, c = L.attention_apply(
            eff("attn", "attn"), h, cfg, positions=positions,
            cache=cache.get("attn") if cache else None,
            delta=deltas.get("attn"), head_idx=chan_idx.get("attn"),
            valid=valid,
        )
        if new_cache is not None:
            new_cache["attn"] = c
    else:
        y, c = S.ssd_apply(
            eff("ssm", "ssm"), h, cfg,
            cache=cache.get("ssm") if cache else None,
            delta=deltas.get("ssm"), head_idx=chan_idx.get("ssm"),
            valid=valid,
        )
        if new_cache is not None:
            new_cache["ssm"] = c
    if "mixer" in taps:
        # tap over per-head/per-channel outputs: scale (B, n_units)
        nb = taps["mixer"].shape[-1]
        yb = y.reshape(y.shape[0], y.shape[1], nb, -1)
        y = (yb * taps["mixer"][:, None, :, None].astype(yb.dtype)
             ).reshape(y.shape)
    x = x + y

    if fk != "none":
        h = L.apply_norm(cfg.norm, p["norm2"], x)
        if fk == "moe":
            y, aux = L.moe_apply(
                eff("moe", "moe"), h, cfg,
                delta=deltas.get("moe"), expert_idx=chan_idx.get("moe"),
                tap=taps.get("ffn"), drop_free=drop_free,
            )
        else:
            if "ffn" in taps:
                # tap on the hidden d_ff activation via scaled gate path
                y = _mlp_tapped(p["mlp"], h, cfg.act, taps["ffn"])
            else:
                y = L.mlp_apply(
                    eff("mlp", "mlp"), h, cfg.act,
                    delta=deltas.get("mlp"), idx=chan_idx.get("mlp"),
                )
        x = x + y

    if "norm_x" in p:
        # decoder-with-cross-attn variant (whisper): xattn after self attn.
        # Gate on the layer's own parameters, not on enc_out — running an
        # encoder-decoder layer without encoder outputs must fail at trace
        # time instead of silently decoding without cross-attention.
        if enc_out is None:
            raise ValueError(
                "encoder-decoder layer has cross-attention parameters but "
                "no enc_out was supplied — refusing to silently skip xattn "
                "(pass the encoder outputs / Request.enc_feats)"
            )
        h = L.apply_norm(cfg.norm, p["norm_x"], x)
        y, _ = L.attention_apply(
            eff("xattn", "xattn"), h, cfg, positions=positions,
            cross_hidden=enc_out,
            delta=deltas.get("xattn"), head_idx=chan_idx.get("xattn"),
        )
        if "xattn" in taps:
            nb = taps["xattn"].shape[-1]
            yb = y.reshape(y.shape[0], y.shape[1], nb, -1)
            y = (yb * taps["xattn"][:, None, :, None].astype(yb.dtype)
                 ).reshape(y.shape)
        x = x + y
    return x, new_cache, aux


def _mlp_tapped(p: Params, x: jax.Array, act: str, tap: jax.Array) -> jax.Array:
    """MLP with a per-(sample, d_ff-channel) tap scale on the hidden act."""
    if act in ("swiglu", "geglu"):
        h = L._act(act, x @ p["w_gate"]) * (x @ p["w_up"])
    else:
        h = L._act(act, x @ p["w_up"])
    h = h * tap[:, None, :].astype(h.dtype)
    return h @ p["w_down"]


# ---------------------------------------------------------------------------
# Forward driver
# ---------------------------------------------------------------------------


def _shared_attn_apply(cfg: ArchConfig, p: Params, x, positions, cache=None,
                       valid=None):
    h = L.apply_norm(cfg.norm, p["norm1"], x)
    y, c = L.attention_apply(p["attn"], h, cfg, positions=positions,
                             cache=cache, valid=valid)
    x = x + y
    h = L.apply_norm(cfg.norm, p["norm2"], x)
    x = x + L.mlp_apply(p["mlp"], h, cfg.act)
    return x, c


def _scan_run(cfg, stack, x, positions, lo, hi, group_ids, *, taps=None,
              caches=None, enc_out=None, stop_grad=False, remat=False,
              valid=None, drop_free=False):
    """Scan layers [lo, hi) of one stack group (absolute layer ids group_ids).

    taps: stacked (n, ...) tap arrays aligned with the slice, or None.
    caches: stacked caches aligned with the slice, or None.
    """
    n = hi - lo
    if n <= 0:
        return x, caches, jnp.zeros((), jnp.float32)
    sl = jax.tree_util.tree_map(lambda a: a[lo:hi], stack)
    if stop_grad:
        sl = jax.tree_util.tree_map(lax.stop_gradient, sl)
        x = lax.stop_gradient(x)
    layer0 = group_ids[lo]

    if n == 1:
        lp = jax.tree_util.tree_map(lambda a: a[0], sl)
        tap = jax.tree_util.tree_map(lambda a: a[0], taps) if taps else {}
        cache_in = jax.tree_util.tree_map(lambda a: a[0], caches) if caches else None
        x, nc, aux = _apply_block(
            cfg, lp, x, positions, layer0, cache=cache_in, enc_out=enc_out,
            taps=tap, valid=valid, drop_free=drop_free,
        )
        ncs = (
            jax.tree_util.tree_map(lambda a: a[None], nc) if caches else None
        )
        return x, ncs, aux

    if taps is None and caches is None:
        def body2(carry, lp):
            xcur = carry
            xcur, _, aux = _apply_block(cfg, lp, xcur, positions, layer0,
                                        enc_out=enc_out, drop_free=drop_free)
            return xcur, aux
        if remat and not stop_grad:
            body2 = jax.checkpoint(body2)
        x, auxs = lax.scan(body2, x, sl)
        return x, None, jnp.sum(auxs)
    if caches is None:
        def body3(carry, xs):
            lp, tap = xs
            xcur = carry
            xcur, _, aux = _apply_block(cfg, lp, xcur, positions, layer0,
                                        enc_out=enc_out, taps=tap,
                                        drop_free=drop_free)
            return xcur, aux
        x, auxs = lax.scan(body3, x, (sl, taps))
        return x, None, jnp.sum(auxs)

    def body4(carry, xs):
        lp, cache_in = xs
        xcur = carry
        xcur, nc, aux = _apply_block(cfg, lp, xcur, positions, layer0,
                                     cache=cache_in, enc_out=enc_out,
                                     valid=valid, drop_free=drop_free)
        return xcur, (nc, aux)

    x, (ncs, auxs) = lax.scan(body4, x, (sl, caches))
    return x, ncs, jnp.sum(auxs)


def forward_hidden(
    cfg: ArchConfig,
    params: Params,
    x: jax.Array,
    positions: jax.Array,
    *,
    caches: Optional[Dict[str, Any]] = None,
    enc_out: Optional[Tuple[jax.Array, jax.Array]] = None,
    deltas: Optional[Dict[str, Params]] = None,
    plan=None,  # repro.core.policy.SparseUpdatePolicy
    taps: Optional[Dict[str, Any]] = None,
    chan_idx: Optional[Dict[int, Dict[str, jax.Array]]] = None,
    seq_valid: Optional[jax.Array] = None,
    drop_free: bool = False,
    overlay: Optional[Dict[int, Dict[str, Tuple[Any, Any]]]] = None,
) -> Tuple[jax.Array, Optional[Dict[str, Any]], jax.Array]:
    """Run the decoder stacks.  Exactly one of (deltas+plan, taps, caches)
    modes may be active; all may be None for plain inference.

    ``chan_idx`` optionally overrides the plan's static channel indices with
    *traced* arrays: the adaptation engine jits one step per policy
    *structure* and feeds per-task channel choices as runtime arguments
    (no recompile per task).

    ``seq_valid`` (B, S) enables block-prefill cache mode: every cached
    mixer writes its slot's left-aligned valid tokens at that slot's own
    cache cursor (ragged tails masked) instead of assuming batch-aligned
    sequence positions.  ``drop_free`` switches MoE layers to
    never-drop expert capacity (the serving contract).

    ``overlay`` ({layer: {kind: (delta_pack, channel_idx)}}, slot-stacked
    leaves) applies per-slot effective weights on the plan's selected
    layers — the serving engine's personalisation path.  Requires ``plan``
    so those layers get their own (non-scanned) segments."""
    groups = stack_groups(cfg)
    aux_total = jnp.zeros((), jnp.float32)
    new_caches: Dict[str, Any] = {}
    selected = set(plan.selected_layers()) if plan is not None else set()
    # remat layers inside the backprop span: TinyTrain keeps the span short,
    # so the recompute cost is bounded while activation memory drops.
    # Opt-in via policy meta (see EXPERIMENTS.md §Perf for the measured
    # trade-off per backend).
    remat = plan is not None and bool((plan.meta or {}).get("remat", False))

    shared_every = cfg.hybrid_attn_every if cfg.family == "hybrid" else 0

    for gi, (_, ids) in enumerate(groups):
        stack = params["stacks"][f"g{gi}"]
        g_taps = taps.get(f"g{gi}") if taps else None
        g_caches = caches.get(f"g{gi}") if caches else None
        n = len(ids)
        out_caches = [None] * n

        # split group into segments around selected layers / horizon / shared
        boundaries = set()
        for j, lid in enumerate(ids):
            if lid in selected:
                boundaries.add(j)
                boundaries.add(j + 1)
            if plan is not None and ids[0] < plan.horizon <= lid:
                boundaries.add(j)
            if shared_every and (lid + 1) % shared_every == 0:
                boundaries.add(j + 1)
        cuts = sorted(boundaries | {0, n})
        segs = [(cuts[i], cuts[i + 1]) for i in range(len(cuts) - 1) if cuts[i] < cuts[i + 1]]

        for (lo, hi) in segs:
            lid = ids[lo]
            if hi - lo == 1 and lid in selected:
                lp = jax.tree_util.tree_map(lambda a: a[lo], stack)
                lp = jax.tree_util.tree_map(lax.stop_gradient, lp)
                tap = jax.tree_util.tree_map(lambda a: a[lo], g_taps) if g_taps else {}
                cache_in = (
                    jax.tree_util.tree_map(lambda a: a[lo], g_caches)
                    if g_caches else None
                )

                ci = None
                if plan is not None:
                    ci = (chan_idx or {}).get(lid) or plan.channel_idx.get(lid)

                def sel_block(lp_, x_, d_, ci_):
                    return _apply_block(
                        cfg, lp_, x_, positions, lid,
                        cache=cache_in, enc_out=enc_out, deltas=d_,
                        chan_idx=ci_, taps=tap, valid=seq_valid,
                        drop_free=drop_free,
                        overlay=(overlay or {}).get(lid),
                    )

                if remat:
                    sel_block = jax.checkpoint(sel_block, static_argnums=())
                x, nc, aux = sel_block(lp, x, (deltas or {}).get(f"L{lid}"), ci)
                if g_caches is not None:
                    out_caches[lo] = nc
            else:
                stop = plan is not None and ids[hi - 1] < plan.horizon
                seg_taps = (
                    jax.tree_util.tree_map(lambda a: a[lo:hi], g_taps)
                    if g_taps else None
                )
                seg_caches = (
                    jax.tree_util.tree_map(lambda a: a[lo:hi], g_caches)
                    if g_caches else None
                )
                x, ncs, aux = _scan_run(
                    cfg, stack, x, positions, lo, hi, ids,
                    taps=seg_taps, caches=seg_caches, enc_out=enc_out,
                    stop_grad=stop, remat=remat, valid=seq_valid,
                    drop_free=drop_free,
                )
                if g_caches is not None:
                    for j in range(lo, hi):
                        out_caches[j] = jax.tree_util.tree_map(
                            lambda a: a[j - lo], ncs
                        )
            aux_total = aux_total + aux
            # zamba2 shared attention block after every k-th layer
            if shared_every:
                last = ids[hi - 1]
                if (last + 1) % shared_every == 0:
                    sc = caches.get(f"shared{last}") if caches else None
                    x, nc = _shared_attn_apply(
                        cfg, params["shared_attn"], x, positions, cache=sc,
                        valid=seq_valid,
                    )
                    if caches is not None:
                        new_caches[f"shared{last}"] = nc

        if g_caches is not None:
            new_caches[f"g{gi}"] = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *out_caches
            )

    x = L.apply_norm(cfg.norm, params["final_norm"], x)
    return x, (new_caches if caches is not None else None), aux_total


# ---------------------------------------------------------------------------
# Embedding / head / losses
# ---------------------------------------------------------------------------


def embed_tokens(cfg: ArchConfig, params: Params, tokens: jax.Array) -> jax.Array:
    e = params["embed"][tokens]
    if cfg.family in ("vlm", "dense") and cfg.norm == "rmsnorm" and cfg.tie_embeddings:
        # gemma-style sqrt(d) embedding scale (harmless for others)
        e = e * jnp.asarray(math.sqrt(cfg.d_model), e.dtype)
    return e


def unembed(cfg: ArchConfig, params: Params, h: jax.Array) -> jax.Array:
    w = params["unembed"] if not cfg.tie_embeddings else params["embed"].T
    return h @ w


def encode(cfg: ArchConfig, params: Params, frames: jax.Array) -> jax.Array:
    """Whisper encoder over precomputed (stub) frame embeddings."""
    x = frames
    positions = jnp.broadcast_to(
        jnp.arange(frames.shape[1])[None], frames.shape[:2]
    )

    def body(carry, lp):
        xcur = carry
        h = L.apply_norm(cfg.norm, lp["norm1"], xcur)
        y, _ = L.attention_apply(lp["attn"], h, cfg, positions=positions,
                                 causal=False)
        xcur = xcur + y
        h = L.apply_norm(cfg.norm, lp["norm2"], xcur)
        xcur = xcur + L.mlp_apply(lp["mlp"], h, cfg.act)
        return xcur, None

    x, _ = lax.scan(body, x, params["encoder"])
    return L.apply_norm(cfg.norm, params["enc_norm"], x)


def build_inputs(cfg: ArchConfig, params: Params, batch: Dict[str, jax.Array]):
    """Map a raw batch to (x_embed, positions, enc_out)."""
    tokens = batch["tokens"]
    x = embed_tokens(cfg, params, tokens)
    enc_out = None
    if cfg.family == "vlm":
        img = batch["image_embeds"] @ params["img_proj"]
        x = jnp.concatenate([img.astype(x.dtype), x], axis=1)
    if cfg.is_encoder_decoder:
        enc_h = encode(cfg, params, batch["frames"].astype(x.dtype))
        # precompute nothing per-layer; cross-attn projects per layer
        enc_out = enc_h
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(x.shape[1])[None], x.shape[:2])
    return x, positions, enc_out


def _ce_sums(cfg, params, h, labels) -> Tuple[jax.Array, jax.Array]:
    """(Σ nll, Σ mask) over one hidden chunk."""
    logits = unembed(cfg, params, h).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1
    )[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum((logz - gold) * mask), jnp.sum(mask)


def ce_from_hidden(
    cfg: ArchConfig, params: Params, h: jax.Array, labels: jax.Array,
    logit_chunk: int = 0,
) -> jax.Array:
    """Cross-entropy; ``logit_chunk`` > 0 scans over sequence chunks so the
    (B, S, V) logits tensor never materialises (peak memory / chunk-count).
    """
    b, s, _ = h.shape
    if logit_chunk and s > logit_chunk and s % logit_chunk == 0:
        nc = s // logit_chunk
        hs = jnp.moveaxis(h.reshape(b, nc, logit_chunk, -1), 1, 0)
        ls = jnp.moveaxis(labels.reshape(b, nc, logit_chunk), 1, 0)

        @jax.checkpoint  # recompute chunk logits in backward; never store B,S,V
        def body(carry, xs):
            hc, lc = xs
            nll, m = _ce_sums(cfg, params, hc, lc)
            return (carry[0] + nll, carry[1] + m), None

        (nll, m), _ = lax.scan(body, (jnp.zeros(()), jnp.zeros(())), (hs, ls))
    else:
        nll, m = _ce_sums(cfg, params, h, labels)
    return nll / jnp.maximum(m, 1.0)


def lm_loss(
    cfg: ArchConfig,
    params: Params,
    batch: Dict[str, jax.Array],
    *,
    deltas: Optional[Dict[str, Params]] = None,
    plan=None,
    taps: Optional[Dict[str, Any]] = None,
    logit_chunk: int = 0,
    chan_idx=None,
) -> jax.Array:
    """Next-token cross-entropy (mean over positions with label >= 0)."""
    x, positions, enc_out = build_inputs(cfg, params, batch)
    h, _, aux = forward_hidden(
        cfg, params, x, positions,
        deltas=deltas, plan=plan, taps=taps, enc_out=enc_out,
        chan_idx=chan_idx,
    )
    labels = batch["labels"]
    if cfg.family == "vlm":
        h = h[:, -labels.shape[1]:]
    loss = ce_from_hidden(cfg, params, h, labels, logit_chunk)
    if cfg.n_experts:
        loss = loss + 0.01 * aux
    if cfg.mtp:
        loss = loss + 0.1 * _mtp_loss(cfg, params, h, batch, logit_chunk)
    return loss


def _mtp_loss(cfg, params, h, batch, logit_chunk: int = 0):
    """DeepSeek-style 1-depth multi-token prediction head."""
    tokens, labels = batch["tokens"], batch["labels"]
    if cfg.family == "vlm":
        return jnp.zeros((), jnp.float32)
    nxt = embed_tokens(cfg, params, jnp.roll(tokens, -1, axis=1))
    z = jnp.concatenate([h[:, :-2], nxt[:, 1:-1].astype(h.dtype)], axis=-1)
    z = z @ params["mtp"]["proj"]
    positions = jnp.broadcast_to(jnp.arange(z.shape[1])[None], z.shape[:2])
    lp = jax.tree_util.tree_map(lambda a: a[0], params["mtp"]["block"])
    z, _, _ = _apply_block(cfg, lp, z, positions, cfg.n_layers - 1)
    z = L.apply_norm(cfg.norm, params["mtp"]["norm"], z)
    return ce_from_hidden(cfg, params, z, labels[:, 2:], logit_chunk)


def pooled_features(
    cfg: ArchConfig,
    params: Params,
    batch: Dict[str, jax.Array],
    *,
    deltas=None,
    plan=None,
    taps=None,
    chan_idx=None,
) -> jax.Array:
    """Mean-pooled final hidden state — the backbone feature map f(x) used by
    ProtoNet (Sec. 2.1) for few-shot episodic adaptation of LM backbones."""
    x, positions, enc_out = build_inputs(cfg, params, batch)
    h, _, _ = forward_hidden(cfg, params, x, positions, deltas=deltas,
                             plan=plan, taps=taps, enc_out=enc_out,
                             chan_idx=chan_idx)
    mask = (batch["tokens"] >= 0).astype(h.dtype)
    if cfg.family == "vlm":
        pad = jnp.ones((h.shape[0], h.shape[1] - mask.shape[1]), h.dtype)
        mask = jnp.concatenate([pad, mask], axis=1)
    h = jnp.sum(h * mask[..., None], axis=1) / jnp.maximum(
        jnp.sum(mask, axis=1, keepdims=True), 1.0
    )
    return h


# ---------------------------------------------------------------------------
# Serving: cache init / prefill / decode
# ---------------------------------------------------------------------------


def init_caches(cfg: ArchConfig, batch: int, max_len: int, dtype=None, *,
                paging=None) -> Dict[str, Any]:
    """Decode caches for a slot batch.

    ``paging`` is an optional :class:`repro.serving.paging.PagingSpec`; if
    omitted and ``cfg.kv_paging`` is set, a default spec (page budget =
    fixed-stripe capacity) is built from the config knobs.  Paged layers
    store K/V (or MLA latents) as page arenas shared across slots plus a
    per-slot ``page_table``; rolling sliding-window buffers (window <
    max_len, already O(window)) and SSM recurrent state (O(1)) stay
    contiguous.
    """
    dtype = dtype or jnp.dtype(cfg.dtype)
    if paging is None and getattr(cfg, "kv_paging", False):
        from ..serving.paging import PagingSpec
        paging = PagingSpec.build(max_len, page_size=cfg.kv_page_size,
                                  slots=batch, int8=cfg.kv_int8)
    rolling = bool(cfg.sliding_window) and cfg.sliding_window < max_len

    def _paged(feats: Dict[str, Tuple[int, ...]]) -> Dict[str, Any]:
        from ..serving import paging as PG
        c = {name: PG.store_init(paging, shape, dtype)
             for name, shape in feats.items()}
        c["page_table"] = jnp.full((batch, paging.max_pages), -1, jnp.int32)
        c["len"] = jnp.zeros((batch,), jnp.int32)
        return c

    def _attn_cache() -> Dict[str, Any]:
        if paging is not None and not rolling:
            return _paged({"k": (cfg.n_kv_heads, cfg.head_dim),
                           "v": (cfg.n_kv_heads, cfg.head_dim)})
        s_max = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
        return {
            "k": jnp.zeros((batch, s_max, cfg.n_kv_heads, cfg.head_dim), dtype),
            "v": jnp.zeros((batch, s_max, cfg.n_kv_heads, cfg.head_dim), dtype),
            "len": jnp.zeros((batch,), jnp.int32),
        }

    groups = stack_groups(cfg)
    caches: Dict[str, Any] = {}
    for gi, (_, ids) in enumerate(groups):
        per = []
        for lid in ids:
            bk = block_kind(cfg, lid)
            c: Dict[str, Any] = {}
            if bk == "mla":
                if paging is not None:
                    c["attn"] = _paged({"ckv": (cfg.kv_lora_rank,),
                                        "krope": (cfg.qk_rope_dim,)})
                else:
                    c["attn"] = {
                        "ckv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
                        "krope": jnp.zeros((batch, max_len, cfg.qk_rope_dim), dtype),
                        "len": jnp.zeros((batch,), jnp.int32),
                    }
            elif bk == "attn":
                c["attn"] = _attn_cache()
            else:
                c["ssm"] = {
                    "conv": jnp.zeros((batch, cfg.d_conv - 1, cfg.d_inner + 2 * cfg.ssm_state), dtype),
                    "ssm": jnp.zeros((batch, cfg.n_ssm_heads, cfg.ssm_head_dim, cfg.ssm_state), dtype),
                    "len": jnp.zeros((batch,), jnp.int32),
                }
            per.append(c)
        caches[f"g{gi}"] = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per)
    if cfg.family == "hybrid" and cfg.hybrid_attn_every:
        for lid in range(cfg.hybrid_attn_every - 1, cfg.n_layers, cfg.hybrid_attn_every):
            caches[f"shared{lid}"] = _attn_cache()
    return caches


def reset_slot_state(caches: Dict[str, Any], mask: jax.Array) -> Dict[str, Any]:
    """Reset per-slot decode state for masked slots of a cache batch.

    ``mask`` is ``(B,)`` bool over the cache slot (batch) axis; masked slots
    are reset so a re-admitted request starts from a clean length-0 cache.
    Works both eagerly (host-side admission) and traced inside the serving
    ``lax.scan`` (device-side re-admission).

    Length leaves zero: attention masks K/V reads by ``kv_len``, so stale
    entries beyond the reset length are never attended to.  SSM recurrent
    state (conv window + state matrix) must zero outright — unlike K/V it
    feeds forward with no length masking, so a reused slot would otherwise
    leak the previous request's state into the new stream.
    """
    from ..utils import named_tree_map

    mask = jnp.asarray(mask)
    keep = (~mask)

    def fix(path, x):
        if path.endswith("len"):
            # len leaves are (B,) or layer-stacked (L, B): slot is last axis
            return jnp.where(mask, 0, x)
        parts = path.split("/")
        if "ssm" in parts:
            # recurrent state: slot axis sits after the stacked layer axis
            shape = [1] * x.ndim
            shape[1] = mask.shape[0]
            return x * keep.reshape(shape).astype(x.dtype)
        return x

    return named_tree_map(fix, caches)


def _swap_prefix(x: jax.Array, positions: jax.Array,
                 embed_prefix: Optional[jax.Array]) -> jax.Array:
    """Replace token embeddings at absolute positions < P with rows of
    ``embed_prefix`` (B, P, d_model) — the serving-path equivalent of
    :func:`build_inputs`'s image-prefix concat for VLM requests, applied
    positionally so block prefill and single-token decode both work."""
    if embed_prefix is None:
        return x
    n = embed_prefix.shape[1]
    sel = jnp.clip(positions, 0, n - 1)
    rows = jnp.take_along_axis(
        embed_prefix.astype(x.dtype), sel[..., None], axis=1)
    return jnp.where((positions < n)[..., None], rows, x)


def decode_step(
    cfg: ArchConfig,
    params: Params,
    tokens: jax.Array,  # (B, 1)
    caches: Dict[str, Any],
    pos: jax.Array,  # () shared or (B,) per-slot positions
    enc_out: Optional[jax.Array] = None,
    *,
    embed_prefix: Optional[jax.Array] = None,
    drop_free: bool = False,
    overlay: Optional[Dict[int, Dict[str, Tuple[Any, Any]]]] = None,
    plan=None,
) -> Tuple[jax.Array, Dict[str, Any]]:
    """One decode step: new token -> logits over vocab, updated caches.

    ``drop_free=True`` is the serving engines' setting: MoE expert queues
    are sized so no routed token drops, keeping a slot's stream independent
    of its batch neighbours (and of prefill block size).

    ``embed_prefix`` (B, P, d_model) substitutes precomputed embeddings at
    positions ``< P`` (the VLM image prefix): the engine feeds placeholder
    tokens there and this swap reproduces ``build_inputs``'s concat — image
    rows enter *without* the gemma sqrt(d) token-embedding scale.

    ``overlay`` + ``plan`` decode each slot against its own per-user delta
    set (see :func:`forward_hidden`).
    """
    x = embed_tokens(cfg, params, tokens)
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        positions = jnp.broadcast_to(pos[None, None], tokens.shape)
    else:
        positions = pos[:, None]
    x = _swap_prefix(x, positions, embed_prefix)
    h, new_caches, _ = forward_hidden(
        cfg, params, x, positions, caches=caches, enc_out=enc_out,
        drop_free=drop_free, overlay=overlay, plan=plan,
    )
    logits = unembed(cfg, params, h)
    return logits, new_caches


def prefill_block(
    cfg: ArchConfig,
    params: Params,
    tokens: jax.Array,  # (B, S) block of prompt tokens, left-aligned valid
    caches: Dict[str, Any],
    pos: jax.Array,  # (B,) per-slot absolute position of tokens[:, 0]
    valid: Optional[jax.Array] = None,  # (B, S) bool; None = all valid
    enc_out: Optional[jax.Array] = None,
    *,
    embed_prefix: Optional[jax.Array] = None,
    drop_free: bool = True,
    overlay: Optional[Dict[int, Dict[str, Tuple[Any, Any]]]] = None,
    plan=None,
) -> Tuple[jax.Array, Dict[str, Any]]:
    """Sequence-mode prompt ingestion: a whole (B, S) block per dispatch.

    Every cached mixer writes its slot's ``valid`` tokens in one shot at
    that slot's own cache cursor — attention scatters S K/V rows and runs
    causal block attention from per-slot offsets (the Pallas flash kernel
    on TPU, jnp fallback elsewhere); SSM layers fold the block through the
    conv window + recurrent state.  ``valid`` must be a left-aligned prefix
    mask per slot (ragged prompt tails; all-False rows are paused slots and
    advance nothing).  Returns (logits (B, S, vocab), new_caches); only
    logits at valid positions are meaningful.

    Feeding a prompt through ``prefill_block`` produces the same caches and
    next-token choice as feeding it token-by-token through
    :func:`decode_step` — the serving engine's block/token parity contract.
    """
    x = embed_tokens(cfg, params, tokens)
    s = tokens.shape[1]
    positions = jnp.asarray(pos)[:, None] + jnp.arange(s)[None, :]
    x = _swap_prefix(x, positions, embed_prefix)
    if valid is None:
        valid = jnp.ones(tokens.shape, bool)
    h, new_caches, _ = forward_hidden(
        cfg, params, x, positions, caches=caches, enc_out=enc_out,
        seq_valid=valid, drop_free=drop_free, overlay=overlay, plan=plan,
    )
    logits = unembed(cfg, params, h)
    return logits, new_caches
