"""Mixture-of-Experts layer: top-k router, sort-based capacity dispatch, EP.

Dispatch algorithm (static shapes, GSPMD-friendly):
  1. router logits -> top-k (expert_id, weight) per token
  2. sort the (T*k) assignments by expert id; position-in-segment gives each
     assignment its capacity slot; slots >= capacity are DROPPED (standard
     dropped-token MoE with capacity_factor)
  3. scatter tokens into an (E, C, D) buffer; a sharding constraint places
     E on the "expert" (model) mesh axis — GSPMD materializes the all-to-all
  4. per-expert FFN via einsum over the stacked expert weights (MXU batch)
  5. gather back + combine with router weights; add shared experts
     (DeepSeek-style always-on experts) computed as a dense gated MLP.

Aux losses: Switch-style load-balancing loss and router z-loss, both returned
for the trainer to weigh in.

The structural kinship with the paper is intentional and documented
(DESIGN.md §5): route-to-local-expert is the same compute shape as DC-SVM's
early prediction (route-to-cluster, score with the local model) — with the
difference that the SVM serving path never drops an overflow query (extra
on-device rounds instead of capacity drops).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.models.param import ParamDecl
from repro.models.sharding import MeshCtx, maybe_constrain

Array = jax.Array


def moe_decls(cfg, L: int) -> Dict[str, ParamDecl]:
    m = cfg.moe
    D = cfg.d_model
    F = m.d_expert or cfg.d_ff
    E = m.num_experts
    d = {
        # router is tiny: replicate so shard_map bodies use it locally
        "router": ParamDecl((L, D, E), ("layers", None, None),
                            init="normal", scale=0.02),
        "w1": ParamDecl((L, E, D, F), ("layers", "expert", "embed", None)),
        "w3": ParamDecl((L, E, D, F), ("layers", "expert", "embed", None)),
        "w2": ParamDecl((L, E, F, D), ("layers", "expert", None, "embed")),
    }
    if m.num_shared > 0:
        Fs = F * m.num_shared
        d["sh_w1"] = ParamDecl((L, D, Fs), ("layers", "embed", "mlp"))
        d["sh_w3"] = ParamDecl((L, D, Fs), ("layers", "embed", "mlp"))
        d["sh_w2"] = ParamDecl((L, Fs, D), ("layers", "mlp", "embed"))
    return d


def capacity(cfg, tokens: int) -> int:
    m = cfg.moe
    c = int(np.ceil(tokens * m.top_k * m.capacity_factor / m.num_experts))
    return max(8, -(-c // 8) * 8)    # pad to a multiple of 8 for TPU layout


def moe_apply(
    p: Dict[str, Array], x: Array, cfg, ctx: Optional[MeshCtx] = None,
) -> Tuple[Array, Dict[str, Array]]:
    """x: (B, S, D) -> (out, aux losses).

    With a mesh context the dispatch runs MANUALLY under shard_map
    (§Perf H5): tokens never leave their data shard except through the
    explicit (E, C_loc, D) all-to-all over the model axis.  Left to GSPMD,
    the global sort/scatter dispatch triggers involuntary full
    rematerialization — measured at 3.75 GiB of all-gather per MoE layer on
    deepseek-moe (see EXPERIMENTS.md §Perf)."""
    if ctx is not None and "model" in ctx.mesh.axis_names:
        return _moe_apply_sharded(p, x, cfg, ctx)
    return _moe_apply_dense(p, x, cfg, ctx)


def _moe_apply_dense(
    p: Dict[str, Array], x: Array, cfg, ctx: Optional[MeshCtx] = None,
) -> Tuple[Array, Dict[str, Array]]:
    """Reference dispatch (single-device path; the shard_map path is tested
    for equivalence against this)."""
    m = cfg.moe
    B, S, D = x.shape
    T = B * S
    E, K = m.num_experts, m.top_k
    C = capacity(cfg, T)
    xt = x.reshape(T, D)

    # ---- router --------------------------------------------------------
    logits = jnp.einsum("td,de->te", xt, p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, K)                     # (T, K)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # aux: Switch load-balance + z-loss
    density = jnp.mean(jax.nn.one_hot(top_e[:, 0], E, dtype=jnp.float32), axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux_lb = E * jnp.sum(density * mean_prob)
    aux_z = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)

    # ---- sort-based capacity dispatch -----------------------------------
    flat_e = top_e.reshape(-1)                                 # (T*K,)
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)
    flat_w = top_w.reshape(-1)
    order = jnp.argsort(flat_e)                                # stable
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    seg_start = jnp.searchsorted(se, jnp.arange(E), side="left")
    pos = jnp.arange(T * K, dtype=jnp.int32) - seg_start[se].astype(jnp.int32)
    keep = pos < C
    pos_safe = jnp.where(keep, pos, 0)
    se_safe = jnp.where(keep, se, 0)

    buf = jnp.zeros((E, C, D), x.dtype)
    vals = jnp.where(keep[:, None], xt[st], 0.0)
    buf = buf.at[se_safe, pos_safe].add(vals)
    buf = maybe_constrain(ctx, buf, "expert", None, None)      # all-to-all here

    # ---- expert FFN (batched einsum over E) ------------------------------
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w1"]))
    h = h * jnp.einsum("ecd,edf->ecf", buf, p["w3"])
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["w2"])
    out_buf = maybe_constrain(ctx, out_buf, "expert", None, None)

    # ---- combine ---------------------------------------------------------
    gathered = out_buf[se_safe, pos_safe] * (sw * keep)[:, None]
    out = jnp.zeros((T, D), x.dtype).at[st].add(gathered)

    # ---- shared experts (dense, always-on) -------------------------------
    if m.num_shared > 0:
        hs = jax.nn.silu(jnp.einsum("td,df->tf", xt, p["sh_w1"]))
        hs = hs * jnp.einsum("td,df->tf", xt, p["sh_w3"])
        out = out + jnp.einsum("tf,fd->td", hs, p["sh_w2"])

    aux = {"moe_lb": aux_lb, "moe_z": aux_z,
           "moe_drop_frac": 1.0 - jnp.mean(keep.astype(jnp.float32))}
    return out.reshape(B, S, D), aux


# ---------------------------------------------------------------------------
# shard_map dispatch (manual all-to-all, §Perf H5)
# ---------------------------------------------------------------------------

def _dispatch_local(xt: Array, router: Array, m, C: int):
    """Local routing + capacity dispatch for one shard's tokens.
    Returns (buf (E, C, D), combine info, aux scalars)."""
    T, D = xt.shape
    E, K = m.num_experts, m.top_k
    logits = jnp.einsum("td,de->te", xt, router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, K)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    density = jnp.mean(jax.nn.one_hot(top_e[:, 0], E, dtype=jnp.float32), axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux_lb = E * jnp.sum(density * mean_prob)
    aux_z = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)

    flat_e = top_e.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)
    flat_w = top_w.reshape(-1)
    order = jnp.argsort(flat_e)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    seg_start = jnp.searchsorted(se, jnp.arange(E), side="left")
    pos = jnp.arange(T * K, dtype=jnp.int32) - seg_start[se].astype(jnp.int32)
    keep = pos < C
    pos_safe = jnp.where(keep, pos, 0)
    se_safe = jnp.where(keep, se, 0)
    buf = jnp.zeros((E, C, D), xt.dtype)
    buf = buf.at[se_safe, pos_safe].add(jnp.where(keep[:, None], xt[st], 0.0))
    info = (se_safe, pos_safe, st, sw, keep)
    aux = (aux_lb, aux_z, 1.0 - jnp.mean(keep.astype(jnp.float32)))
    return buf, info, aux


def _combine_local(out_buf: Array, info, T: int, D: int) -> Array:
    se_safe, pos_safe, st, sw, keep = info
    vals = out_buf[se_safe, pos_safe] * (sw * keep)[:, None]
    return jnp.zeros((T, D), out_buf.dtype).at[st].add(vals)


def _moe_apply_sharded(
    p: Dict[str, Array], x: Array, cfg, ctx: MeshCtx,
) -> Tuple[Array, Dict[str, Array]]:
    m = cfg.moe
    mesh = ctx.mesh
    B, S, D = x.shape
    E = m.num_experts
    F = m.d_expert or cfg.d_ff
    data_axes = tuple(a for a in mesh.axis_names if a != "model")
    n_data = int(np.prod([mesh.shape[a] for a in data_axes]))
    n_model = mesh.shape["model"]
    e_loc = max(E // n_model, 1)
    model_sharded = E % n_model == 0 and E >= n_model
    B_loc = B // n_data if B % n_data == 0 else B
    T_loc = B_loc * S
    C = capacity(cfg, T_loc)

    from jax.sharding import PartitionSpec as P

    batch_spec = data_axes if (B % n_data == 0 and data_axes) else None
    x_spec = P(batch_spec, None, None)
    r_spec = P(None, None)
    # expert weights: (E->model, D->data, F) — re-gathered over data in-body
    d_ax = "data" if "data" in mesh.axis_names else None
    d_sharded = d_ax is not None and D % mesh.shape["data"] == 0
    e_spec = "model" if model_sharded else None
    w13_spec = P(e_spec, "data" if d_sharded else None, None)
    w2_spec = P(e_spec, None, "data" if d_sharded else None)

    def body(xl, router, w1l, w3l, w2l):
        Bl = xl.shape[0]
        xt = xl.reshape(Bl * S, D)
        buf, info, aux = _dispatch_local(xt, router, m, C)     # (E, C, D)

        # gather expert weights over the data axis (FSDP-style, per layer)
        if d_sharded:
            w1g = jax.lax.all_gather(w1l, d_ax, axis=1, tiled=True)
            w3g = jax.lax.all_gather(w3l, d_ax, axis=1, tiled=True)
            w2g = jax.lax.all_gather(w2l, d_ax, axis=2, tiled=True)
        else:
            w1g, w3g, w2g = w1l, w3l, w2l

        if model_sharded and n_model > 1:
            # all-to-all over the model axis: peer j receives the j-th e_loc
            # expert block from every peer; regroup source-major -> expert-major
            bufx = jax.lax.all_to_all(buf, "model", split_axis=0,
                                      concat_axis=0, tiled=True)
            bufe = bufx.reshape(n_model, e_loc, C, D).transpose(1, 0, 2, 3)
            bufe = bufe.reshape(e_loc, n_model * C, D)
        else:
            bufe = buf
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", bufe, w1g))
        h = h * jnp.einsum("ecd,edf->ecf", bufe, w3g)
        oute = jnp.einsum("ecf,efd->ecd", h, w2g)
        if model_sharded and n_model > 1:
            outx = oute.reshape(e_loc, n_model, C, D).transpose(1, 0, 2, 3)
            outx = outx.reshape(E, C, D)
            out_buf = jax.lax.all_to_all(outx, "model", split_axis=0,
                                         concat_axis=0, tiled=True)
        else:
            out_buf = oute
        out = _combine_local(out_buf, info, Bl * S, D).reshape(Bl, S, D)

        axes_all = tuple(mesh.axis_names)
        aux_lb = jax.lax.pmean(aux[0], axes_all)
        aux_z = jax.lax.pmean(aux[1], axes_all)
        aux_dr = jax.lax.pmean(aux[2], axes_all)
        return out, aux_lb[None], aux_z[None], aux_dr[None]

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, r_spec, w13_spec, w13_spec, w2_spec),
        out_specs=(x_spec, P(None), P(None), P(None)),
        check_vma=False,
    )
    out, lb, z, dr = fn(x, p["router"], p["w1"], p["w3"], p["w2"])
    aux = {"moe_lb": lb[0], "moe_z": z[0], "moe_drop_frac": dr[0]}

    # shared experts: dense Megatron MLP under GSPMD (one AR per direction)
    if m.num_shared > 0:
        hs = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, p["sh_w1"]))
        hs = hs * jnp.einsum("bsd,df->bsf", x, p["sh_w3"])
        out = out + jnp.einsum("bsf,fd->bsd", hs, p["sh_w2"])
    return out, aux
