"""Mixture-of-Experts FFN with expert parallelism (EP) over the TP axis.

Design (DESIGN.md §5): activations stay replicated across the `model` axis
(as in TP transformers); each model-rank owns E/|model| experts, selects its
local experts' tokens from the (replicated) token set via a sorted
fixed-capacity dispatch, runs a per-expert matmul loop, scatters results
back, and a single psum over `model` combines expert outputs — the same
collective a dense TP FFN needs, so EP costs no extra collective class.
Expert weights are additionally FSDP-sharded over `data` and all-gathered
per layer.

The per-expert dynamic-slice loop avoids materializing the (T*k, d) gathered
token buffer (4+ GB at 32k-prefill scale); peak extra memory is
O(E_local * capacity * d).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .layers import dense_init
from .parallel import ParallelCtx, NO_PARALLEL, shard_map
from jax.sharding import PartitionSpec as P


def moe_init(key, cfg, dtype=jnp.float32):
    d, E, ff = cfg.d_model, cfg.n_experts, cfg.expert_d_ff
    ks = jax.random.split(key, 5)
    p = {"router": dense_init(ks[0], (d, E), in_axis=0, dtype=jnp.float32)}
    if cfg.activation == "swiglu":
        p["wi"] = dense_init(ks[1], (E, d, ff), in_axis=1, dtype=dtype)
        p["wg"] = dense_init(ks[2], (E, d, ff), in_axis=1, dtype=dtype)
    else:
        p["wi"] = dense_init(ks[1], (E, d, ff), in_axis=1, dtype=dtype)
    p["wo"] = dense_init(ks[3], (E, ff, d), in_axis=1, dtype=dtype)
    return p


def _expert_ffn(x, wi, wg, wo, activation):
    if activation == "swiglu":
        h = jax.nn.silu(x @ wg) * (x @ wi)
    elif activation == "squared_relu":
        h = jnp.square(jax.nn.relu(x @ wi))
    else:
        h = jax.nn.gelu(x @ wi)
    return h @ wo


def _moe_local(params, x2d, cfg, ep_axis: Optional[str], fsdp_axis: Optional[str],
               dp_axes: Tuple[str, ...] = ()):
    """Per-device MoE over local tokens (replicated across ep_axis)."""
    T, d = x2d.shape
    E, K = cfg.n_experts, cfg.top_k
    ep = jax.lax.axis_size(ep_axis) if ep_axis else 1
    E_loc = E // ep
    e_off = jax.lax.axis_index(ep_axis) * E_loc if ep_axis else 0
    cap = max(1, min(T * K, int(math.ceil(T * K / E * cfg.capacity_factor))))

    logits = (x2d @ params["router"].astype(x2d.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, K)                     # (T, K)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

    # Sorted fixed-capacity dispatch (stable: earlier tokens win capacity,
    # mirroring the paper-era switch routing priority).
    flat_e = ids.reshape(-1)                                 # (T*K,)
    flat_t = jnp.arange(T * K, dtype=jnp.int32) // K
    flat_g = gates.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    seg_start = jnp.searchsorted(se, se, side="left")
    pos = jnp.arange(T * K, dtype=jnp.int32) - seg_start.astype(jnp.int32)

    wi, wo = params["wi"], params["wo"]
    wg = params.get("wg")
    if ep_axis:  # shard_map gave us the local expert slab
        pass
    if fsdp_axis:  # FSDP: gather the d (or ff) dimension shards per layer
        wi = jax.lax.all_gather(wi, fsdp_axis, axis=1, tiled=True)
        wo = jax.lax.all_gather(wo, fsdp_axis, axis=1, tiled=True)
        if wg is not None:
            wg = jax.lax.all_gather(wg, fsdp_axis, axis=1, tiled=True)

    out = jnp.zeros((T, d), jnp.float32)
    for le in range(E_loc):
        e = le + e_off
        start = jnp.searchsorted(se, e, side="left").astype(jnp.int32)
        tok = jax.lax.dynamic_slice_in_dim(st, start, cap)
        eid = jax.lax.dynamic_slice_in_dim(se, start, cap)
        g = jax.lax.dynamic_slice_in_dim(sg, start, cap)
        within = jax.lax.dynamic_slice_in_dim(pos, start, cap)
        keep = (eid == e) & (within < cap)
        g = jnp.where(keep, g, 0.0)
        xe = x2d[tok] * keep[:, None].astype(x2d.dtype)      # (cap, d)
        ye = _expert_ffn(
            xe.astype(x2d.dtype),
            wi[le].astype(x2d.dtype),
            None if wg is None else wg[le].astype(x2d.dtype),
            wo[le].astype(x2d.dtype),
            cfg.activation,
        )
        out = out.at[tok].add(ye.astype(jnp.float32) * g[:, None])

    if ep_axis:
        out = jax.lax.psum(out, ep_axis)

    # Which experts received tokens (Vilamb dirty tracking) + balance loss.
    counts = jnp.zeros((E,), jnp.int32).at[flat_e].add(1, mode="drop")
    me = jnp.mean(probs, axis=0)
    ce = counts.astype(jnp.float32) / max(T * K, 1)
    aux_loss = E * jnp.sum(me * ce)
    # Reduce stats to a value identical on every device: tokens are
    # replicated over ep_axis (divide the ep-fold back out) and partitioned
    # over dp_axes (sum).
    stat_axes = tuple(dp_axes) + ((ep_axis,) if ep_axis else ())
    if stat_axes:
        counts = jax.lax.psum(counts, stat_axes) // ep
        aux_loss = jax.lax.psum(aux_loss, stat_axes) / ep
        ndp = jax.lax.psum(1, tuple(dp_axes)) if dp_axes else 1
        aux_loss = aux_loss / ndp
    return out.astype(x2d.dtype), counts, aux_loss


def moe_apply(
    params, x2d: jax.Array, cfg, ctx: ParallelCtx = NO_PARALLEL
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """MoE FFN over flat tokens (T, d) -> (out, expert_counts, aux_loss)."""
    if ctx.mesh is None or ctx.tp_axis is None or cfg.n_experts % max(ctx.axis_size(ctx.tp_axis), 1):
        out, counts, aux = _moe_local(params, x2d, cfg, None, None)
        return out, counts, aux

    tp, fsdp = ctx.tp_axis, ctx.fsdp_axis
    dp = ctx.batch_spec
    dp_axes = ctx.dp_axes
    if dp is not None:
        import numpy as _np
        k = int(_np.prod([ctx.axis_size(a) for a in ctx.dp_axes]))
        if x2d.shape[0] % max(k, 1):
            dp, dp_axes = None, ()   # tiny decode batches: replicate tokens
    wspec_i = P(tp, fsdp, None)
    wspec_o = P(tp, fsdp, None)
    in_specs = (
        {
            "router": P(None, None),
            **({"wg": wspec_i} if "wg" in params else {}),
            "wi": wspec_i,
            "wo": wspec_o,
        },
        P(dp, None),
    )

    def body(p, x):
        return _moe_local(p, x, cfg, tp, fsdp, dp_axes=dp_axes)

    fn = shard_map(
        body, mesh=ctx.mesh, in_specs=in_specs,
        out_specs=(P(dp, None), P(None), P()),
        check_vma=False,
    )
    return fn(params, x2d)
