"""Dense MLPs (SwiGLU / GeGLU / GELU) and the top-k MoE layer."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import ACTIVATIONS, PSpec


def mlp_desc(cfg, d_ff: int | None = None) -> dict:
    D, F = cfg.d_model, d_ff or cfg.d_ff
    d = {
        "wi": PSpec((D, F), ("fsdp", "d_ff")),
        "wo": PSpec((F, D), ("d_ff", "fsdp")),
    }
    if cfg.mlp in ("swiglu", "geglu"):
        d["wg"] = PSpec((D, F), ("fsdp", "d_ff"))
    return d


def mlp_apply(cfg, p, x):
    dt = x.dtype
    act = ACTIVATIONS[cfg.mlp]
    h = jnp.einsum("bsd,df->bsf", x, p["wi"].astype(dt))
    if "wg" in p:
        h = act(jnp.einsum("bsd,df->bsf", x, p["wg"].astype(dt))) * h
    else:
        h = act(h)
    return jnp.einsum("bsf,fd->bsd", h, p["wo"].astype(dt))


# ---------------------------------------------------------------------------
# Mixture of Experts (token-choice top-k, capacity-bounded, exact combine)
# ---------------------------------------------------------------------------

def moe_desc(cfg) -> dict:
    D, F, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    d = {
        "router": PSpec((D, E), ("fsdp", None), scale=D ** -0.5),
        "wi": PSpec((E, D, F), ("experts", "fsdp", None)),
        "wo": PSpec((E, F, D), ("experts", None, "fsdp")),
    }
    if cfg.mlp in ("swiglu", "geglu"):
        d["wg"] = PSpec((E, D, F), ("experts", "fsdp", None))
    return d


def moe_apply(cfg, p, x, *, rules=None, impl: str = "global"):
    """Top-k MoE. ``impl``:

    * ``global`` — paper-faithful-to-GShard pjit dispatch: one argsort over
      the *global* token stream; GSPMD inserts the (expensive) cross-device
      collectives.  The baseline in EXPERIMENTS.md §Perf.
    * ``local``  — shard_map dispatch: every device sorts only its own
      tokens into buffers for its *local* experts; the only collective is
      one (B,S,D) psum over the expert (model) axis per layer.
    """
    if impl == "local" and rules is not None and rules.mesh is not None:
        return _moe_apply_local(cfg, p, x, rules)
    return _moe_apply_global(cfg, p, x)


def _moe_apply_global(cfg, p, x):
    """Sort-based dispatch: tokens → (E, C) buffers → grouped matmul → combine.

    Exact (no approximation beyond the capacity drop at C = cf·N·k/E, the
    standard GShard-style bound).  Returns (y, aux) with the load-balance and
    router-z losses.
    """
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    N = B * S
    dt = x.dtype
    act = ACTIVATIONS[cfg.mlp]
    xf = x.reshape(N, D)

    logits = jnp.einsum("nd,de->ne", xf, p["router"].astype(dt)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)            # (N, K)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # aux losses (Switch-style load balance + router z-loss)
    me = jnp.mean(probs, axis=0)                              # (E,)
    ce = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], E, dtype=jnp.float32), axis=0)
    aux = {
        "load_balance": E * jnp.sum(me * ce),
        "router_z": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
    }

    C = max(128, int(cfg.moe_capacity_factor * N * K / E + 127) // 128 * 128)
    C = min(C, N)

    flat_e = gate_idx.reshape(-1)                             # (N·K,)
    order = jnp.argsort(flat_e)                               # stable
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=E)
    offsets = jnp.cumsum(counts) - counts                     # exclusive
    rank = jnp.arange(N * K, dtype=jnp.int32) - offsets[sorted_e].astype(jnp.int32)
    keep = rank < C
    slot = jnp.where(keep, sorted_e.astype(jnp.int32) * C + rank, E * C)

    token = (order // K).astype(jnp.int32)
    buf = jnp.zeros((E * C + 1, D), dt).at[slot].set(xf[token])
    buf = buf[: E * C].reshape(E, C, D)

    h = jnp.einsum("ecd,edf->ecf", buf, p["wi"].astype(dt))
    if "wg" in p:
        h = act(jnp.einsum("ecd,edf->ecf", buf, p["wg"].astype(dt))) * h
    else:
        h = act(h)
    out = jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(dt))   # (E, C, D)

    flat_out = jnp.concatenate([out.reshape(E * C, D), jnp.zeros((1, D), dt)], axis=0)
    gathered = flat_out[slot]                                  # (N·K, D) routed copies
    w = (gate_vals.reshape(-1)[order] * keep).astype(dt)       # dropped → 0
    y = jnp.zeros((N, D), dt).at[token].add(gathered * w[:, None])
    return y.reshape(B, S, D), aux


# ---------------------------------------------------------------------------
# shard_map local dispatch (EXPERIMENTS.md §Perf: the MoE hillclimb)
# ---------------------------------------------------------------------------

def _moe_apply_local(cfg, p, x, rules):
    """Per-device dispatch: each device routes its token shard into buffers
    for its local expert shard; partial outputs psum over the expert axis."""
    from jax.sharding import PartitionSpec as P

    mesh = rules.mesh
    sizes = rules.mesh_axis_sizes
    ep_axes = tuple(a for a in rules.rules.get("experts", ())
                    if sizes.get(a, 1) > 1 and cfg.num_experts % sizes[a] == 0)
    batch_axes = tuple(a for a in rules.rules.get("batch", ())
                       if sizes.get(a, 1) > 1)
    bsz = 1
    for a in batch_axes:
        bsz *= sizes[a]
    if x.shape[0] % max(bsz, 1):
        batch_axes = ()
    bspec = (batch_axes if len(batch_axes) > 1 else
             (batch_axes[0] if batch_axes else None))
    ep = ep_axes if len(ep_axes) != 1 else ep_axes[0]
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    act = ACTIVATIONS[cfg.mlp]
    has_gate = "wg" in p

    def body(xs, router, wi, wo, wg):
        B_loc, S, D = xs.shape
        N = B_loc * S
        dt = xs.dtype
        xf = xs.reshape(N, D)
        logits = jnp.einsum("nd,de->ne", xf, router.astype(dt)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, K)
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], E, dtype=jnp.float32), axis=0)
        if batch_axes:
            me = jax.lax.pmean(me, batch_axes)
            ce = jax.lax.pmean(ce, batch_axes)
        zl = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
        if batch_axes:
            zl = jax.lax.pmean(zl, batch_axes)
        aux = {"load_balance": E * jnp.sum(me * ce), "router_z": zl}

        E_loc = wi.shape[0]
        lo = (jax.lax.axis_index(ep) * E_loc) if ep_axes else 0
        C = max(16, int(cfg.moe_capacity_factor * N * K / E + 15) // 16 * 16)
        C = min(C, N)

        ids = gate_idx.reshape(-1) - lo                      # local coords
        ids = jnp.where((ids >= 0) & (ids < E_loc), ids, E_loc)  # E_loc = not mine
        order = jnp.argsort(ids)
        sorted_ids = ids[order]
        counts = jnp.bincount(ids, length=E_loc + 1)
        offsets = jnp.cumsum(counts) - counts
        rank = jnp.arange(N * K, dtype=jnp.int32) - offsets[sorted_ids].astype(jnp.int32)
        keep = (sorted_ids < E_loc) & (rank < C)
        slot = jnp.where(keep, sorted_ids.astype(jnp.int32) * C + rank, E_loc * C)
        token = (order // K).astype(jnp.int32)

        buf = jnp.zeros((E_loc * C + 1, D), dt).at[slot].set(xf[token])
        buf = buf[: E_loc * C].reshape(E_loc, C, D)
        h = jnp.einsum("ecd,edf->ecf", buf, wi.astype(dt))
        if has_gate:
            h = act(jnp.einsum("ecd,edf->ecf", buf, wg.astype(dt))) * h
        else:
            h = act(h)
        out = jnp.einsum("ecf,efd->ecd", h, wo.astype(dt))

        flat_out = jnp.concatenate([out.reshape(E_loc * C, D),
                                    jnp.zeros((1, D), dt)], axis=0)
        gathered = flat_out[slot]
        w = (gate_vals.reshape(-1)[order] * keep).astype(dt)
        y = jnp.zeros((N, D), dt).at[token].add(gathered * w[:, None])
        if ep_axes:
            y = jax.lax.psum(y, ep)                           # combine experts
        return y.reshape(B_loc, S, D), aux

    espec = ep if ep_axes else None
    wg = p.get("wg", p["wi"])  # dummy when ungated (ignored in body)
    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, None, None), P(None, None),
                  P(espec, None, None), P(espec, None, None), P(espec, None, None)),
        out_specs=(P(bspec, None, None), P()),
    )(x, p["router"], p["wi"], p["wo"], wg)
    return y, aux
