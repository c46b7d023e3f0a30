"""Pipeline parallelism over the ``pod`` axis (GPipe-style ring schedule).

The stacked layer periods are split across pods (stage s owns periods
[s·P/S, (s+1)·P/S)); microbatches stream through stages with
``jax.lax.ppermute`` handing activations to the next pod while ``data`` /
``model`` axes stay under GSPMD inside each stage (``shard_map`` with auto
axes).  The steady-state bubble is the classic (S−1)/(M+S−1).

This is the forward/serving pipeline (prefill scoring, eval, reward-model
passes).  Training backward uses the ZeRO-3 + TP path (`train/step.py`),
where the pod axis acts as extra data parallelism — on the assigned 2-pod
mesh that is the better-utilization choice; a 1F1B training schedule slots
into the same stage/ppermute skeleton.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.lm import block_apply


def make_pipelined_forward(model, rules, num_microbatches: int):
    """Returns fwd(params, embeds) → hidden states (B, S, D), pipelined over
    the pod axis.  `embeds` enter at stage 0; results exit at the last stage
    and are ppermuted back to stage 0 order.  Requires
    model.n_periods % n_stages == 0 and batch % (num_microbatches·data) == 0.
    """
    mesh = rules.mesh
    sizes = rules.mesh_axis_sizes
    n_stages = sizes.get("pod", 1)
    assert n_stages > 1, "pipeline needs a pod axis"
    assert model.n_periods % n_stages == 0, (model.n_periods, n_stages)
    per_stage = model.n_periods // n_stages
    cfg = model.cfg
    M = num_microbatches

    def stage_fn(blocks, h, positions):
        def apply_period(h, blk):
            for i, kind in enumerate(model.period_kinds):
                h, _ = block_apply(cfg, kind, blk[str(i)], h, positions,
                                   chunk=model.attn_chunk, rules=rules,
                                   moe_impl=model.moe_impl)
            return h, None
        h, _ = jax.lax.scan(apply_period, h, blocks)
        return h

    def body(blocks, embeds):  # inside shard_map over ("pod",); auto elsewhere
        # blocks: this pod's (per_stage, ...) slice.  embeds: (M, mb, S, D)
        stage = jax.lax.axis_index("pod")
        mb, S, D = embeds.shape[1:]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (mb, S))
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            inflight, outputs = carry
            # stage 0 injects microbatch t (if any); others use handed-off h
            inject = jnp.where(t < M, t, M - 1)
            h_in = jnp.where(stage == 0, embeds[inject], inflight)
            h_out = stage_fn(blocks, h_in.astype(embeds.dtype), positions)
            # completed microbatch index at the last stage
            done_idx = t - (n_stages - 1)
            write = (stage == n_stages - 1) & (done_idx >= 0) & (done_idx < M)
            upd = jax.lax.dynamic_update_index_in_dim(
                outputs, h_out, jnp.clip(done_idx, 0, M - 1), axis=0)
            outputs = jnp.where(write, upd, outputs)
            handed = jax.lax.ppermute(h_out, "pod", perm)
            return (handed, outputs), None

        inflight0 = jax.lax.pcast(jnp.zeros_like(embeds[0]), ("pod",),
                                  to="varying")
        outputs0 = jax.lax.pcast(jnp.zeros_like(embeds), ("pod",),
                                 to="varying")
        (_, outputs), _ = jax.lax.scan(
            tick, (inflight0, outputs0),
            jnp.arange(M + n_stages - 1, dtype=jnp.int32))
        # non-last stages never write → psum both broadcasts the last
        # stage's results and proves pod-invariance for out_specs=P().
        # (f32 round-trip: XLA:CPU's ChangeOpDataType pass crashes cloning
        # bf16 all-reduces under partial-manual shard_map.)
        return jax.lax.psum(outputs.astype(jnp.float32), "pod").astype(outputs.dtype)

    def fwd(params, embeds):
        B, S, D = embeds.shape
        assert B % M == 0, (B, M)
        embs = embeds.reshape(M, B // M, S, D)
        # partial-manual shard_map: only the pod axis is manual; data/model
        # sharding rides on the arrays themselves under GSPMD.
        out = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P("pod"), P()),
            out_specs=P(),
            axis_names=frozenset({"pod"}),
        )(params["blocks"], embs)
        return out.reshape(B, S, D)

    return fwd
