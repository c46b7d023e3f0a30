"""Decoder-only LM assembled from heterogeneous blocks.

Layers follow ``cfg.layer_pattern`` repeated over depth.  One *superblock* =
one pattern period; full periods are stacked and applied with
``jax.lax.scan`` (small HLO, fast 512-device compiles), remainder layers run
unrolled as the "tail".  The same structure drives init (smoke tests),
``jax.eval_shape`` param shapes (dry-run), PartitionSpecs (via logical axis
names), training forward, prefill, and one-token decode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import attention, mlp, rglru, ssm
from .common import PSpec, init_tree, rms_norm, shape_tree, spec_tree, stack

COMPUTE_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


# ---------------------------------------------------------------------------
# Per-layer blocks
# ---------------------------------------------------------------------------

def block_desc(cfg, kind: str) -> dict:
    D = cfg.d_model
    ln = lambda: PSpec((D,), (None,), init="zeros")
    if kind in ("attn", "local"):
        d = {"ln1": ln(), "attn": attention.attn_desc(cfg), "ln2": ln()}
        if cfg.num_experts:
            d["moe"] = mlp.moe_desc(cfg)
        else:
            d["mlp"] = mlp.mlp_desc(cfg)
        return d
    if kind == "ssm":
        return {"ln1": ln(), "ssm": ssm.ssm_desc(cfg)}
    if kind == "rglru":
        return {"ln1": ln(), "rglru": rglru.rglru_desc(cfg), "ln2": ln(),
                "mlp": mlp.mlp_desc(cfg)}
    raise ValueError(f"unknown block kind {kind!r}")


def _zero_aux():
    return {"load_balance": jnp.float32(0.0), "router_z": jnp.float32(0.0)}


def block_apply(cfg, kind, p, x, positions, *, chunk=None, rules=None,
                moe_impl="global"):
    from jax.ad_checkpoint import checkpoint_name
    aux = _zero_aux()
    window = cfg.sliding_window if kind == "local" else None
    if kind in ("attn", "local"):
        h = attention.attn_apply(cfg, p["attn"], rms_norm(x, p["ln1"]), positions,
                                 window=window, chunk=chunk, rules=rules)
        x = x + checkpoint_name(h, "attn_out")
        hin = rms_norm(x, p["ln2"])
        if cfg.num_experts:
            h, aux = mlp.moe_apply(cfg, p["moe"], hin, rules=rules, impl=moe_impl)
        else:
            h = mlp.mlp_apply(cfg, p["mlp"], hin)
        return x + checkpoint_name(h, "mlp_out"), aux
    if kind == "ssm":
        h = ssm.ssm_apply(cfg, p["ssm"], rms_norm(x, p["ln1"]))
        return x + checkpoint_name(h, "ssm_out"), aux
    if kind == "rglru":
        x = x + checkpoint_name(
            rglru.rglru_apply(cfg, p["rglru"], rms_norm(x, p["ln1"])), "rnn_out")
        x = x + checkpoint_name(
            mlp.mlp_apply(cfg, p["mlp"], rms_norm(x, p["ln2"])), "mlp_out")
        return x, aux
    raise ValueError(kind)


def block_cache_desc(cfg, kind, batch: int, max_len: int,
                     cache_dtype: str = "bfloat16") -> dict:
    if kind == "attn":
        return attention.cache_desc(cfg, batch, max_len, cache_dtype=cache_dtype)
    if kind == "local":
        return attention.cache_desc(cfg, batch, max_len, window=cfg.sliding_window,
                                    cache_dtype=cache_dtype)
    if kind == "ssm":
        return ssm.ssm_cache_desc(cfg, batch)
    if kind == "rglru":
        return rglru.rglru_cache_desc(cfg, batch)
    raise ValueError(kind)


def block_decode(cfg, kind, p, cache, x, pos, *, rules=None):
    window = cfg.sliding_window if kind == "local" else None
    if kind in ("attn", "local"):
        c, h = attention.attn_decode(cfg, p["attn"], cache, rms_norm(x, p["ln1"]),
                                     pos, window=window, rules=rules)
        x = x + h
        hin = rms_norm(x, p["ln2"])
        if cfg.num_experts:
            h, _ = mlp.moe_apply(cfg, p["moe"], hin)
        else:
            h = mlp.mlp_apply(cfg, p["mlp"], hin)
        return c, x + h
    if kind == "ssm":
        c, h = ssm.ssm_decode(cfg, p["ssm"], cache, rms_norm(x, p["ln1"]), pos)
        return c, x + h
    if kind == "rglru":
        c, h = rglru.rglru_decode(cfg, p["rglru"], cache, rms_norm(x, p["ln1"]), pos)
        x = x + h
        x = x + mlp.mlp_apply(cfg, p["mlp"], rms_norm(x, p["ln2"]))
        return c, x
    raise ValueError(kind)


def block_prefill(cfg, kind, p, x, positions, max_len, *, chunk=None, rules=None,
                  cache_dtype: str = "bfloat16"):
    window = cfg.sliding_window if kind == "local" else None
    if kind in ("attn", "local"):
        c, h = attention.attn_prefill(cfg, p["attn"], rms_norm(x, p["ln1"]), positions,
                                      max_len, window=window, chunk=chunk, rules=rules,
                                      cache_dtype=cache_dtype)
        x = x + h
        hin = rms_norm(x, p["ln2"])
        if cfg.num_experts:
            h, _ = mlp.moe_apply(cfg, p["moe"], hin)
        else:
            h = mlp.mlp_apply(cfg, p["mlp"], hin)
        return c, x + h
    if kind == "ssm":
        c, h = ssm.ssm_apply(cfg, p["ssm"], rms_norm(x, p["ln1"]), return_cache=True)
        return c, x + h
    if kind == "rglru":
        c, h = rglru.rglru_apply(cfg, p["rglru"], rms_norm(x, p["ln1"]), return_cache=True)
        x = x + h
        x = x + mlp.mlp_apply(cfg, p["mlp"], rms_norm(x, p["ln2"]))
        return c, x
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Full model
# ---------------------------------------------------------------------------

class LM:
    def __init__(self, cfg, *, attn_chunk: int | None = None, remat: str = "full",
                 rules=None, moe_impl: str = "global",
                 cache_dtype: str = "bfloat16"):
        self.cfg = cfg
        self.attn_chunk = attn_chunk
        self.remat = remat
        self.rules = rules
        self.moe_impl = moe_impl
        self.cache_dtype = cache_dtype
        self.period_kinds = cfg.layer_pattern
        self.n_periods = cfg.full_periods
        self.tail_kinds = cfg.tail_layers

    # ---- parameter descriptors ------------------------------------------
    def desc(self) -> dict:
        cfg = self.cfg
        sb = {str(i): block_desc(cfg, k) for i, k in enumerate(self.period_kinds)}
        d = {
            # untied: the input table is vocab-sharded and gathered via a
            # Megatron-style shard_map (each shard takes its own vocab range,
            # psum over the TP axis); the unembed is vocab-sharded so the
            # logits matmul partitions as a plain contraction.  A naive
            # jnp.take on a sharded table makes GSPMD replicate the whole
            # table per microbatch ("involuntary full rematerialization").
            "embed": PSpec((cfg.padded_vocab, cfg.d_model), ("vocab", None),
                           scale=1.0),
            "unembed": PSpec((cfg.padded_vocab, cfg.d_model), ("vocab", "fsdp"),
                             scale=cfg.d_model ** -0.5),
            "final_norm": PSpec((cfg.d_model,), (None,), init="zeros"),
        }
        if self.n_periods:
            d["blocks"] = stack(sb, self.n_periods)
        if self.tail_kinds:
            d["tail"] = {str(i): block_desc(cfg, k)
                         for i, k in enumerate(self.tail_kinds)}
        return d

    def init(self, key):
        return init_tree(self.desc(), key, COMPUTE_DTYPES[self.cfg.param_dtype])

    def param_shapes(self):
        return shape_tree(self.desc(), COMPUTE_DTYPES[self.cfg.param_dtype])

    def param_specs(self, rules):
        return spec_tree(self.desc(), rules)

    def param_count(self) -> int:
        import numpy as np
        return int(sum(np.prod(s.shape) for s in jax.tree.leaves(self.param_shapes())))

    # ---- embedding ---------------------------------------------------------
    def _embed(self, params, tokens):
        """Token embedding lookup, vocab-parallel when rules carry a mesh."""
        cdt = COMPUTE_DTYPES[self.cfg.dtype]
        table = params["embed"].astype(cdt)
        rules = self.rules
        if rules is None or rules.mesh is None:
            return table[tokens]
        vocab_axes = tuple(a for a in rules.rules.get("vocab", ())
                           if rules.mesh_axis_sizes.get(a, 1) > 1)
        if not vocab_axes or table.shape[0] % rules.mesh_axis_sizes[vocab_axes[0]]:
            return table[tokens]
        assert len(vocab_axes) == 1, vocab_axes
        (vax,) = vocab_axes
        batch_axes = rules.rules.get("batch", ())
        from jax.sharding import PartitionSpec as P

        bsize = 1
        for a in batch_axes:
            bsize *= rules.mesh_axis_sizes.get(a, 1)
        if batch_axes and tokens.shape[0] % max(bsize, 1) != 0:
            batch_axes = ()  # tiny batch (e.g. long-context B=1): replicate
        bspec = (batch_axes if len(batch_axes) > 1 else
                 (batch_axes[0] if batch_axes else None))

        def body(tab, tok):  # tab (V/tp, D) local shard, tok (B/dp, S)
            vshard = tab.shape[0]
            start = jax.lax.axis_index(vax) * vshard
            loc = tok - start
            ok = (loc >= 0) & (loc < vshard)
            rows = jnp.take(tab, jnp.clip(loc, 0, vshard - 1), axis=0)
            rows = jnp.where(ok[..., None], rows, jnp.zeros((), tab.dtype))
            return jax.lax.psum(rows, vax)

        return jax.shard_map(
            body, mesh=rules.mesh,
            in_specs=(P(vax, None), P(bspec, None)),
            out_specs=P(bspec, None, None))(table, tokens)

    # ---- forward ----------------------------------------------------------
    def _superblock(self, params, x, positions):
        aux = _zero_aux()
        for i, kind in enumerate(self.period_kinds):
            x, a = block_apply(self.cfg, kind, params[str(i)], x, positions,
                               chunk=self.attn_chunk, rules=self.rules,
                               moe_impl=self.moe_impl)
            aux = jax.tree.map(jnp.add, aux, a)
        return x, aux

    def forward(self, params, tokens=None, embeds=None, positions=None):
        """→ (logits f32 (B,S,Vp), aux). Feed `embeds` for vlm/audio stubs."""
        cfg = self.cfg
        cdt = COMPUTE_DTYPES[cfg.dtype]
        if embeds is None:
            h = self._embed(params, tokens)
        else:
            h = embeds.astype(cdt)
        B, S = h.shape[:2]
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

        aux = _zero_aux()
        if self.n_periods:
            body = self._superblock
            if self.remat == "full":
                body = jax.checkpoint(
                    body, policy=jax.checkpoint_policies.nothing_saveable)
            elif self.remat == "names":
                body = jax.checkpoint(
                    body, policy=jax.checkpoint_policies.save_only_these_names(
                        "attn_out", "mlp_out", "ssm_out", "rnn_out", "kv_flat"))

            def scan_fn(carry, blk):
                h, aux = carry
                h, a = body(blk, h, positions)
                return (h, jax.tree.map(jnp.add, aux, a)), None

            (h, aux), _ = jax.lax.scan(scan_fn, (h, aux), params["blocks"])
        for i, kind in enumerate(self.tail_kinds):
            h, a = block_apply(cfg, kind, params["tail"][str(i)], h, positions,
                               chunk=self.attn_chunk, rules=self.rules,
                               moe_impl=self.moe_impl)
            aux = jax.tree.map(jnp.add, aux, a)

        h = rms_norm(h, params["final_norm"])
        logits = jnp.einsum("bsd,vd->bsv", h, params["unembed"].astype(cdt),
                            preferred_element_type=jnp.float32)
        return logits, aux

    def loss(self, params, batch):
        """Cross-entropy (+ MoE aux). batch: tokens|embeds, labels (B,S)."""
        logits, aux = self.forward(
            params, tokens=batch.get("tokens"), embeds=batch.get("embeds"))
        labels = batch["labels"]
        mask = (labels >= 0).astype(jnp.float32)
        safe = jnp.maximum(labels, 0)
        # CE via fused one-hot (a take_along_axis over the model-sharded vocab
        # dim would trigger an SPMD gather; iota-compare-reduce partitions
        # cleanly and XLA fuses it without materializing the one-hot).
        lse = jax.nn.logsumexp(logits, axis=-1)
        vocab_iota = jnp.arange(logits.shape[-1], dtype=labels.dtype)
        onehot = (safe[..., None] == vocab_iota).astype(logits.dtype)
        true_logit = jnp.sum(logits * onehot, axis=-1)
        nll = lse - true_logit
        ce = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        total = ce + 0.01 * aux["load_balance"] + 0.001 * aux["router_z"]
        return total, {"ce": ce, **aux}

    # ---- serving ----------------------------------------------------------
    def cache_desc(self, batch: int, max_len: int) -> dict:
        cfg = self.cfg
        sb = {str(i): block_cache_desc(cfg, k, batch, max_len, self.cache_dtype)
              for i, k in enumerate(self.period_kinds)}
        d = {}
        if self.n_periods:
            d["blocks"] = stack(sb, self.n_periods)
        if self.tail_kinds:
            d["tail"] = {str(i): block_cache_desc(cfg, k, batch, max_len,
                                                  self.cache_dtype)
                         for i, k in enumerate(self.tail_kinds)}
        return d

    def init_cache(self, batch: int, max_len: int):
        return init_tree(self.cache_desc(batch, max_len), jax.random.PRNGKey(0),
                         COMPUTE_DTYPES[self.cfg.dtype])

    def cache_shapes(self, batch: int, max_len: int):
        return shape_tree(self.cache_desc(batch, max_len),
                          COMPUTE_DTYPES[self.cfg.dtype])

    def cache_specs(self, batch: int, max_len: int, rules):
        return spec_tree(self.cache_desc(batch, max_len), rules)

    def decode_step(self, params, cache, tokens, pos):
        """One token for every sequence. tokens (B,1) int32, pos scalar."""
        cfg = self.cfg
        cdt = COMPUTE_DTYPES[cfg.dtype]
        h = self._embed(params, tokens)

        if self.n_periods:
            def scan_fn(h, inp):
                blk_p, blk_c = inp
                new_c = {}
                for i, kind in enumerate(self.period_kinds):
                    new_c[str(i)], h = block_decode(cfg, kind, blk_p[str(i)],
                                                    blk_c[str(i)], h, pos,
                                                    rules=self.rules)
                return h, new_c

            h, new_blocks = jax.lax.scan(scan_fn, h, (params["blocks"], cache["blocks"]))
            new_cache = dict(cache)
            new_cache["blocks"] = new_blocks
        else:
            new_cache = dict(cache)
        if self.tail_kinds:
            tail = {}
            for i, kind in enumerate(self.tail_kinds):
                tail[str(i)], h = block_decode(cfg, kind, params["tail"][str(i)],
                                               cache["tail"][str(i)], h, pos,
                                               rules=self.rules)
            new_cache["tail"] = tail

        h = rms_norm(h, params["final_norm"])
        logits = jnp.einsum("bsd,vd->bsv", h, params["unembed"].astype(cdt),
                            preferred_element_type=jnp.float32)
        return new_cache, logits

    def prefill(self, params, tokens=None, embeds=None, max_len: int | None = None):
        """Full-sequence prefill → (cache, last-token logits)."""
        cfg = self.cfg
        cdt = COMPUTE_DTYPES[cfg.dtype]
        h = self._embed(params, tokens) if embeds is None else embeds.astype(cdt)
        B, S = h.shape[:2]
        max_len = max_len or S
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

        new_cache = {}
        if self.n_periods:
            def scan_fn(h, blk_p):
                cs = {}
                for i, kind in enumerate(self.period_kinds):
                    cs[str(i)], h = block_prefill(cfg, kind, blk_p[str(i)], h,
                                                  positions, max_len,
                                                  chunk=self.attn_chunk,
                                                  rules=self.rules,
                                                  cache_dtype=self.cache_dtype)
                return h, cs

            h, new_cache["blocks"] = jax.lax.scan(scan_fn, h, params["blocks"])
        if self.tail_kinds:
            tail = {}
            for i, kind in enumerate(self.tail_kinds):
                tail[str(i)], h = block_prefill(cfg, kind, params["tail"][str(i)], h,
                                                positions, max_len, chunk=self.attn_chunk,
                                                rules=self.rules,
                                                cache_dtype=self.cache_dtype)
            new_cache["tail"] = tail

        h = rms_norm(h[:, -1:], params["final_norm"])
        logits = jnp.einsum("bsd,vd->bsv", h, params["unembed"].astype(cdt),
                            preferred_element_type=jnp.float32)
        return new_cache, logits
