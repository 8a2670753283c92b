"""Decoder-only LM assembly: block patterns, scan-over-periods, caches.

Supports every assigned architecture family:
- dense GQA transformers (llama3.2, qwen3, minitron, nemotron, llava backbone)
- MoE transformers (qwen3-moe, granite-moe)
- hybrid RG-LRU + local-attention (recurrentgemma, pattern ("rec","rec","attn_local"))
- attention-free RWKV6 (pattern ("rwkv",))
- hybrid Mamba-2 + NoPE attention (granite-4.0-h, pattern mamba×5, attn, mamba×4)

Layers are stacked per pattern-position and scanned over periods (MaxText
style) so the HLO stays compact at 96 layers; remainder layers (depth not a
multiple of the pattern period) are applied unscanned.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from . import layers as L
from . import attention as A
from . import moe as M
from . import recurrent as R
from ..core.packing import RowBalancedSparse, pad_packed
from ..core.sparsity import keep_count
from ..kernels.ops import serve_dense
from ..sharding import constrain
from ..configs.base import ArchConfig

# block kinds whose padded prompt positions leave every valid row's state
# exactly as an unpadded prefill would (``prefill(..., length=)``)
_MASKS_PADDING = ("attn", "attn_local", "mamba")
# block kinds whose every prunable projection decodes packed
_PACKABLE = ("attn", "attn_local", "mamba")


class TransformerLM:
    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        P = len(cfg.block_pattern)
        self.n_periods = cfg.num_layers // P
        self.rem_kinds = tuple(cfg.block_pattern[: cfg.num_layers % P])
        self.vocab_padded = L.pad_vocab(cfg.vocab_size)
        kinds = set(cfg.block_pattern)
        plain = not (cfg.moe or cfg.num_patches)
        # a model whose every block masks padding takes ``length``: the
        # scheduler then pads prompts to buckets; any other keeps the
        # exact-length prefill
        self.masks_padding = plain and kinds <= set(_MASKS_PADDING)
        if self.masks_padding:
            self.prefill = self._prefill_length
        # every prunable leaf can pack: ServeEngine.prepare packs all but
        # those ``dense_served`` picks; a projection reads a packed leaf
        # through kernels.ops.rb_project and a dense one through a dot
        self.supports_packed_decode = plain and kinds <= set(_PACKABLE)

    # ------------------------------------------------------------- defs
    def _block_defs(self, kind: str) -> dict:
        cfg = self.cfg
        dt = cfg.jdtype
        d = {"norm1": L.norm_defs(cfg.norm, cfg.d_model)}
        if kind in ("attn", "attn_local"):
            h_eff = cfg.pad_heads_to or cfg.num_heads
            d["attn"] = A.attn_defs(cfg.d_model, h_eff,
                                    cfg.num_kv_heads, cfg.head_dim,
                                    cfg.qk_norm, dt)
        elif kind == "rec":
            d["rec"] = R.rglru_defs(cfg.d_model, cfg.rnn_width,
                                    cfg.conv_width, dt)
        elif kind == "rwkv":
            d["rwkv"] = R.rwkv_defs(cfg.d_model, cfg.num_heads, cfg.head_dim,
                                    cfg.d_ff, dt)
        elif kind == "mamba":
            d["mamba"] = R.mamba2_defs(cfg.d_model, cfg.mamba_heads,
                                       cfg.mamba_head_dim, cfg.mamba_d_state,
                                       cfg.mamba_groups, cfg.conv_width, dt)
        else:
            raise ValueError(kind)
        d["norm2"] = L.norm_defs(cfg.norm, cfg.d_model)
        if kind != "rwkv":  # rwkv carries its own channel-mix FFN
            if cfg.moe:
                d["moe"] = M.moe_defs(cfg.d_model, cfg.d_ff, cfg.num_experts,
                                      cfg.activation, dt)
            else:
                d["mlp"] = L.mlp_defs(cfg.d_model, cfg.d_ff, cfg.activation, dt)
        return d

    def param_defs(self) -> dict:
        cfg = self.cfg
        dt = cfg.jdtype
        defs: dict[str, Any] = {
            "embed": L.embed_defs(self.vocab_padded, cfg.d_model, dt),
            "final_norm": L.norm_defs(cfg.norm, cfg.d_model),
        }
        if not cfg.tie_embeddings:
            defs["head"] = {"w": L.PSpec((cfg.d_model, self.vocab_padded),
                                         ("embed", "vocab"), dtype=dt)}
        if cfg.num_patches:
            defs["patch_norm"] = L.norm_defs("rmsnorm", cfg.d_model)
        if self.n_periods:
            defs["blocks"] = tuple(
                L.stack_defs(self._block_defs(k), self.n_periods)
                for k in cfg.block_pattern)
        for i, k in enumerate(self.rem_kinds):
            defs[f"rem_{i}"] = self._block_defs(k)
        return defs

    def init(self, rng):
        return L.init_params(self.param_defs(), rng)

    def abstract_params(self):
        return L.abstract_params(self.param_defs())

    def param_axes(self):
        return L.param_axes(self.param_defs())

    def param_count(self) -> int:
        return L.count_params(self.param_defs())

    # ------------------------------------------------------------- blocks
    def _mixer(self, kind, p, x, positions, mode, cache, pos, length=None):
        """Sequence mixer. Returns (y, new_cache)."""
        cfg = self.cfg
        if kind in ("attn", "attn_local"):
            window = cfg.window if kind == "attn_local" else None
            h_eff = cfg.pad_heads_to or cfg.num_heads
            att = dict(scale=cfg.attention_multiplier or None,
                       precision=cfg.precision)
            q, k, v = A.qkv_project(p["attn"], x, positions,
                                    qk_norm=cfg.qk_norm,
                                    rope_theta=cfg.rope_theta,
                                    use_rope=cfg.use_rope,
                                    head_dim=cfg.head_dim,
                                    precision=cfg.precision)
            if mode == "decode":
                new_cache = A.kv_cache_update(cache, k, v, pos)  # true kv
                dq = A.dequantize_cache(new_cache, cfg.jdtype)
                kx, vx, _ = A.expand_cache_heads(dq["k"], dq["v"],
                                                 cfg.num_heads, h_eff)
                qp, _ = A.pad_q_heads(q)
                o = A.decode_attention_einsum(qp, kx, vx, pos + 1,
                                              window=window,
                                              **att)[:, :, :h_eff]
            else:
                qp, kp, vp = A.prepare_heads(q, k, v, cfg.num_heads)
                if x.shape[1] <= max(cfg.block_q, 1024):
                    o = A.full_attention(qp, kp, vp, causal=True,
                                         window=window, **att)
                else:
                    o = A.blocked_attention(qp, kp, vp, causal=True,
                                            window=window,
                                            block_q=cfg.block_q,
                                            block_kv=cfg.block_kv, **att)
                o = o[:, :, :h_eff]
                new_cache = None
                if mode == "prefill":
                    new_cache = A.kv_cache_update(cache, k, v, 0)
            if h_eff != cfg.num_heads:
                # hard-mask dummy TP-padding heads → mathematically inert
                hm = (jnp.arange(h_eff) < cfg.num_heads).astype(o.dtype)
                o = o * hm[None, None, :, None]
            return A.out_project(p["attn"], o, cfg.precision), new_cache
        if kind == "rec":
            if mode == "decode":
                return R.rglru_step(p["rec"], x, cache)
            st = cache if mode == "prefill_chained" else None
            y, new_state = R.rglru_apply(p["rec"], x, state=st)
            if mode == "train":
                new_state = None
            elif mode == "prefill" and cache is not None:
                pass
            return y, new_state
        if kind == "mamba":
            mk = dict(groups=cfg.mamba_groups, eps=cfg.norm_eps or 1e-6,
                      precision=cfg.precision)
            if mode == "decode":
                return R.mamba2_step(p["mamba"], x, cache, **mk)
            y, state = R.mamba2_apply(
                p["mamba"], x, length=length,
                backend="ref" if mode == "train" else None, **mk)
            return y, (None if mode == "train" else state)
        if kind == "rwkv":
            if mode == "decode":
                return R.rwkv_time_mix_step(p["rwkv"], x, cache)
            st = cache if cache is not None else {
                "S": jnp.zeros((x.shape[0], self.cfg.num_heads,
                                self.cfg.head_dim, self.cfg.head_dim),
                               jnp.float32),
                "x_tm": jnp.zeros((x.shape[0], x.shape[2]), x.dtype)}
            y, new_state = R.rwkv_time_mix(p["rwkv"], x, st,
                                           chunk=self.cfg.rwkv_chunk)
            if mode == "train":
                new_state = None
            return y, new_state
        raise ValueError(kind)

    def _residual(self, x, y):
        """x + m·y: Granite scales each residual branch."""
        m = self.cfg.residual_multiplier
        return x + (y if m == 1.0 else y * m)

    def _norm(self, p, x):
        return L.apply_norm(self.cfg.norm, p, x, self.cfg.norm_eps)

    def _block(self, kind, p, x, positions, mode, cache, pos, length=None):
        """Apply one block. Returns (x, new_cache, aux)."""
        cfg = self.cfg
        aux = jnp.float32(0.0)
        h = self._norm(p["norm1"], x)
        mix_cache = None if cache is None else cache.get("mix")
        y, new_mix_cache = self._mixer(kind, p, h, positions, mode,
                                       mix_cache, pos, length)
        x = self._residual(x, y)
        new_cache = {}
        if new_mix_cache is not None:
            new_cache["mix"] = new_mix_cache
        if kind == "rwkv":
            h = self._norm(p["norm2"], x)
            cm_state = (cache or {}).get("x_cm",
                                         jnp.zeros((x.shape[0], x.shape[2]),
                                                   x.dtype))
            y, new_cm = R.rwkv_channel_mix(p["rwkv"], h, cm_state)
            x = x + y
            if cache is not None and mode != "train":
                new_cache["x_cm"] = new_cm
        else:
            h = self._norm(p["norm2"], x)
            if cfg.moe:
                y, aux = M.moe_apply(
                    p["moe"], h, num_experts=cfg.num_experts,
                    top_k=cfg.experts_per_token,
                    capacity_factor=cfg.capacity_factor,
                    activation=cfg.activation,
                    group_size=cfg.moe_group)
            else:
                y = L.mlp_apply(p["mlp"], h, cfg.activation, cfg.precision)
            x = self._residual(x, y)
        x = constrain(x, "batch", "seq", "embed")
        return x, (new_cache if new_cache else None), aux

    # ------------------------------------------------------------- forward
    def _embed_inputs(self, params, tokens, patch_embeds):
        x = L.embed_apply(params["embed"], tokens)
        if self.cfg.embedding_multiplier != 1.0:
            x = x * jnp.asarray(self.cfg.embedding_multiplier, x.dtype)
        if self.cfg.num_patches and patch_embeds is not None:
            pe = L.apply_norm("rmsnorm", params["patch_norm"],
                              patch_embeds.astype(x.dtype))
            P = pe.shape[1]
            x = jnp.concatenate([pe, x[:, P:]], axis=1)
        return constrain(x, "batch", "seq", "embed")

    def _run_blocks(self, params, x, positions, mode, cache, pos,
                    length=None):
        """Scan over periods + remainder blocks. Returns (x, new_cache, aux)."""
        cfg = self.cfg
        pattern = cfg.block_pattern
        aux_total = jnp.float32(0.0)
        new_cache = {} if cache is not None or mode == "prefill" else None

        if self.n_periods:
            blocks_p = params["blocks"]
            cache_p = None if cache is None else cache["blocks"]

            def period_body(carry, xs):
                xc, auxc = carry
                if cache_p is None:
                    pslices = xs
                    cslices = (None,) * len(pattern)
                else:
                    pslices, cslices = xs
                outs = []
                for i, kind in enumerate(pattern):
                    xc, c_new, a = self._block(kind, pslices[i], xc,
                                               positions, mode, cslices[i],
                                               pos, length)
                    outs.append(c_new)
                    auxc = auxc + a
                ys = tuple(outs) if any(o is not None for o in outs) else None
                return (xc, auxc), ys

            body = period_body
            if cfg.remat and mode == "train":
                body = jax.checkpoint(period_body,
                                      prevent_cse=False)
            xs = blocks_p if cache_p is None else (blocks_p, cache_p)
            (x, aux_total), ys = jax.lax.scan(body, (x, aux_total), xs)
            if ys is not None and new_cache is not None:
                new_cache["blocks"] = ys

        for i, kind in enumerate(self.rem_kinds):
            c = None if cache is None else cache[f"rem_{i}"]
            x, c_new, a = self._block(kind, params[f"rem_{i}"], x, positions,
                                      mode, c, pos, length)
            aux_total = aux_total + a
            if c_new is not None and new_cache is not None:
                new_cache[f"rem_{i}"] = c_new
        return x, new_cache, aux_total

    def forward(self, params, tokens, patch_embeds=None):
        """Training forward: tokens (B, S) → logits (B, S, V) fp32."""
        x = self._embed_inputs(params, tokens, patch_embeds)
        positions = jnp.arange(tokens.shape[1])[None, :]
        x, _, aux = self._run_blocks(params, x, positions, "train", None, 0)
        return self._logits(params, x), aux

    def _logits(self, params, x):
        """Final norm and head → (..., V) fp32. A tied head reads the
        embedding table at the model's matmul precision (a float32 model's
        at full precision); logits are divided by ``logits_scaling``."""
        cfg = self.cfg
        x = self._norm(params["final_norm"], x)
        if not cfg.tie_embeddings:
            return L.logits_apply(params["head"], x, cfg.vocab_size)
        logits = jnp.einsum("...d,vd->...v", x, params["embed"]["table"],
                            precision=cfg.precision).astype(jnp.float32)
        if cfg.logits_scaling != 1.0:
            logits = logits / cfg.logits_scaling
        if self.vocab_padded != cfg.vocab_size:
            vocab_pos = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                                 logits.ndim - 1)
            logits = jnp.where(vocab_pos < cfg.vocab_size, logits, -1e30)
        return logits

    def loss(self, params, batch):
        logits, aux = self.forward(params, batch["tokens"],
                                   batch.get("patch_embeds"))
        from ..core.metrics import cross_entropy
        ce = cross_entropy(logits[:, :-1], batch["labels"][:, 1:],
                           batch.get("mask"))
        return ce + self.cfg.aux_loss_coef * aux

    # ------------------------------------------------------------- serving
    def _cache_defs_block(self, kind, batch, max_len) -> dict:
        cfg = self.cfg
        dt = cfg.jdtype
        if kind in ("attn", "attn_local"):
            return {"mix": A.kv_cache_defs(batch, max_len, cfg.num_kv_heads,
                                           cfg.head_dim, dt,
                                           quant=cfg.kv_quant)}
        if kind == "rec":
            return {"mix": R.rglru_state_defs(batch, cfg.rnn_width,
                                              cfg.conv_width, dt)}
        if kind == "mamba":
            return {"mix": R.mamba2_state_defs(
                batch, cfg.mamba_heads, cfg.mamba_head_dim,
                cfg.mamba_d_state, cfg.mamba_groups, cfg.conv_width)}
        if kind == "rwkv":
            st = R.rwkv_state_defs(batch, cfg.num_heads, cfg.head_dim,
                                   cfg.d_model, dt)
            return {"mix": {"S": st["S"], "x_tm": st["x_tm"]},
                    "x_cm": st["x_cm"]}
        raise ValueError(kind)

    def cache_defs(self, batch: int, max_len: int) -> dict:
        defs: dict[str, Any] = {}
        if self.n_periods:
            defs["blocks"] = tuple(
                L.stack_defs(self._cache_defs_block(k, batch, max_len),
                             self.n_periods)
                for k in self.cfg.block_pattern)
        for i, k in enumerate(self.rem_kinds):
            defs[f"rem_{i}"] = self._cache_defs_block(k, batch, max_len)
        return defs

    def init_cache(self, batch: int, max_len: int):
        return L.init_params(self.cache_defs(batch, max_len), jax.random.key(0))

    def prefill(self, params, tokens, max_len: int, extra=None):
        """Process a full prompt, build the cache. ``extra`` is the VLM
        patch embeds (DecodeStep contract). Returns (logits_last, cache).
        A model whose every block masks padding (``masks_padding``) serves
        ``_prefill_length`` under this name."""
        return self._prefill_length(params, tokens, max_len, extra)

    def _prefill_length(self, params, tokens, max_len: int, extra=None,
                        length=None):
        """``prefill`` with ``length`` ((B,) or scalar true prompt lengths
        ≤ S): positions at or past a row's length are padding. Attention
        rows past it are written to the cache but never read (decode
        starts at ``pos = length`` and masks to it); Mamba leaves its state
        at the last valid position. The logits are each row's at
        ``length - 1``."""
        B, S = tokens.shape
        cache = self.init_cache(B, max_len)
        x = self._embed_inputs(params, tokens, extra)
        positions = jnp.arange(S)[None, :]
        x, new_cache, _ = self._run_blocks(params, x, positions, "prefill",
                                           cache, 0, length)
        if length is None:
            x = x[:, -1:]
        else:
            last = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (B,)) - 1
            x = jnp.take_along_axis(x, last[:, None, None], axis=1)
        return self._logits(params, x), new_cache

    def decode_step(self, params, cache, tokens, pos):
        """One decode step. tokens (B, 1); pos: scalar current position or
        (B,) per-sequence positions (continuous batching).
        Returns (logits (B, 1, V), new_cache)."""
        x = self._embed_inputs(params, tokens, None)
        pos = jnp.asarray(pos, jnp.int32)
        positions = (pos.reshape(-1, 1) if pos.ndim == 1
                     else jnp.full((1, 1), pos, jnp.int32))
        x, new_cache, _ = self._run_blocks(params, x, positions, "decode",
                                           cache, pos)
        return self._logits(params, x), new_cache

    @staticmethod
    def dense_served(plan, batch: int, kind: str) -> frozenset:
        """Paths of the row-balanced leaves of ``plan`` to serve as their
        pruned dense matrix rather than packed: those whose gather at
        ``batch`` activation rows on a ``kind`` device would take longer
        than streaming the dense bytes (``kernels.ops.serve_dense``). Each
        projection takes either form; a dense one runs at the model's
        matmul precision."""
        return frozenset(
            path for path, s in plan.sites.items()
            if s.fmt.name == "row_balanced" and serve_dense(
                s.d_out, s.d_in, keep_count(s.d_in, s.rule.ratio), batch,
                kind, jnp.dtype(s.dtype).itemsize))

    @staticmethod
    def pad_packed_params(packed):
        """Pad every packed leaf's rows, once, to the row block
        ``kernels.ops.rb_project`` runs it at (``project_block_rows``);
        a layer-stacked leaf pads each layer."""
        from ..kernels.ops import project_block_rows

        def pad(leaf):
            if not isinstance(leaf, RowBalancedSparse):
                return leaf
            block = project_block_rows(leaf.values.shape[-1])
            if leaf.values.ndim == 2:
                return pad_packed(leaf, block)
            layers = [pad_packed(jax.tree.map(lambda a: a[i], leaf), block)
                      for i in range(leaf.values.shape[0])]
            return jax.tree.map(lambda *a: jnp.stack(a), *layers)

        return jax.tree.map(
            pad, packed, is_leaf=lambda v: isinstance(v, RowBalancedSparse))
