"""The paper's LSTM (eq. 1–2) with first-class BRDS sparsity.

Gate layout: rows grouped by gate [f; i; g; o], each H rows, so W ∈ R^{4H×X}
and W_h ∈ R^{4H×H} exactly as in the paper (the paper interleaves the four
gates' rows in memory; grouping is an equivalent permutation — noted in
DESIGN.md). Dense masked path for training/retraining; packed row-balanced
path (rb_dual_spmv + lstm_gates Pallas kernels) for inference — the BRDS
accelerator datapath.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from . import layers as L
from ..core import sparsity as S
from ..core.packing import RowBalancedSparse, pad_packed
from ..kernels import ops as K
from ..quant import RowBalancedSparseQ8, quantize_packed, parse_scheme
from ..sparse import get_format, lstm_policy
from ..sparse import mask_grads as _sparse_mask_grads
from ..sparse.temporal import delta_threshold


@dataclasses.dataclass(frozen=True)
class LSTMConfig:
    name: str
    input_size: int            # X
    hidden: int                # H
    num_layers: int = 1
    vocab_size: int = 0        # >0 → language model (embed + head)
    num_classes: int = 0       # >0 → sequence classifier (IMDB) / framewise (TIMIT)
    framewise: bool = False    # per-step classification (TIMIT-style)
    dtype: Any = jnp.float32
    pwl_activations: bool = False   # paper's piecewise-linear σ/tanh


class LSTMModel:
    """The paper's LSTM behind every surface of the stack.

    ``delta`` (a ``repro.sparse.DeltaGateConfig`` or None) switches the
    serving path to Spartus-style temporal sparsity: the DecodeStep cache
    grows per-layer reference states (x_ref, h_ref), a partial-sum memory
    m, and fired-column counters (nx, nh), and prefill/decode step through
    ``_delta_step`` — only columns whose activation delta crossed Θ
    contribute matvec products (``kernels.ops.delta_rb_spmv`` on packed
    params, masked einsum on dense ones).

    ``quant`` (a ``repro.quant.QuantPlan`` or None) carries the calibrated
    per-layer activation scales for quantized packed params
    (RowBalancedSparseQ8 leaves): every step dispatches the q8 kernels
    (integer products, int32 accumulate, per-row dequant). Quantized
    params without a plan still serve — the kernels fall back to dynamic
    max-abs activation scales.

    ``mesh`` (a jax Mesh with a ``model`` axis, or None) switches packed
    decode to the sharded path (``repro.dist``): params must be
    ``partition_lstm_params``' gate-aligned row-sharded layout, the cache
    keeps c (and the delta partial sums m) sharded with h replicated, and
    each step's only collective is the all-gather of h. Composes with
    ``delta`` and ``quant``.

    ``fused`` (None/True/False) controls single-launch decode: the
    default (None) dispatches every packed step through the fused
    ``kernels.fused_step`` kernels — dual-ratio SpMV + bias + gates +
    cell in ONE ``pallas_call``, bitwise-identical to the chained path —
    wherever shapes allow; sharded (``mesh``) decode always falls back to
    the chained per-kernel path (the all-gather between Gate and Function
    needs the kernel boundary). ``fused=False`` forces the chained path
    (two to three launches per token)."""

    def __init__(self, cfg: LSTMConfig, delta=None, quant=None, mesh=None,
                 fused=None):
        self.cfg = cfg
        self.delta = delta
        self.quant = quant
        self.mesh = mesh
        self.fused = fused

    def with_delta(self, delta) -> "LSTMModel":
        """Copy of this model serving through the temporal-delta path
        (``delta``: a DeltaGateConfig, or None to disable)."""
        return LSTMModel(self.cfg, delta=delta, quant=self.quant,
                         mesh=self.mesh, fused=self.fused)

    def with_quant(self, quant) -> "LSTMModel":
        """Copy of this model carrying a quantization plan
        (``quant``: a repro.quant.QuantPlan, or None to disable)."""
        return LSTMModel(self.cfg, delta=self.delta, quant=quant,
                         mesh=self.mesh, fused=self.fused)

    def with_mesh(self, mesh) -> "LSTMModel":
        """Copy of this model decoding through the sharded packed path
        (``mesh``: a Mesh with a ``model`` axis — serve it
        ``repro.dist.partition_lstm_params``' layout — or None)."""
        return LSTMModel(self.cfg, delta=self.delta, quant=self.quant,
                         mesh=mesh, fused=self.fused)

    def with_fused(self, fused) -> "LSTMModel":
        """Copy of this model with single-launch fused decode forced on
        (True), forced off (False), or automatic (None — on wherever
        shapes allow)."""
        return LSTMModel(self.cfg, delta=self.delta, quant=self.quant,
                         mesh=self.mesh, fused=fused)

    @property
    def _use_fused(self) -> bool:
        """Fused single-launch kernels on this step? Default-on; sharded
        decode needs the chained kernel boundary for its collective."""
        return (self.fused is None or bool(self.fused)) \
            and self.mesh is None

    # ------------------------------------------------------------- params
    def param_defs(self) -> dict:
        cfg = self.cfg
        dt = cfg.dtype
        defs: dict[str, Any] = {"layers": []}
        for i in range(cfg.num_layers):
            x_in = cfg.input_size if i == 0 else cfg.hidden
            defs["layers"].append({
                "w_x": L.PSpec((4 * cfg.hidden, x_in),
                               ("lstm_gates", "embed"), dtype=dt),
                "w_h": L.PSpec((4 * cfg.hidden, cfg.hidden),
                               ("lstm_gates", "lstm_hidden"), dtype=dt),
                "b": L.PSpec((4 * cfg.hidden,), ("lstm_gates",),
                             init="zeros", dtype=dt),
            })
        if cfg.vocab_size:
            defs["embed"] = {"table": L.PSpec((cfg.vocab_size, cfg.input_size),
                                              ("vocab", "embed"), scale=1.0,
                                              dtype=dt)}
            defs["head"] = {"w": L.PSpec((cfg.hidden, cfg.vocab_size),
                                         ("embed", "vocab"), dtype=dt)}
        if cfg.num_classes:
            defs["head"] = {"w": L.PSpec((cfg.hidden, cfg.num_classes),
                                         ("embed", None), dtype=dt)}
        return defs

    def init(self, rng):
        return L.init_params(self.param_defs(), rng)

    def abstract_params(self):
        return L.abstract_params(self.param_defs())

    def param_axes(self):
        return L.param_axes(self.param_defs())

    def param_count(self) -> int:
        return L.count_params(self.param_defs())

    # ------------------------------------------------------------- core
    @staticmethod
    def _cell(z, c_prev, *, pwl=False):
        """z (B, 4H) grouped [f; i; g; o] → (c, h)."""
        H4 = z.shape[-1]
        H = H4 // 4
        zf, zi, zg, zo = (z[..., :H], z[..., H:2 * H], z[..., 2 * H:3 * H],
                          z[..., 3 * H:])
        from ..kernels.ref import lstm_cell_ref
        return lstm_cell_ref(zf, zi, zg, zo, c_prev, pwl=pwl)

    def _scan_layer(self, lp, xs, c0, h0):
        """xs (B, T, X_in) → hs (B, T, H)."""
        def step(carry, x_t):
            c, h = carry
            z = (x_t @ lp["w_x"].T + h @ lp["w_h"].T +
                 lp["b"][None, :]).astype(jnp.float32)
            c, h = self._cell(z, c, pwl=self.cfg.pwl_activations)
            return (c, h), h
        (c, h), hs = jax.lax.scan(step, (c0, h0), xs.transpose(1, 0, 2))
        return hs.transpose(1, 0, 2), (c, h)

    def features(self, params, inputs):
        """inputs: tokens (B, T) int if LM else features (B, T, X).
        Returns per-step hidden states of the last layer (B, T, H)."""
        cfg = self.cfg
        if cfg.vocab_size:
            x = L.embed_apply(params["embed"], inputs)
        else:
            x = inputs.astype(cfg.dtype)
        B = x.shape[0]
        for lp in params["layers"]:
            c0 = jnp.zeros((B, cfg.hidden), cfg.dtype)
            h0 = jnp.zeros((B, cfg.hidden), cfg.dtype)
            x, _ = self._scan_layer(lp, x, c0, h0)
        return x

    def forward(self, params, inputs):
        cfg = self.cfg
        hs = self.features(params, inputs)
        if cfg.vocab_size:
            return jnp.einsum("bth,hv->btv", hs,
                              params["head"]["w"]).astype(jnp.float32)
        logits = jnp.einsum("bth,hc->btc", hs,
                            params["head"]["w"]).astype(jnp.float32)
        return logits if cfg.framewise else logits[:, -1]

    def loss(self, params, batch):
        from ..core.metrics import cross_entropy
        cfg = self.cfg
        logits = self.forward(params, batch["inputs"])
        if cfg.vocab_size:
            return cross_entropy(logits[:, :-1], batch["labels"][:, 1:])
        if cfg.framewise:
            return cross_entropy(logits, batch["labels"])
        lab = batch["labels"]
        onehot = jax.nn.one_hot(lab, logits.shape[-1])
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.sum(onehot * logp, axis=-1))

    # ------------------------------------------------------------- BRDS
    # The sparsity surface is repro.sparse: these methods are conveniences
    # over lstm_policy → SparsityPlan so existing callers keep working.
    def sparsity_policy(self, spar_x: float, spar_h: float, *,
                        backend: str = "auto"):
        """The paper's dual-ratio policy for this model's param tree."""
        return lstm_policy(spar_x, spar_h, backend=backend)

    def prune(self, params, spar_x: float, spar_h: float):
        """Row-balanced dual-ratio prune of every layer. Returns
        (pruned_params, masks) — masks: {path: bool_mask} (repro.sparse
        layout, accepted by mask_grads)."""
        plan = self.sparsity_policy(spar_x, spar_h).compile(params)
        return plan.prune(params)

    def mask_grads(self, grads, masks):
        """Freeze pruned weights: zero their gradients. Accepts the plan's
        {path: mask} dict or the legacy per-layer list of dicts."""
        if isinstance(masks, dict):
            return _sparse_mask_grads(grads, masks)
        new_layers = []
        for g, m in zip(grads["layers"], masks):
            new_layers.append({**g,
                               "w_x": S.apply_mask(g["w_x"], m["w_x"]),
                               "w_h": S.apply_mask(g["w_h"], m["w_h"])})
        return {**grads, "layers": new_layers}

    def pack(self, params, masks: dict | None = None, quant=None):
        """Pack pruned layers into RowBalancedSparse pairs for serving.

        ``masks`` is the {path: mask} dict from ``prune`` — packing from
        the plan's masks keeps surviving weights that happen to be exactly
        zero and preserves the row-balance accounting. With masks=None the
        survivors are re-selected per row by magnitude at the maximum
        per-row non-zero count (ties resolve to zeros, so rows stay
        balanced even if some survivors vanished during retraining).
        ``quant`` (a scheme name like ``"int8"``/``"q1.11"``, a
        QuantScheme, or a QuantConfig) additionally quantizes each packed
        matrix to RowBalancedSparseQ8 (integer codes + per-row scales)."""
        fmt = get_format("row_balanced")
        scheme = None
        if quant is not None:
            scheme = parse_scheme(getattr(quant, "scheme", quant))
        packed = []
        for i, lp in enumerate(params["layers"]):
            entry = {"b": lp["b"]}
            for key, out in (("w_x", "sx"), ("w_h", "sh")):
                m = (masks or {}).get(f"layers/{i}/{key}")
                if m is None:
                    m = _survivor_mask(lp[key])
                s = fmt.pack(lp[key], m)
                s = quantize_packed(s, scheme) if scheme else s
                # pad the row axis to the kernel block multiple ONCE here
                # instead of inside every jitted step (sharded decode
                # re-partitions rows, so it packs unpadded)
                entry[out] = s if self.mesh is not None else pad_packed(s)
            packed.append(entry)
        return packed

    @staticmethod
    def pad_packed_params(packed, block_rows: int = 256):
        """Pre-pad every packed matrix's rows to the kernel-block multiple
        (``core.packing.pad_packed``) so the per-step wrappers consume the
        arrays as-is — no per-token re-pad copy of the weight stream on
        the decode hot path. Accepts ``pack``'s per-layer list or a
        SparsityPlan.pack'd param tree; no-op on already-padded or dense
        leaves."""
        def _pad(s):
            return (pad_packed(s, block_rows)
                    if isinstance(s, (RowBalancedSparse, RowBalancedSparseQ8))
                    else s)
        if isinstance(packed, dict) and "layers" in packed:
            return {**packed, "layers": [
                {**lp, "w_x": _pad(lp["w_x"]), "w_h": _pad(lp["w_h"])}
                for lp in packed["layers"]]}
        return [{**lp, "sx": _pad(lp["sx"]), "sh": _pad(lp["sh"])}
                for lp in packed]

    @staticmethod
    def _packed_layers(packed):
        """Normalize to the per-layer [{'sx','sh','b'}] list: accepts that
        list directly or a SparsityPlan.pack'd param tree (whose w_x/w_h
        leaves are RowBalancedSparse)."""
        if isinstance(packed, dict) and "layers" in packed:
            return [{"sx": lp["w_x"], "sh": lp["w_h"], "b": lp["b"]}
                    for lp in packed["layers"]]
        return packed

    def _act_scales(self, i: int):
        """Calibrated (s_x, s_h) activation scales for layer ``i``, or
        (None, None) — the q8 kernels then fall back to dynamic max-abs
        (scaled schemes) / the fixed-point constant."""
        if self.quant is None or i >= self.quant.num_layers:
            return (None, None)
        return self.quant.scale_for(i)

    def sparse_step(self, packed, x_t, state, *, backend: str | None = None):
        """One inference time step on the packed BRDS path.

        x_t (B, X); state: list of (c, h) per layer. The dual-ratio fused
        kernel is the accelerator's Gate module; lstm_gates is Function.
        ``packed`` is model.pack's per-layer list or a SparsityPlan.pack'd
        param tree; quantized packings (RowBalancedSparseQ8) run the q8
        datapath."""
        new_state = []
        inp = x_t
        for i, (lp, (c, h)) in enumerate(zip(self._packed_layers(packed),
                                             state)):
            if isinstance(lp["sx"], RowBalancedSparseQ8):
                ax, ah = self._act_scales(i)
                c, h = K.brds_lstm_step_q8(
                    lp["sx"], inp, lp["sh"], h, lp["b"], c,
                    act_scale_x=ax, act_scale_h=ah,
                    pwl=self.cfg.pwl_activations, backend=backend)
            else:
                c, h = K.brds_lstm_step(lp["sx"], inp, lp["sh"], h, lp["b"],
                                        c, pwl=self.cfg.pwl_activations,
                                        backend=backend)
            new_state.append((c, h))
            inp = h
        return inp, new_state

    def dense_step(self, params, x_t, state):
        """Dense reference step (same contract as sparse_step)."""
        new_state = []
        inp = x_t
        for lp, (c, h) in zip(params["layers"], state):
            z = (inp @ lp["w_x"].T + h @ lp["w_h"].T +
                 lp["b"][None, :]).astype(jnp.float32)
            c, h = self._cell(z, c, pwl=self.cfg.pwl_activations)
            new_state.append((c, h))
            inp = h
        return inp, new_state

    def init_state(self, batch: int):
        cfg = self.cfg
        return [(jnp.zeros((batch, cfg.hidden), cfg.dtype),
                 jnp.zeros((batch, cfg.hidden), cfg.dtype))
                for _ in range(cfg.num_layers)]

    # ------------------------------------------------------------- serving
    # DecodeStep contract (repro.serving.runtime): the recurrent (c, h)
    # pair per layer IS the decode cache. decode_step dispatches on the
    # param leaves: SparsityPlan.pack'd trees (w_x/w_h are
    # RowBalancedSparse) run the packed rb_dual_spmv + lstm_gates
    # accelerator datapath, quantized trees (RowBalancedSparseQ8) the q8
    # int32-accumulate datapath; dense trees run the reference einsum step.
    supports_packed_decode = True

    @staticmethod
    def is_packed(params) -> bool:
        return isinstance(params["layers"][0]["w_x"],
                          (RowBalancedSparse, RowBalancedSparseQ8))

    @staticmethod
    def is_quantized(params) -> bool:
        return isinstance(params["layers"][0]["w_x"], RowBalancedSparseQ8)

    def cache_defs(self, batch: int, max_len: int) -> dict:
        """Decode-cache declaration (a PSpec pytree).

        ``max_len`` is part of the contract but unused — state is O(1).
        With temporal sparsity enabled the cache additionally carries, per
        layer: the reference states ``x_ref`` (B, X_in) / ``h_ref``
        (B, H), the fp32 partial-sum memory ``m`` (B, 4H), and cumulative
        fired-column counters ``nx``/``nh`` (B,) — the effective-ops
        numerators ``repro.sparse.occupancy_report`` reduces.

        With a ``mesh`` the sharded-decode layouts apply: ``c`` carries
        the ``lstm_hidden_shard`` logical axis (model-sharded with the
        gate rows it is updated from) while ``h`` stays replicated — the
        per-step activation broadcast (``m`` already rides the
        model-sharded ``lstm_gates`` axis)."""
        cfg = self.cfg
        c_ax = "lstm_hidden_shard" if self.mesh is not None else "lstm_hidden"
        defs = {"layers": [
            {"c": L.PSpec((batch, cfg.hidden), ("batch", c_ax),
                          init="zeros", dtype=cfg.dtype),
             "h": L.PSpec((batch, cfg.hidden), ("batch", "lstm_hidden"),
                          init="zeros", dtype=cfg.dtype)}
            for _ in range(cfg.num_layers)]}
        if self.delta is not None:
            for i, lp in enumerate(defs["layers"]):
                x_in = cfg.input_size if i == 0 else cfg.hidden
                lp.update({
                    "x_ref": L.PSpec((batch, x_in), ("batch", "embed"),
                                     init="zeros", dtype=cfg.dtype),
                    "h_ref": L.PSpec((batch, cfg.hidden),
                                     ("batch", "lstm_hidden"),
                                     init="zeros", dtype=cfg.dtype),
                    "m": L.PSpec((batch, 4 * cfg.hidden),
                                 ("batch", "lstm_gates"),
                                 init="zeros", dtype=jnp.float32),
                    "nx": L.PSpec((batch,), ("batch",), init="zeros",
                                  dtype=jnp.float32),
                    "nh": L.PSpec((batch,), ("batch",), init="zeros",
                                  dtype=jnp.float32),
                })
        return defs

    def init_cache(self, batch: int, max_len: int):
        return L.init_params(self.cache_defs(batch, max_len),
                             jax.random.key(0))

    def _step(self, params, x_t, state):
        """One time step, packed or dense by param type. state/new_state:
        list of (c, h); returns (h_last, new_state) in cfg.dtype."""
        cfg = self.cfg
        packed = self.is_packed(params)
        quantized = packed and self.is_quantized(params)
        if packed and self.mesh is not None:
            from ..dist import collective_ops as C
            scales = ([self._act_scales(i) for i in range(cfg.num_layers)]
                      if quantized else None)
            return C.dist_lstm_step(self.mesh, params["layers"], x_t, state,
                                    pwl=cfg.pwl_activations, dtype=cfg.dtype,
                                    act_scales=scales)
        fused = self._use_fused
        new_state = []
        inp = x_t
        for i, (lp, (c, h)) in enumerate(zip(params["layers"], state)):
            if quantized:
                ax, ah = self._act_scales(i)
                step_q8 = (K.fused_brds_lstm_step_q8 if fused
                           else K.brds_lstm_step_q8)
                c, h = step_q8(lp["w_x"], inp, lp["w_h"], h,
                               lp["b"], c, act_scale_x=ax,
                               act_scale_h=ah,
                               pwl=cfg.pwl_activations)
            elif packed:
                step = (K.fused_brds_lstm_step if fused
                        else K.brds_lstm_step)
                c, h = step(lp["w_x"], inp, lp["w_h"], h,
                            lp["b"], c,
                            pwl=cfg.pwl_activations)
            else:
                z = (inp @ lp["w_x"].T + h @ lp["w_h"].T +
                     lp["b"][None, :]).astype(jnp.float32)
                c, h = self._cell(z, c, pwl=cfg.pwl_activations)
            c, h = c.astype(cfg.dtype), h.astype(cfg.dtype)
            new_state.append((c, h))
            inp = h
        return inp, new_state

    def _delta_step(self, params, x_t, state):
        """One temporally-sparse time step (Spartus composition).

        ``state``: per-layer dicts {c, h, x_ref, h_ref, m, nx, nh}. Each
        layer thresholds its input/hidden deltas against the reference
        states and advances the partial-sum memory with only the fired
        columns' products: packed params run the fused
        ``brds_delta_lstm_step`` (delta_rb_dual_spmv + lstm_gates), dense
        params the masked-delta einsum. Returns (h_last, new_state)."""
        cfg = self.cfg
        d = self.delta
        packed = self.is_packed(params)
        quantized = packed and self.is_quantized(params)
        if packed and self.mesh is not None:
            from ..dist import collective_ops as C
            scales = None
            if quantized:
                # same delta-range doubling as the loop below: the
                # calibrated scales bound absolute activations, a delta
                # spans twice that range
                scales = [tuple(None if s is None else 2.0 * s
                                for s in self._act_scales(i))
                          for i in range(cfg.num_layers)]
            return C.dist_delta_lstm_step(
                self.mesh, params["layers"], x_t, state, d,
                pwl=cfg.pwl_activations, dtype=cfg.dtype, act_scales=scales)
        fused = self._use_fused
        new_state = []
        inp = x_t
        for i, (lp, st) in enumerate(zip(params["layers"], state)):
            dx, fx, x_ref = delta_threshold(inp, st["x_ref"], d.theta_x,
                                            d.cap_x)
            dh, fh, h_ref = delta_threshold(st["h"], st["h_ref"], d.theta_h,
                                            d.cap_h)
            if quantized:
                ax, ah = self._act_scales(i)
                # the calibrated scales bound ABSOLUTE activations; a
                # delta spans up to twice that range (−amax → +amax), and
                # a clipped delta bakes its error into the partial-sum
                # memory permanently — double the scale on this path
                # (fixed-point schemes ignore it: they saturate by design)
                ax = None if ax is None else 2.0 * ax
                ah = None if ah is None else 2.0 * ah
                step_q8 = (K.fused_brds_delta_lstm_step_q8 if fused
                           else K.brds_delta_lstm_step_q8)
                c, h, m = step_q8(
                    lp["w_x"], dx, fx, lp["w_h"], dh, fh, st["m"], lp["b"],
                    st["c"], act_scale_x=ax, act_scale_h=ah,
                    pwl=cfg.pwl_activations)
            elif packed:
                step_d = (K.fused_brds_delta_lstm_step if fused
                          else K.brds_delta_lstm_step)
                c, h, m = step_d(
                    lp["w_x"], dx, fx, lp["w_h"], dh, fh, st["m"], lp["b"],
                    st["c"], pwl=cfg.pwl_activations)
            else:
                dxm = jnp.where(fx, dx, 0).astype(jnp.float32)
                dhm = jnp.where(fh, dh, 0).astype(jnp.float32)
                m = (st["m"].astype(jnp.float32)
                     + dxm @ lp["w_x"].T.astype(jnp.float32)
                     + dhm @ lp["w_h"].T.astype(jnp.float32))
                z = m + lp["b"].astype(jnp.float32)[None, :]
                c, h = self._cell(z, st["c"], pwl=cfg.pwl_activations)
            new_state.append({
                "c": c.astype(cfg.dtype), "h": h.astype(cfg.dtype),
                "x_ref": x_ref, "h_ref": h_ref,
                "m": m.astype(jnp.float32),
                "nx": st["nx"] + jnp.sum(fx, axis=1, dtype=jnp.float32),
                "nh": st["nh"] + jnp.sum(fh, axis=1, dtype=jnp.float32)})
            inp = new_state[-1]["h"]
        return inp, new_state

    def score(self, params, inputs, labels=None):
        """Teacher-forced mean NLL through the SERVING step path.

        Unlike ``loss`` (the training-time dense scan), ``score`` steps
        every position through ``_step``/``_delta_step`` — the exact
        per-token computation decode runs — so it accepts dense, packed
        (RowBalancedSparse), quantized (RowBalancedSparseQ8), and
        temporal-delta deployments alike and produces the quality number
        *of the deployed model*. ``launch.pipeline`` uses it on both sides
        of its serving-parity gate: the manually packed model and the
        ``ServeEngine.prepare``'d one must score bitwise equal.

        Parameters
        ----------
        params : pytree
            Dense or packed param tree (embed/head stay dense either way).
        inputs : jnp.ndarray
            (B, T) token ids (LM — next-token NLL over positions 1..T-1)
            or (B, T, X) frames (framewise — per-step NLL vs ``labels``).
        labels : jnp.ndarray, optional
            (B, T) int labels; defaults to ``inputs`` (the LM case).

        Returns
        -------
        jnp.ndarray
            Scalar fp32 mean NLL (``core.metrics.perplexity`` exponentiates
            it).
        """
        from ..core.metrics import cross_entropy
        cfg = self.cfg
        if cfg.vocab_size:
            x = L.embed_apply(params["embed"], inputs)
            if labels is None:
                labels = inputs
        else:
            x = inputs.astype(cfg.dtype)
            if labels is None:
                raise ValueError("framewise score needs labels")
        B, T = x.shape[0], x.shape[1]
        if self.delta is not None:
            state0 = tuple(self.init_cache(B, T)["layers"])
            step_fn = lambda st, x_t: self._delta_step(params, x_t, list(st))
        else:
            state0 = tuple(self.init_state(B))
            step_fn = lambda st, x_t: self._step(params, x_t, st)

        def body(st, x_t):
            h, st2 = step_fn(st, x_t)
            return tuple(st2), h

        _, hs = jax.lax.scan(body, state0, x.transpose(1, 0, 2))
        hs = hs.transpose(1, 0, 2)
        logits = jnp.einsum("bth,hv->btv", hs.astype(jnp.float32),
                            params["head"]["w"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        if cfg.vocab_size:
            return cross_entropy(logits[:, :-1], labels[:, 1:])
        return cross_entropy(logits, labels)

    def _head_logits(self, params, h):
        """h (B, H) → logits (B, 1, V or C) fp32.

        Full f32 precision: at the TPU's default, which rounds f32
        operands to bf16, a row's greedy tokens on a v5e depended on the
        batch it was served in, breaking the scheduler's parity with
        batch-1 decode."""
        return jnp.einsum("bh,hv->bv", h.astype(jnp.float32),
                          params["head"]["w"].astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST)[:, None]

    def _embed_step(self, params, tokens):
        """tokens (B, 1) ids (LM) or (B, 1, X) features → x_t (B, X)."""
        if self.cfg.vocab_size:
            return L.embed_apply(params["embed"], tokens[:, 0])
        return tokens[:, 0].astype(self.cfg.dtype)

    def prefill(self, params, tokens, max_len: int, extra=None,
                length=None):
        """Process a full prompt, build the decode cache.

        Works on dense and SparsityPlan.pack'd params. With temporal
        sparsity enabled the prompt is scanned through ``_delta_step`` so
        the reference states, partial sums, and occupancy counters arrive
        at decode already warm (the Spartus steady state).

        Parameters
        ----------
        params : pytree
            Dense or packed param tree.
        tokens : jnp.ndarray
            (B, S) int token ids (LM) or (B, S, X) feature frames.
        max_len : int
            Cache capacity (contractual; the LSTM cache is O(1)).
        extra : Any, optional
            Unused by the LSTM (family conditioning slot).
        length : int or (B,) int32, optional
            True prompt length(s) when ``tokens`` is right-padded to a
            bucket: steps at t ≥ length compute-and-discard (the carry is
            frozen per sequence), so the returned cache and last-valid
            logits are BITWISE what the unpadded prompt would produce.
            This is the scheduler's bucketed-prefill hook — one compile
            per padded width instead of one per distinct prompt length.

        Returns
        -------
        (logits, cache)
            Logits at the last (valid) position (B, 1, V) and the decode
            cache.
        """
        cfg = self.cfg
        if cfg.vocab_size:
            x = L.embed_apply(params["embed"], tokens)
        else:
            x = tokens.astype(cfg.dtype)
        B = x.shape[0]
        delta = self.delta is not None
        if delta:
            state0 = tuple(self.init_cache(B, max_len)["layers"])
            step_fn = lambda st, x_t: self._delta_step(params, x_t, list(st))
        else:
            state0 = tuple(self.init_state(B))
            step_fn = lambda st, x_t: self._step(params, x_t, st)

        # Exact (length=None) and bucketed prefill share ONE scan body:
        # the select that freezes padded-out state changes XLA's fusion
        # decisions inside the loop body at the ulp level, so a separate
        # unmasked fast path would NOT be bitwise against the masked one.
        # Running every prefill through the masked body makes padded+length
        # reproduce the unpadded prefill exactly (same compiled body, the
        # selects are all-keep no-ops below each sequence's length).
        if length is None:
            length = x.shape[1]
        length = jnp.asarray(length, jnp.int32)

        def step(carry, xt):
            st, h_last = carry
            x_t, t = xt
            h, st2 = step_fn(st, x_t)
            keep = jnp.broadcast_to(t < length, (B,))
            sel = lambda n, o: jnp.where(
                keep.reshape((B,) + (1,) * (n.ndim - 1)), n, o)
            st2 = jax.tree.map(sel, tuple(st2), st)
            return (st2, jnp.where(keep[:, None], h, h_last)), None

        h0 = jnp.zeros((B, cfg.hidden), cfg.dtype)
        (state, h_last), _ = jax.lax.scan(
            step, (state0, h0),
            (x.transpose(1, 0, 2), jnp.arange(x.shape[1])))
        logits = self._head_logits(params, h_last)
        if delta:
            return logits, {"layers": list(state)}
        return logits, {"layers": [{"c": c, "h": h} for c, h in state]}

    def decode_step(self, params, cache, tokens, pos):
        """One decode step over the cache.

        ``pos`` is accepted per the DecodeStep contract but unused (the
        recurrent cache has no positional structure). Dispatches packed vs
        dense on the param leaves, and through the temporal-delta path
        when the model carries a ``delta`` config.

        Returns
        -------
        (logits, cache)
            Logits (B, 1, V) and the advanced cache.
        """
        x_t = self._embed_step(params, tokens)
        if self.delta is not None:
            h, new_state = self._delta_step(params, x_t, cache["layers"])
            return self._head_logits(params, h), {"layers": new_state}
        state = [(lp["c"], lp["h"]) for lp in cache["layers"]]
        h, new_state = self._step(params, x_t, state)
        logits = self._head_logits(params, h)
        cache = {"layers": [{"c": c, "h": h} for c, h in new_state]}
        return logits, cache


def _survivor_mask(w) -> jnp.ndarray:
    """Row-balanced keep-mask for an already-pruned dense weight: per-row
    magnitude top-K at the maximum per-row non-zero count (zero-ties keep
    every row at exactly K non-zeros)."""
    import numpy as np
    counts = np.asarray(jnp.sum(w != 0, axis=1))
    k = int(counts.max()) if counts.size else 0
    order = jnp.argsort(-jnp.abs(w), axis=1)[:, :k]
    rows = jnp.broadcast_to(jnp.arange(w.shape[0])[:, None], order.shape)
    return jnp.zeros(w.shape, bool).at[rows, order].set(True)


# Paper benchmark configs (§5.1): TIMIT X=153 H=1024; PTB large 1500/1500;
# IMDB binary classifier.
LSTM_CONFIGS = {
    "lstm_timit": LSTMConfig("lstm_timit", input_size=153, hidden=1024,
                             num_classes=61, framewise=True),
    "lstm_ptb": LSTMConfig("lstm_ptb", input_size=1500, hidden=1500,
                           vocab_size=10000),
    "lstm_imdb": LSTMConfig("lstm_imdb", input_size=128, hidden=512,
                            vocab_size=0, num_classes=2),
}
