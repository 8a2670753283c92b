"""Serving engine: sharded prefill + on-device lockstep batched decode.

The engine programs against the ``DecodeStep`` contract (runtime.py): any
model with cache_defs / prefill / decode_step — the transformer zoo, the
enc-dec, and the paper's LSTM — serves through the same code path.
Generation is one jitted ``lax.scan`` (runtime.decode_loop) with the cache
donated and sampling on device: one dispatch per generate call, zero
per-token host syncs.

``sparsity=`` is the repro.sparse seam: ``prepare(params)`` prunes to the
policy's patterns and, for models that decode through packed kernels
(``supports_packed_decode``, e.g. the LSTM's rb_dual_spmv + lstm_gates
datapath), packs the surviving weights so serving exercises the BRDS
accelerator path. A model whose projections take either form
(``dense_served``, the transformer zoo) packs only the leaves whose
gather at the engine's slot batch beats their dense bytes on the device
it runs on; the rest serve as masked-dense matmuls at the model's
precision.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kernels import ops as kops
from ..obs import trace as obs_trace
from ..training.train_loop import param_shardings
from ..sharding import named_sharding
from . import runtime
from .sampling import SamplingConfig


def cache_shardings(mesh: Mesh, model, batch: int, max_len: int):
    from ..models import layers as L
    defs = model.cache_defs(batch, max_len)
    axes = L.param_axes(defs)
    shapes = L.param_shapes(defs)
    return jax.tree.map(
        lambda lg, sh: named_sharding(mesh, lg, sh),
        axes, shapes,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))


class ServeEngine:
    def __init__(self, model, cfg=None, mesh: Mesh | None = None,
                 max_len: int = 2048, batch: int = 8, sparsity=None):
        """``sparsity`` is the repro.sparse seam: a SparsityPolicy (or an
        already-compiled SparsityPlan) applied to params via ``prepare``
        before serving — the BRDS deployment scenario."""
        if not runtime.conforms(model):
            raise TypeError(
                f"{type(model).__name__} does not implement the DecodeStep "
                "serving contract (cache_defs / prefill / decode_step)")
        self.model = model
        self.cfg = cfg
        self.mesh = mesh
        self.max_len = max_len
        self.batch = batch
        self.sparsity = sparsity
        self._loops: dict = {}
        # flipped by prepare() when params get dist-partitioned (sharded
        # packed decode): the loop then jits WITHOUT explicit shardings —
        # partitioned params are device-committed and the model's
        # shard_map step pins the cache layout
        self._dist = False
        if mesh is not None:
            self._p_sh = param_shardings(mesh, model)
            self._c_sh = cache_shardings(mesh, model, batch, max_len)
            self._b_sh = NamedSharding(mesh, P(("pod", "data") if "pod" in
                                               mesh.axis_names else "data"))
            self._scalar = NamedSharding(mesh, P())
        self._prefill = jax.jit(model.prefill,
                                static_argnames=("max_len",))
        self._dprefills: dict = {}   # id(draft) → jitted draft prefill

    @obs_trace.traced("engine.prepare")
    def prepare(self, params, pack: bool | None = None, calib=None):
        """Apply the engine's sparsity policy/plan to params. Prunes to the
        policy's patterns; when the model decodes through packed kernels
        (``pack=None`` → ``model.supports_packed_decode``), the pruned
        weights are additionally packed from the prune masks so decode runs
        the row-balanced SpMV path. A policy carrying an activation rule
        (``DeltaGateConfig``) is wired into the model here: the engine
        swaps in ``model.with_delta(...)`` so the decode cache grows the
        temporal reference state and every step skips unfired columns.
        A policy ``quant`` rule (``QuantConfig``) likewise rewires the
        model: activation scales are calibrated over ``calib`` (a token /
        feature batch run through the DENSE params — ``repro.quant.
        calibrate_lstm``; scale-free fallback when None), the model swaps
        to ``with_quant(plan)``, and packing emits RowBalancedSparseQ8 so
        decode runs the int32-accumulate q8 kernels.
        A model that serves a projection from either form
        (``dense_served``) keeps the leaves the engine's slot batch would
        make the gather slower than the dense bytes as their pruned dense
        matrix (``kernels.ops.serve_dense``, from the leaf's shape, the
        batch and the device kind); they are never packed.
        Returns (params, report) — report is None when the engine is
        dense; a packed report carries ``gather_visit_share`` per
        projection leaf (``SparsityPlan.gather_visit_shares``; 0.0 for a
        dense-served leaf, whose source chunks no gather visits) and
        ``dense_served``, the share of kept entries served dense."""
        if self.sparsity is None:
            return params, None
        plan = (self.sparsity.compile(params)
                if hasattr(self.sparsity, "compile") else self.sparsity)
        act = getattr(plan, "activation", None)
        qcfg = getattr(plan, "quant", None)
        rewired = False
        if act is not None:
            if not hasattr(self.model, "with_delta"):
                raise ValueError(
                    f"sparsity policy carries an activation rule ({act}) "
                    f"but {type(self.model).__name__} has no temporal-"
                    "delta serving path (with_delta)")
            self.model = self.model.with_delta(act)
            rewired = True
        if qcfg is not None:
            if not hasattr(self.model, "with_quant"):
                raise ValueError(
                    f"sparsity policy carries a quant rule ({qcfg}) but "
                    f"{type(self.model).__name__} has no quantized "
                    "serving path (with_quant)")
            from ..quant import calibrate_lstm, default_plan
            if calib is not None:
                qplan = calibrate_lstm(self.model, params, calib, qcfg)
            else:
                qplan = default_plan(qcfg, len(params["layers"]))
            self.model = self.model.with_quant(qplan)
            rewired = True
        if rewired:
            self._prefill = jax.jit(self.model.prefill,
                                    static_argnames=("max_len",))
            self._loops.clear()
            if self.mesh is not None:   # the delta cache has more leaves
                self._c_sh = cache_shardings(self.mesh, self.model,
                                             self.batch, self.max_len)
        pruned, masks = plan.prune(params)
        report = plan.summary(masks)
        if pack is None:
            pack = getattr(self.model, "supports_packed_decode", False)
        if pack:
            # leaves whose gather at the slot batch would outlast their
            # dense bytes serve as the pruned matrix, never packed
            dense = (self.model.dense_served(plan, self.batch,
                                             kops.device_kind())
                     if hasattr(self.model, "dense_served") else frozenset())
            packed, pack_report = plan.pack(pruned, masks, dense=dense)
            pack_report["gather_visit_share"] = {
                **plan.gather_visit_shares(packed),
                **dict.fromkeys(dense, 0.0)}
            kept = {p: int(jnp.sum(m)) for p, m in masks.items()}
            pack_report["dense_served"] = (
                sum(kept[p] for p in dense) / max(sum(kept.values()), 1))
            packed = self._maybe_partition(packed)
            if not self._dist and hasattr(self.model, "pad_packed_params"):
                # hoist the kernel-block row padding out of the per-token
                # hot path (sharded decode re-splits rows — skip there)
                packed = self.model.pad_packed_params(packed)
            return packed, {**report, **pack_report}
        return pruned, report

    def _maybe_partition(self, packed):
        """Shard packed params across the engine's mesh (repro.dist):
        gate-aligned row-sharded weights, model rewired to the sharded
        decode step. No-op without a mesh / a model-axis / packed leaves."""
        from .. import dist
        if (self.mesh is None or not dist.supports_dist(self.model, self.mesh)
                or not dist.is_partitionable(packed)):
            return packed
        packed = dist.partition_lstm_params(packed, self.mesh)
        self.model = self.model.with_mesh(self.mesh)
        self._dist = True
        self._prefill = jax.jit(self.model.prefill,
                                static_argnames=("max_len",))
        self._loops.clear()
        return packed

    # ------------------------------------------------------------ decode
    def _loop(self, steps: int, sampling: SamplingConfig):
        """One jitted scan-decode per (steps, sampling); cache donated."""
        key = (steps, sampling)
        if key not in self._loops:
            def run(params, cache, logits, pos, rng):
                return runtime.decode_loop(
                    self.model, params, cache, logits, pos, rng, steps,
                    sampling, limit=self.max_len)
            if self.mesh is not None and not self._dist:
                fn = jax.jit(run,
                             in_shardings=(self._p_sh, self._c_sh,
                                           self._b_sh, self._scalar,
                                           self._scalar),
                             donate_argnums=(1,))
            else:
                fn = jax.jit(run, donate_argnums=(1,))
            self._loops[key] = fn
        return self._loops[key]

    def _spec_loop(self, steps: int, k: int, sampling: SamplingConfig,
                   draft):
        """One jitted speculative round loop per (steps, k, sampling,
        draft); target cache + draft state donated. Jits plain (no
        explicit shardings) — the spec loop is a CPU/single-device
        serving composition."""
        key = ("spec", steps, k, sampling, draft.sampling, id(draft))
        if key not in self._loops:
            from ..spec import spec_decode_loop

            def run(params, dparams, cache, dstate, probs, pos, rng):
                return spec_decode_loop(
                    self.model, draft, params, dparams, cache, dstate,
                    probs, pos, rng, steps, k, sampling,
                    limit=self.max_len)

            self._loops[key] = jax.jit(run, donate_argnums=(2, 3))
        return self._loops[key]

    def generate(self, params, tokens, steps: int, *, extra=None,
                 temperature: float = 0.0, top_k: int = 0, eos_id: int = -1,
                 rng=None, sampling: SamplingConfig | None = None,
                 return_state: bool = False, lengths=None, draft=None,
                 spec_k: int = 4):
        """Generate ``steps`` tokens for a lockstep batch of prompts.

        tokens (B, S) prompt; ``extra`` is family-specific conditioning
        (encoder frames, patch embeds). Returns (B, steps) int32 ids —
        finished sequences (per-sequence EOS) pad with ``sampling.pad_id``.
        ``return_state=True`` additionally returns the decode_loop's final
        state dict (cache/logits/pos/...), e.g. to read the temporal-delta
        occupancy counters out of the cache after serving.

        ``lengths`` (a (B,) int vector) serves a RAGGED batch in one
        lockstep call: ``tokens`` is right-padded to a common width, the
        model's length-aware prefill masks each sequence's padded tail
        out of its state, and decode runs with per-sequence cache
        positions. Requires a model whose prefill accepts ``length``
        (``runtime.prefill_accepts_length``); each row's output is
        bitwise what its unpadded batch=1 decode would produce (greedy).

        ``draft`` (a ``repro.spec.DraftModel``) switches generation to
        speculative rounds: the draft proposes ``spec_k`` tokens, the
        target verifies the block in one dispatch, and both roll back to
        the accepted prefix. Greedy output is bitwise identical to
        ``draft=None``; ``return_state=True`` then also exposes per-row
        ``rounds``/``drafted``/``accepted`` counters (acceptance-rate =
        accepted / drafted).
        """
        if sampling is None:
            sampling = SamplingConfig(temperature=temperature, top_k=top_k,
                                      eos_id=eos_id)
        if rng is None:
            rng = jax.random.key(0)
        if getattr(self.model, "mesh", None) is not None:
            # packed-but-unpartitioned params would decode garbage silently
            # through the sharded step (the permuted layout is invisible in
            # the tree structure) — O(1) sharding check
            from ..dist import check_partitioned
            check_partitioned(params, self.model.mesh)
        if lengths is not None:
            if not runtime.prefill_accepts_length(self.model):
                raise TypeError(
                    f"{type(self.model).__name__}.prefill has no "
                    "length-masked path — ragged lockstep serving needs "
                    "the `length` prefill parameter")
            lengths = jnp.asarray(lengths, jnp.int32)
            with obs_trace.span("engine.prefill", batch=tokens.shape[0],
                                width=tokens.shape[1], ragged=True):
                logits, cache = self._prefill(params, tokens,
                                              max_len=self.max_len,
                                              extra=extra, length=lengths)
            pos = lengths
        else:
            with obs_trace.span("engine.prefill", batch=tokens.shape[0],
                                width=tokens.shape[1], ragged=False):
                logits, cache = self._prefill(params, tokens,
                                              max_len=self.max_len,
                                              extra=extra)
            pos = jnp.int32(tokens.shape[1])
        if draft is not None:
            from .sampling import sample_dist
            dpf = self._dprefills.setdefault(
                id(draft), jax.jit(draft.prefill,
                                   static_argnames=("max_len",)))
            if lengths is not None:
                if not runtime.prefill_accepts_length(draft.model):
                    raise TypeError(
                        f"{type(draft.model).__name__}.prefill has no "
                        "length-masked path — ragged speculative serving "
                        "needs the `length` prefill parameter")
                _, dstate = dpf(draft.params, tokens, max_len=self.max_len,
                                length=lengths)
                pos_v = lengths
            else:
                _, dstate = dpf(draft.params, tokens, max_len=self.max_len)
                pos_v = jnp.full((tokens.shape[0],), tokens.shape[1],
                                 jnp.int32)
            probs = sample_dist(logits[:, -1], sampling)
            with obs_trace.span("engine.spec_loop", steps=steps, k=spec_k):
                toks, state = self._spec_loop(steps, spec_k, sampling,
                                              draft)(params, draft.params,
                                                     cache, dstate, probs,
                                                     pos_v, rng)
            return (toks, state) if return_state else toks
        # the span covers compile+enqueue — decode itself is async; wall
        # time to tokens is the caller's block_until_ready
        with obs_trace.span("engine.decode_loop", steps=steps):
            toks, state = self._loop(steps, sampling)(params, cache, logits,
                                                      pos, rng)
        return (toks, state) if return_state else toks
