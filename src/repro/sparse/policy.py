"""SparsityPolicy → SparsityPlan: one declaration drives prune, retrain
masking, and packing for ANY model's param tree.

A policy is a list of rules, each mapping a param-path regex to a
(format, ratio) pair:

    policy = SparsityPolicy.of({"w_x$": ("row_balanced", 0.875),
                                "w_h$": ("row_balanced", 0.75)},
                               layout="out_in")
    plan = policy.compile(params)
    pruned, masks = plan.prune(params)         # masks: {path: bool mask}
    grads = plan.mask_grads(grads, masks)      # freeze pruned weights
    packed, report = plan.pack(pruned)         # packed-format param tree

Weight layout per rule (how a leaf maps to the accelerator's
(rows=output, cols=fan-in) matrix):

  "out_in"        (out, in...)   — the LSTM's W ∈ R^{4H×X} convention
  "in_out"        (in..., out)   — transformer projections (out = last dim)
  "out_trailing"  (in, out...)   — rwkv mixer weights

The two stock policies — ``lstm_policy`` (the paper's dual-ratio W_x/W_h
split) and ``transformer_policy`` (family A = feed-forward, family B =
mixer, per DESIGN.md §4) — replace the scattered ``LSTMModel.prune``/
``training.brds_masks`` surfaces; those remain as deprecation shims.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from .formats import SparseFormat, get_format
from . import backend as B

__all__ = ["Rule", "SparsityPolicy", "SparsityPlan", "lstm_policy",
           "transformer_policy", "apply_masks", "mask_grads",
           "sparsity_report"]

_LAYOUTS = ("out_in", "in_out", "out_trailing")


# ----------------------------------------------------------------- paths

def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


# ----------------------------------------------------------------- rules

@dataclasses.dataclass(frozen=True)
class Rule:
    """One policy entry: params whose path matches ``pattern`` (re.search)
    are pruned with ``format`` at ``ratio``."""

    pattern: str
    format: str = "row_balanced"
    ratio: float = 0.0
    layout: str = "in_out"
    options: Mapping[str, Any] = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        if self.layout not in _LAYOUTS:
            raise ValueError(f"layout must be one of {_LAYOUTS}, "
                             f"got {self.layout!r}")
        if not (0.0 <= self.ratio < 1.0):
            raise ValueError(f"ratio must be in [0, 1), got {self.ratio}")


@dataclasses.dataclass(frozen=True)
class _Site:
    """One matched param leaf, normalized to the (rows=out, cols=in) view."""

    path: str
    rule: Rule
    fmt: SparseFormat
    L: int | None          # stacked leading dim (scanned blocks) or None
    d_in: int
    d_out: int
    shape: tuple
    dtype: Any

    def to_oi(self, leaf) -> jnp.ndarray:
        """leaf → (L1, d_out, d_in) with rows = output units."""
        L1 = self.L or 1
        if self.rule.layout == "out_in":
            return leaf.reshape(L1, self.d_out, self.d_in)
        w3 = leaf.reshape(L1, self.d_in, self.d_out)
        return jnp.swapaxes(w3, -1, -2)

    def from_oi(self, arr3) -> jnp.ndarray:
        if self.rule.layout == "out_in":
            return arr3.reshape(self.shape)
        return jnp.swapaxes(arr3, -1, -2).reshape(self.shape)


def _resolve_dims(layout: str, core: tuple) -> tuple[int, int]:
    """→ (d_in, d_out) for the un-stacked core shape."""
    if layout == "out_in":
        return int(np.prod(core[1:])), core[0]
    if layout == "out_trailing":
        return core[0], int(np.prod(core[1:]))
    return int(np.prod(core[:-1])), core[-1]


def _is_stacked(ps: str, leaf_ndim: int) -> bool:
    return "blocks/" in ps and leaf_ndim >= 3


# ---------------------------------------------------------------- policy

@dataclasses.dataclass(frozen=True)
class SparsityPolicy:
    """One declaration of everything sparse about a deployment.

    Ordered weight rules (first match wins) select a (format, ratio) per
    param-path regex; ``backend`` picks the kernel implementation once for
    everything the policy touches; ``activation`` optionally adds the
    temporal (activation-side) rule — a
    :class:`repro.sparse.temporal.DeltaGateConfig` that serving threads
    into the model's decode cache (Spartus-style delta skipping composed
    with the packed weight formats).

    Parameters
    ----------
    rules : tuple of Rule
        Weight rules, matched in order against each param path.
    backend : {"auto", "pallas", "ref"}
        Kernel backend for every matvec the compiled plan dispatches.
    activation : DeltaGateConfig, optional
        Temporal-delta activation rule; None (default) means dense
        activations.
    quant : QuantConfig, optional
        Fixed-point inference rule (``repro.quant``): row-balanced sites
        pack to quantized codes + per-row scales and serving runs the q8
        kernels; None (default) keeps float packed values.

    Examples
    --------
    >>> p = SparsityPolicy.of({r"w_x$": ("row_balanced", 0.875),
    ...                        r"w_h$": ("row_balanced", 0.75)},
    ...                       layout="out_in")
    >>> p.match("layers/0/w_x").ratio
    0.875
    >>> p.match("layers/0/b") is None
    True
    """

    rules: tuple
    backend: str = "auto"
    activation: Any = None
    quant: Any = None

    def __post_init__(self):
        if self.backend not in B.BACKENDS:
            raise ValueError(f"backend must be one of {B.BACKENDS}, "
                             f"got {self.backend!r}")

    @classmethod
    def of(cls, mapping: Mapping[str, Any], *, backend: str = "auto",
           layout: str = "in_out", activation: Any = None,
           quant: Any = None) -> "SparsityPolicy":
        """Build a policy from a ``{pattern: spec}`` mapping.

        Parameters
        ----------
        mapping : Mapping[str, float | tuple]
            ``{pattern: ratio | (format, ratio) | (format, ratio,
            options)}``; bare floats mean ``row_balanced``.
        backend : {"auto", "pallas", "ref"}
            Kernel backend for the compiled plan.
        layout : {"out_in", "in_out", "out_trailing"}
            Weight layout shared by every rule built here.
        activation : DeltaGateConfig, optional
            Temporal-delta activation rule.
        quant : QuantConfig, optional
            Fixed-point inference rule (quantized packing + q8 kernels).

        Returns
        -------
        SparsityPolicy
        """
        rules = []
        for pat, spec in mapping.items():
            if isinstance(spec, (int, float)):
                rules.append(Rule(pat, "row_balanced", float(spec), layout))
            else:
                fmt, ratio, *rest = spec
                opts = rest[0] if rest else {}
                rules.append(Rule(pat, fmt, float(ratio), layout,
                                  dict(opts)))
        return cls(rules=tuple(rules), backend=backend,
                   activation=activation, quant=quant)

    def with_backend(self, backend: str) -> "SparsityPolicy":
        """Copy of this policy with a different kernel backend."""
        return dataclasses.replace(self, backend=backend)

    def with_activation(self, activation) -> "SparsityPolicy":
        """Copy of this policy with a temporal-delta activation rule
        (a ``DeltaGateConfig``, or None to disable)."""
        return dataclasses.replace(self, activation=activation)

    def with_quant(self, quant) -> "SparsityPolicy":
        """Copy of this policy with a fixed-point inference rule
        (a ``repro.quant.QuantConfig``, or None to disable)."""
        return dataclasses.replace(self, quant=quant)

    def match(self, path_str: str) -> Rule | None:
        """First rule whose pattern ``re.search``-matches ``path_str``."""
        for r in self.rules:
            if re.search(r.pattern, path_str):
                return r
        return None

    def compile(self, params) -> "SparsityPlan":
        """Walk the param tree once, resolving every matched leaf to a
        (format, layout, dims) site. ``params`` may be concrete arrays or
        ShapeDtypeStructs — only shapes/dtypes are read."""
        sites = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
            if not hasattr(leaf, "ndim") or leaf.ndim < 2:
                continue
            ps = _path_str(path)
            rule = self.match(ps)
            if rule is None or rule.ratio <= 0.0:
                continue
            stacked = _is_stacked(ps, leaf.ndim)
            core = leaf.shape[1:] if stacked else leaf.shape
            d_in, d_out = _resolve_dims(rule.layout, core)
            sites[ps] = _Site(
                path=ps, rule=rule, fmt=get_format(rule.format),
                L=leaf.shape[0] if stacked else None,
                d_in=d_in, d_out=d_out, shape=tuple(leaf.shape),
                dtype=leaf.dtype)
        return SparsityPlan(policy=self, sites=sites)


# ------------------------------------------------------------------ plan

# formats whose mask() accepts leading batch dims
_BATCHED_MASK_FORMATS = {"row_balanced", "row_balanced_q8"}


class SparsityPlan:
    """A policy compiled against one param tree.

    All methods are pure and jit-compatible on the array side; site
    resolution (shape/layout/format per matched leaf) happened at compile.
    The plan is the deployment handle: ``prune`` → ``mask_grads`` (retrain)
    → ``pack`` (serve), plus ``matvec`` kernel dispatch per site, with the
    policy's backend and activation rule riding along.

    Attributes
    ----------
    policy : SparsityPolicy
        The declaration this plan was compiled from.
    sites : dict
        ``{path: _Site}`` for every matched param leaf.
    """

    def __init__(self, policy: SparsityPolicy, sites: dict):
        self.policy = policy
        self.sites = sites

    @property
    def backend(self) -> str:
        """The policy's kernel backend ("auto" | "pallas" | "ref")."""
        return self.policy.backend

    @property
    def activation(self):
        """The policy's temporal-delta activation rule
        (``DeltaGateConfig`` or None)."""
        return self.policy.activation

    @property
    def quant(self):
        """The policy's fixed-point inference rule
        (``repro.quant.QuantConfig`` or None)."""
        return self.policy.quant

    def __repr__(self):
        return (f"SparsityPlan(backend={self.backend!r}, "
                f"sites={len(self.sites)})")

    # -- masks ----------------------------------------------------------
    def _site_mask(self, site: _Site, leaf) -> jnp.ndarray:
        w_oi = site.to_oi(leaf)                     # (L1, out, in)
        r, opts = site.rule.ratio, site.rule.options
        if site.fmt.name in _BATCHED_MASK_FORMATS:
            m = site.fmt.mask(w_oi, r, **opts)
        else:
            m = jnp.stack([site.fmt.mask(w_oi[i], r, **opts)
                           for i in range(w_oi.shape[0])])
        return site.from_oi(m)

    def masks(self, params) -> dict:
        """{path: bool mask} for every matched leaf (True = keep)."""
        out = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
            ps = _path_str(path)
            if ps in self.sites:
                out[ps] = self._site_mask(self.sites[ps], leaf)
        return out

    # -- prune / retrain ------------------------------------------------
    def prune(self, params):
        """→ (pruned_params, masks)."""
        masks = self.masks(params)
        return apply_masks(params, masks), masks

    def apply_masks(self, params, masks):
        return apply_masks(params, masks)

    def mask_grads(self, grads, masks):
        return mask_grads(grads, masks)

    # -- pack -----------------------------------------------------------
    def pack(self, params, masks: dict | None = None,
             abstract: bool = False, dense=()):
        """Replace every matched leaf with its packed-format rep.

        masks=None recomputes masks from the rule ratios (correct both for
        raw weights and already-pruned ones — magnitude top-k re-selects
        the survivors). Pass the masks from ``prune`` to pack an exact
        pattern. abstract=True builds ShapeDtypeStruct stand-ins (dry-run).
        A policy ``quant`` rule quantizes every row-balanced site on the
        way out (integer codes + per-row scales; the byte accounting
        reflects the narrowed values). Paths in ``dense`` are served as the
        leaf they are (the pruned dense matrix): no packed copy is built,
        and their bytes count dense. Returns (packed_params, report)."""
        qscheme = None
        if self.quant is not None:
            from ..quant import (abstract_quantize_packed, packed_bytes_q,
                                 parse_scheme, quantize_packed)
            from ..core.packing import RowBalancedSparse
            qscheme = parse_scheme(getattr(self.quant, "scheme", self.quant))
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        out_leaves = []
        dense_bytes = packed_bytes = 0
        for path, leaf in flat:
            ps = _path_str(path)
            site = None if ps in dense else self.sites.get(ps)
            if site is None:
                out_leaves.append(leaf)
                if hasattr(leaf, "dtype"):
                    nbytes = leaf.size * leaf.dtype.itemsize
                    dense_bytes += nbytes
                    packed_bytes += nbytes
                continue
            L1 = site.L or 1
            r, opts = site.rule.ratio, site.rule.options
            dense_bytes += leaf.size * leaf.dtype.itemsize
            if qscheme is not None and site.fmt.name == "row_balanced":
                packed_bytes += L1 * packed_bytes_q(site.d_out, site.d_in,
                                                    r, qscheme)
            else:
                packed_bytes += L1 * site.fmt.packed_bytes(
                    site.d_out, site.d_in, r, leaf.dtype, **opts)
            if abstract:
                rep = site.fmt.abstract_pack(site.d_out, site.d_in, r,
                                             leaf.dtype, **opts)
                if qscheme is not None and isinstance(rep, RowBalancedSparse):
                    rep = abstract_quantize_packed(rep, qscheme)
                if site.L:
                    rep = site.fmt.abstract_stack(rep, site.L)
            else:
                w_oi = site.to_oi(leaf)
                if masks is not None and ps in masks:
                    m_oi = site.to_oi(masks[ps])
                else:
                    m_oi = site.to_oi(self._site_mask(site, leaf))
                packs = [site.fmt.pack(w_oi[i], m_oi[i], **opts)
                         for i in range(L1)]
                rep = site.fmt.stack(packs) if site.L else packs[0]
                if qscheme is not None and isinstance(rep, RowBalancedSparse):
                    rep = quantize_packed(rep, qscheme)
            out_leaves.append(rep)
        packed = jax.tree_util.tree_unflatten(treedef, out_leaves)
        return packed, dict(dense_bytes=dense_bytes,
                            packed_bytes=packed_bytes,
                            ratio=packed_bytes / max(dense_bytes, 1))

    def gather_visit_shares(self, packed) -> dict:
        """{path: share of source chunks the packed gather visits} for each
        row-balanced leaf of a concrete ``pack`` output — a property of the
        packed weights, computed on the host once
        (``kernels.rb_spmv.gather_visit_share``)."""
        from ..kernels.rb_spmv import gather_visit_share
        flat = jax.tree_util.tree_flatten_with_path(
            packed, is_leaf=lambda v: hasattr(v, "deltas"))[0]
        return {_path_str(path): gather_visit_share(leaf.deltas, leaf.ncols)
                for path, leaf in flat if hasattr(leaf, "deltas")}

    # -- kernel dispatch -------------------------------------------------
    def matvec(self, path: str, packed, x):
        """Dispatch one packed matvec through the site's format with the
        plan's backend."""
        site = self.sites[path]
        return site.fmt.matvec(packed, x, backend=self.backend)

    def summary(self, masks: dict) -> dict:
        return sparsity_report(masks)


# -------------------------------------------------------- tree utilities

def _map_masked(tree, masks: dict, fn):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        ps = _path_str(path)
        out.append(fn(leaf, masks[ps]) if ps in masks else leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


def _zero_pruned(leaf, mask):
    return jnp.where(mask, leaf, jnp.zeros_like(leaf))


def apply_masks(params, masks: dict):
    """Zero pruned weights. masks: {path: bool mask}."""
    return _map_masked(params, masks, _zero_pruned)


def mask_grads(grads, masks: dict):
    """Freeze pruned weights by zeroing their gradients."""
    return _map_masked(grads, masks, _zero_pruned)


def sparsity_report(masks: dict) -> dict:
    total = pruned = 0
    for m in masks.values():
        total += m.size
        pruned += int(m.size - jnp.sum(m))
    return {"prunable_params": total, "pruned": pruned,
            "sparsity": pruned / max(total, 1)}


# --------------------------------------------------------- stock policies

def lstm_policy(spar_x: float, spar_h: float, *, backend: str = "auto",
                fmt: str = "row_balanced", delta=None,
                quant=None) -> SparsityPolicy:
    """The paper's dual-ratio split: input weights W_x at ``spar_x``,
    recurrent weights W_h at ``spar_h`` (both row-balanced by default).

    Parameters
    ----------
    spar_x, spar_h : float
        Sparsity ratios for the input / recurrent weight families.
    backend : {"auto", "pallas", "ref"}
        Kernel backend configured on the policy.
    fmt : str
        Registered format name for both families.
    delta : DeltaGateConfig, optional
        Temporal-delta activation rule (Spartus-style skipping) to carry
        alongside the weight rules — serving wires it into the LSTM's
        decode cache (see ``repro.sparse.temporal``).
    quant : QuantConfig, optional
        Fixed-point inference rule (``repro.quant``): pack emits
        quantized codes + per-row scales and decode runs the q8 kernels
        — composes multiplicatively with both weight and delta sparsity.
    """
    return SparsityPolicy.of(
        {r"w_x$": (fmt, spar_x), r"w_h$": (fmt, spar_h)},
        backend=backend, layout="out_in", activation=delta, quant=quant)


# (pattern, family, layout) — family A pruned at spar_a, B at spar_b.
# Rows are output units: q/k/v (d, heads, head_dim) fan in over d alone.
_TRANSFORMER_FAMILIES = (
    (r"(mlp|moe)/w_(gate|up|down)$", "a", "in_out"),
    (r"rwkv/w_cm[12]$", "a", "in_out"),
    (r"(attn|xattn)/w[qkv]$", "b", "out_trailing"),
    (r"(attn|xattn)/wo$", "b", "in_out"),
    (r"mamba/w_(inproj|outproj)$", "b", "in_out"),
    (r"rec/(w_in_gelu|w_in_rec|w_gate_a|w_gate_x|w_out)$", "b", "in_out"),
    (r"rwkv/w_[rkvgw]$", "b", "out_trailing"),
    (r"rwkv/w_out$", "b", "in_out"),
)


def transformer_policy(spar_a: float, spar_b: float, *,
                       backend: str = "auto",
                       fmt: str = "row_balanced") -> SparsityPolicy:
    """Dual-ratio families for the transformer zoo (DESIGN.md §4):
    family A (feed-forward, pruned harder) at ``spar_a``; family B
    (attention / recurrence mixers) at ``spar_b``."""
    rules = tuple(
        Rule(pat, fmt, spar_a if fam == "a" else spar_b, layout)
        for pat, fam, layout in _TRANSFORMER_FAMILIES)
    return SparsityPolicy(rules=rules, backend=backend)


def classify(path_str: str) -> str | None:
    """Family of a transformer param path ('a' | 'b' | None)."""
    for pat, fam, _ in _TRANSFORMER_FAMILIES:
        if re.search(pat, path_str):
            return fam
    return None
