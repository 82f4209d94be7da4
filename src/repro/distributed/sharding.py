"""Logical-axis sharding rules -> mesh PartitionSpecs (DP/FSDP/TP/SP/EP).

Params carry *logical* axis tuples (see models/layers.py); this module
resolves them against a mesh:

    batch     -> ('pod','data')  (pod axis is pure DP when present)
    vocab/ff/heads/experts/d_inner -> 'model'   (tensor/expert parallel)
    residual  -> 'data' iff FSDP (2-D sharded params for the giant archs)
    seq_sp    -> 'model' iff sequence-parallel residual stream
    kv_heads  -> 'model' only when the arch's KV-head projection divides tp
    heads     -> 'model' only when H divides tp (else replicated attention)
    kv_seq    -> 'model' when the decode cache is sequence-sharded

Divisibility is decided per-arch at Sharder construction, so every
(arch x mesh) combination lowers without uneven-sharding surprises.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.distributed import make_mesh  # noqa: F401  (re-exported)
from repro.models.config import ModelConfig


class Sharder:
    """Resolves logical axis names for one (cfg, mesh) pair."""

    def __init__(self, cfg: ModelConfig, mesh: Optional[Mesh]):
        self.cfg = cfg
        self.mesh = mesh
        if mesh is None:
            self.tp = 1
            self.tp_axis = None
            self.dp_axes = ()
            self.rules = {}
            return
        names = mesh.axis_names
        self.tp = mesh.shape["model"] if "model" in names else 1
        self.tp_axis = "model" if "model" in names else None
        self.dp_axes = tuple(a for a in ("pod", "data") if a in names)

        n_heads = cfg.n_heads_padded or cfg.n_heads
        n_kv = cfg.n_kv_heads_padded or cfg.n_kv_heads
        heads_ok = n_heads > 0 and n_heads % self.tp == 0
        kv_ok = n_kv > 0 and n_kv % self.tp == 0
        ff_ok = cfg.d_ff > 0 and cfg.d_ff % self.tp == 0
        ffe_ok = cfg.d_ff_expert > 0 and cfg.d_ff_expert % self.tp == 0
        exp_ok = cfg.n_experts > 0 and cfg.n_experts % self.tp == 0
        din_ok = cfg.d_inner > 0 and cfg.d_inner % self.tp == 0
        fsdp = cfg.fsdp and "data" in names and cfg.d_model % mesh.shape["data"] == 0

        self.rules = {
            "layers": None,
            "batch": self.dp_axes or None,
            "vocab": "model",
            "residual": "data" if fsdp else None,
            "ff": "model" if ff_ok else None,
            "ff_expert": "model" if ffe_ok else None,
            "heads": "model" if heads_ok else None,
            "kv_heads": "model" if kv_ok else None,
            "experts": "model" if exp_ok else None,
            "d_inner": "model" if din_ok else None,
            "seq_sp": "model" if cfg.seq_shard else None,
            "kv_seq": None if kv_ok else "model",
            "expert_local": None,  # inside-shard_map expert dim
        }
        # vocab divisibility (padded vocab is a multiple of 128; 128 % tp == 0
        # for tp in {1,2,4,8,16,...,128})
        if cfg.vocab_padded % self.tp != 0:
            self.rules["vocab"] = None

    # -- params ------------------------------------------------------------
    def spec(self, logical: Tuple) -> P:
        if self.mesh is None:
            return P()
        return P(*(self.rules.get(ax) if ax is not None else None
                   for ax in logical))

    def param_shardings(self, spec_tree):
        """Map a logical-spec tree to NamedSharding tree."""
        if self.mesh is None:
            return None
        return jax.tree.map(
            lambda s: NamedSharding(self.mesh, self.spec(s)), spec_tree,
            is_leaf=lambda x: isinstance(x, tuple))

    def opt_state_spec(self, logical: Tuple) -> P:
        """ZeRO-1: optimizer moments additionally shard 'residual' over
        'data' even when the params themselves don't (fsdp off)."""
        if self.mesh is None:
            return P()
        axes = []
        used = set(a for a in (self.rules.get(ax) for ax in logical) if a)
        for ax in logical:
            r = self.rules.get(ax) if ax is not None else None
            if r is None and ax == "residual" and "data" not in used \
                    and "data" in self.mesh.axis_names:
                axes.append("data")
                used.add("data")
            else:
                axes.append(r)
        return P(*axes)

    def _axis_size(self, axes) -> int:
        if axes is None:
            return 1
        if isinstance(axes, str):
            axes = (axes,)
        return int(np.prod([self.mesh.shape[a] for a in axes]))

    # -- activations ---------------------------------------------------------
    def act(self, x, *logical):
        """with_sharding_constraint, guarded: a dim is only sharded when its
        size divides the axis size (e.g. seq=1 at decode never shards)."""
        if self.mesh is None:
            return x
        entries = []
        for dim, ax in enumerate(logical):
            r = self.rules.get(ax) if ax is not None else None
            if r is not None and x.shape[dim] % self._axis_size(r) != 0:
                r = None
            entries.append(r)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*entries)))

    def pspec(self, *logical) -> P:
        if self.mesh is None:
            return P()
        return P(*(self.rules.get(ax) if ax is not None else None
                   for ax in logical))


NULL = object()
