"""Mixture-of-Experts with expert parallelism (EP) via explicit all-to-all.

Design (scales to 1000+ nodes):

* Experts are sharded over the 'model' mesh axis; tokens are sharded over
  ('pod','data') and — under sequence parallelism — over 'model' too.
* The layer runs under `shard_map`: each shard routes its *local* tokens
  (top-k over the full expert set, router weights replicated), ranks them
  into per-expert capacity slots (cumsum-of-one-hot, deterministic), packs a
  (tp, E_local, C, D) send buffer, and exchanges it with one
  `jax.lax.all_to_all` over 'model'. Expert FFNs run on local experts only;
  a second all-to-all returns results; combine is local. Total comm:
  2 x all-to-all of (k x tokens x D x capacity_factor) bytes — the classic
  DeepSpeed-MoE/GShard schedule, with zero all-reduces.
* Static shapes everywhere: capacity slots are fixed; overflow tokens are
  dropped via a sentinel row (the paper's MAX-sentinel trick reappears —
  invalid slots index a zero row instead of being branched around).

LP-capacity routing (the paper's technique inside the framework): instead of
a uniform per-expert capacity cutoff, a batch of small LPs (one per shard
group) reallocates the slot budget across experts by demand — solved
on-device by repro.core's batched simplex. Static buffer shapes are kept;
only the cutoff mask changes.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig
from .layers import dense_init

def moe_init(key, cfg: ModelConfig):
    D, E, Fe = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    dtype = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 8)
    p, s = {}, {}
    p["router"], s["router"] = dense_init(ks[0], D, E, dtype, ("residual", None))
    scale = 1.0 / np.sqrt(D)
    p["w_gate"] = (jax.random.normal(ks[1], (E, D, Fe), jnp.float32) * scale).astype(dtype)
    p["w_up"] = (jax.random.normal(ks[2], (E, D, Fe), jnp.float32) * scale).astype(dtype)
    p["w_down"] = (jax.random.normal(ks[3], (E, Fe, D), jnp.float32) / np.sqrt(Fe)).astype(dtype)
    s["w_gate"] = ("experts", "residual", None)
    s["w_up"] = ("experts", "residual", None)
    s["w_down"] = ("experts", None, "residual")
    if cfg.n_shared_experts:
        Fs = cfg.n_shared_experts * Fe
        p["ws_gate"], s["ws_gate"] = dense_init(ks[4], D, Fs, dtype, ("residual", "ff_expert"))
        p["ws_up"], s["ws_up"] = dense_init(ks[5], D, Fs, dtype, ("residual", "ff_expert"))
        p["ws_down"], s["ws_down"] = dense_init(ks[6], Fs, D, dtype, ("ff_expert", "residual"))
    return p, s


def _capacity(n_tok: int, k: int, E: int, cf: float) -> int:
    c = int(np.ceil(n_tok * k / E * cf))
    return max(4, (c + 3) // 4 * 4)


def _moe_local(x, p, cfg: ModelConfig, *, tp: int, tp_axis: Optional[str]):
    """Per-shard MoE body. x: (N, D) local tokens; p holds LOCAL expert slabs
    (El, D, Fe). Runs identically for tp=1 (no mesh) and under shard_map."""
    N, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    El = p["w_gate"].shape[0]
    Cl = _capacity(N, K, E, cfg.capacity_factor)

    # --- routing (f32) ------------------------------------------------------
    logits = (x @ p["router"]).astype(jnp.float32)          # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, K)                  # (N, K)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    flat_e = top_e.reshape(-1)                               # (N*K,)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    ranks = jnp.cumsum(onehot, axis=0) - onehot              # rank within expert
    slot = jnp.take_along_axis(ranks, flat_e[:, None], 1)[:, 0]

    # --- capacity cutoff: uniform, or LP-reallocated (paper technique) ------
    if cfg.lp_capacity:
        from repro.core.lp_router import expert_capacity_lp
        demand = probs.sum(0)[None, :] * K                  # (1, E) soft load
        caps = expert_capacity_lp(demand, total_slots=float(N * K),
                                  c_max=float(Cl))[0]       # (E,)
        cap_of = jnp.take(caps, flat_e)
        keep = slot < cap_of
    else:
        keep = slot < Cl

    # --- dispatch: pack (tp, El, Cl, D) send buffer, sentinel-drop overflow -
    sent = tp * El * Cl
    dest = jnp.where(keep, flat_e * Cl + slot, sent)
    xk = jnp.repeat(x, K, axis=0)                            # (N*K, D)
    buf = jnp.zeros((sent + 1, D), x.dtype).at[dest].add(
        xk * keep[:, None].astype(x.dtype))
    buf = buf[:sent].reshape(tp, El * Cl, D)

    if tp_axis is not None and tp > 1:
        buf = jax.lax.all_to_all(buf, tp_axis, split_axis=0, concat_axis=0,
                                 tiled=False)
    # buf: (tp, El*Cl, D) — rows grouped by source shard for MY experts
    h_in = buf.reshape(tp, El, Cl, D).transpose(1, 0, 2, 3).reshape(El, tp * Cl, D)

    # --- expert FFN (SwiGLU) on local experts --------------------------------
    g = jnp.einsum("ecd,edf->ecf", h_in, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", h_in, p["w_up"])
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, p["w_down"])

    # --- return path ----------------------------------------------------------
    y = y.reshape(El, tp, Cl, D).transpose(1, 0, 2, 3).reshape(tp, El * Cl, D)
    if tp_axis is not None and tp > 1:
        y = jax.lax.all_to_all(y, tp_axis, split_axis=0, concat_axis=0,
                               tiled=False)
    y_flat = jnp.concatenate([y.reshape(sent, D),
                              jnp.zeros((1, D), y.dtype)], axis=0)
    z = jnp.take(y_flat, dest, axis=0)                       # (N*K, D)
    w = (top_w.reshape(-1) * keep).astype(x.dtype)
    out = (z * w[:, None]).reshape(N, K, D).sum(axis=1)
    return out


def moe_apply(p, x, cfg: ModelConfig, shd=None):
    """x: (B, S, D). Routed experts via shard_map EP; shared experts as a
    plain TP dense MLP outside."""
    B, S, D = x.shape
    E = cfg.n_experts

    routed_p = {k: p[k] for k in ("router", "w_gate", "w_up", "w_down")}
    if shd is not None and shd.mesh is not None and shd.tp_axis is not None \
            and E % shd.tp == 0 and shd.tp > 1:
        mesh, tp, tp_axis = shd.mesh, shd.tp, shd.tp_axis
        dp = shd.dp_axes or None
        if dp is not None and B % shd._axis_size(dp) != 0:
            dp = None
        seq_ax = shd.rules.get("seq_sp")
        if seq_ax is not None and S % shd._axis_size(seq_ax) != 0:
            seq_ax = None
        x_spec = jax.sharding.PartitionSpec(dp, seq_ax, None)
        w_spec = {
            "router": jax.sharding.PartitionSpec(None, None),
            "w_gate": jax.sharding.PartitionSpec("model", None, None),
            "w_up": jax.sharding.PartitionSpec("model", None, None),
            "w_down": jax.sharding.PartitionSpec("model", None, None),
        }

        def body(xl, pl):
            Bl, Sl, Dl = xl.shape
            out = _moe_local(xl.reshape(Bl * Sl, Dl), pl, cfg, tp=tp,
                             tp_axis=tp_axis)
            return out.reshape(Bl, Sl, Dl)

        out = jax.shard_map(
            body, mesh=mesh,
            in_specs=(x_spec, w_spec),
            out_specs=x_spec,
            check_vma=False,
        )(x, routed_p)
    else:
        out = _moe_local(x.reshape(B * S, D), routed_p, cfg, tp=1,
                         tp_axis=None).reshape(B, S, D)

    if cfg.n_shared_experts:
        h = jax.nn.silu(x @ p["ws_gate"]) * (x @ p["ws_up"])
        if shd is not None:
            h = shd.act(h, "batch", None, "ff_expert")
        out = out + h @ p["ws_down"]
    return out
