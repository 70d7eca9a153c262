"""Mixture-of-Experts layer: top-k router + capacity-bounded expert FFNs.

Dispatch uses a *blocked* one-hot capacity formulation: tokens are processed
in blocks of ``cfg.moe_block``; per block each expert takes at most
C = ceil(k * block / E * capacity_factor) tokens.  The dispatch tensor is
(block, E, C) — bounded memory regardless of sequence length — and the
expert matmuls are dense einsums over the (E, C, d) dispatched activations,
which XLA shards cleanly with experts on the ``experts`` mesh axis (the
token -> expert exchange lowers to all-to-all/all-gather on that axis).

Overflowed tokens are dropped (standard capacity-based MoE); the router
keeps an auxiliary load-balancing loss (Switch-style).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .common import ModelConfig, ParamDef, Rules, shard
from .layers import _act

def moe_defs(cfg: ModelConfig, lead: Tuple[int, ...] = ()) -> Dict:
    la = ("layers",) * len(lead)
    d, f, e = cfg.d_model, cfg.expert_ff, cfg.n_experts
    fs = cfg.n_shared_experts * f
    # expert weights shard experts over the FSDP ('data') axis and the
    # expert FF dim over the tensor axis; the embed dim stays unsharded
    # (it cannot reuse 'data' — one mesh axis per spec position)
    out = {
        "router": ParamDef(lead + (d, e), la + ("embed", None)),
        "wi": ParamDef(lead + (e, d, f), la + ("experts", None, "expert_ff")),
        "wg": ParamDef(lead + (e, d, f), la + ("experts", None, "expert_ff")),
        "wo": ParamDef(lead + (e, f, d), la + ("experts", "expert_ff", None)),
    }
    if fs:
        out["shared_wi"] = ParamDef(lead + (d, fs), la + ("embed", "ff"))
        out["shared_wg"] = ParamDef(lead + (d, fs), la + ("embed", "ff"))
        out["shared_wo"] = ParamDef(lead + (fs, d), la + ("ff", "embed"))
    return out


def _capacity(cfg: ModelConfig) -> int:
    c = int(cfg.top_k * cfg.moe_block / cfg.n_experts * cfg.moe_capacity)
    return max(4, -(-c // 4) * 4)


def apply_moe(cfg: ModelConfig, p: Dict, x: jax.Array,
              rules: Optional[Rules]) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (y, aux_loss)."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    blk = min(cfg.moe_block, b * s)
    cap = _capacity(cfg)
    tokens = x.reshape(-1, d)
    n = tokens.shape[0]
    pad = (-n) % blk
    if pad:
        tokens = jnp.pad(tokens, ((0, pad), (0, 0)))
    nblk = tokens.shape[0] // blk
    tb = tokens.reshape(nblk, blk, d)

    router = p["router"]

    def block_fn(xt: jax.Array) -> Tuple[jax.Array, jax.Array]:
        # xt: (blk, d)
        logits = (xt @ router).astype(jnp.float32)            # (blk, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, idx = jax.lax.top_k(probs, k)              # (blk, k)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
        # position of each (token, choice) within its expert queue
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)      # (blk, k, E)
        flat = onehot.reshape(blk * k, e)
        ranks = jnp.cumsum(flat, axis=0) - flat               # (blk*k, E)
        rank = (ranks * flat).sum(-1).reshape(blk, k)
        keep = rank < cap
        if cfg.moe_dispatch == "scatter":
            # gather/scatter dispatch: ~zero FLOPs, O(tokens*d) traffic —
            # the beyond-paper optimization over the GEMM-dispatch baseline
            pos = idx * cap + rank                            # (blk, k)
            pos_safe = jnp.where(keep, pos, e * cap)          # overflow slot
            xe_flat = jnp.zeros((e * cap + 1, xt.shape[-1]), xt.dtype)
            xe_flat = xe_flat.at[pos_safe.reshape(-1)].add(
                jnp.repeat(xt, k, axis=0))
            xe = xe_flat[:e * cap].reshape(e, cap, -1)        # (E, C, d)
            xe = shard(xe, rules, "experts", None, None)
            h = _act(cfg, jnp.einsum("ecd,edf->ecf", xe, p["wg"])) \
                * jnp.einsum("ecd,edf->ecf", xe, p["wi"])
            h = shard(h, rules, "experts", None, "act_ff")
            ye = jnp.einsum("ecf,efd->ecd", h, p["wo"])       # (E, C, d)
            ye_flat = jnp.concatenate(
                [ye.reshape(e * cap, -1),
                 jnp.zeros((1, xt.shape[-1]), ye.dtype)], axis=0)
            taken = ye_flat[pos_safe]                         # (blk, k, d)
            y = jnp.sum(taken * (gate_vals[..., None] * keep[..., None]
                                 ).astype(taken.dtype), axis=1)
        else:
            # one-hot GEMM dispatch (baseline; maps onto the paper's
            # systolic-GEMM cost model but pays O(blk * E * C * d) FLOPs)
            oh_e = jax.nn.one_hot(idx, e, dtype=xt.dtype) * keep[..., None]
            oh_c = jax.nn.one_hot(jnp.where(keep, rank, cap), cap + 1,
                                  dtype=xt.dtype)[..., :cap]  # (blk, k, C)
            disp = jnp.einsum("bke,bkc->bec", oh_e, oh_c)     # (blk, E, C)
            xe = jnp.einsum("bec,bd->ecd", disp, xt)          # (E, C, d)
            xe = shard(xe, rules, "experts", None, None)
            h = _act(cfg, jnp.einsum("ecd,edf->ecf", xe, p["wg"])) \
                * jnp.einsum("ecd,edf->ecf", xe, p["wi"])
            h = shard(h, rules, "experts", None, "act_ff")
            ye = jnp.einsum("ecf,efd->ecd", h, p["wo"])       # (E, C, d)
            combine = jnp.einsum("bke,bkc->bec",
                                 oh_e * gate_vals[..., None].astype(xt.dtype),
                                 oh_c)
            y = jnp.einsum("bec,ecd->bd", combine, ye)
        # Switch aux loss: E * sum_e (frac_tokens_e * mean_prob_e)
        frac = onehot.sum(1).mean(0).astype(jnp.float32)      # (E,)
        aux = e * jnp.sum(frac * probs.mean(0))
        return y, aux

    ys, auxs = jax.lax.map(block_fn, tb)
    y = ys.reshape(-1, d)[:n].reshape(b, s, d)
    if cfg.n_shared_experts:
        h = _act(cfg, x @ p["shared_wg"]) * (x @ p["shared_wi"])
        y = y + h @ p["shared_wo"]
    return y, auxs.mean()
