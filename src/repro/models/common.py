"""Model-stack foundations: config, parameter declaration, sharding rules.

Parameters are declared once as ``ParamDef`` trees (shape + logical axes +
initializer); the same tree materializes to
  * initialized arrays           (``init_params``)
  * ``jax.ShapeDtypeStruct``s    (``abstract_params`` — dry-run)
  * ``PartitionSpec``s           (``param_specs`` — pjit in/out shardings)

Logical axis names are mapped to mesh axes through a ``Rules`` dict
(MaxText-style).  The production default is FSDP over ``data`` x tensor
parallelism over ``model``; decode/long-context cells override activation
rules (e.g. KV-cache sequence over ``data`` when batch < mesh data size).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0              # 0 -> d_model // n_heads
    # multi-head latent attention (DeepSeek-V2); kv_lora_rank 0 -> off
    kv_lora_rank: int = 0          # width of the compressed kv latent
    qk_nope_head_dim: int = 0      # per-head q/k width without rotary
    qk_rope_head_dim: int = 0      # per-head q width with rotary; k's is
                                   # one head shared by all heads
    v_head_dim: int = 0
    # norms / activations
    norm_type: str = "rmsnorm"     # rmsnorm | layernorm
    act: str = "silu"              # silu | gelu
    qk_norm: bool = False
    # rotary
    rope_theta: float = 1e4
    rope_fraction: float = 1.0     # partial rotary (stablelm: 0.25)
    # attention pattern
    window: int = 0                # sliding-window size (0 = full attention)
    # per-layer pattern of window usage: 'local'/'global'; empty -> all global
    attn_pattern: Tuple[str, ...] = ()
    causal: bool = True
    # MoE
    n_experts: int = 0
    top_k: int = 0
    moe_every: int = 1             # MoE on layers where (i % moe_every)==moe_offset
    moe_offset: int = 0
    moe_d_ff: int = 0              # routed-expert width (0 -> d_ff)
    n_shared_experts: int = 0      # shared experts, run as one MLP of
                                   # n_shared_experts * expert width
    moe_block: int = 1024          # token block size for dispatch
    moe_capacity: float = 1.25     # expert capacity factor (tokens dropped
                                   # beyond cap — standard capacity MoE)
    moe_dispatch: str = "onehot"   # onehot (GEMM dispatch) | scatter
    # mixer pattern: repeating tuple over layers; entries in
    # {'attn','mamba2','rglru'}
    block_pattern: Tuple[str, ...] = ("attn",)
    # SSM (mamba2)
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    conv_width: int = 4
    # RG-LRU
    rnn_width: int = 0             # 0 -> d_model
    # encoder-decoder
    encoder_layers: int = 0
    encoder_seq: int = 0           # fixed encoder length (whisper: 1500)
    learned_pos: int = 0           # learned position table size (0 = rope)
    # vlm stub
    n_patches: int = 0
    # misc
    tie_embeddings: bool = True
    dtype: Any = jnp.bfloat16
    remat: bool = True
    attn_block: int = 1024         # kv block for chunked attention
    dense_attn_max_seq: int = 4096  # use dense attention at/below this length
    ce_chunk: int = 0              # seq-chunked cross-entropy (0 = off):
                                   # only (B, chunk, V) logits materialize
    cache_dtype: Any = None        # KV-cache storage dtype (None = dtype);
                                   # jnp.int8 enables quantized KV serving
    kv_quant_scale: float = 1 / 32.  # symmetric int8 KV quantization scale
    remat_policy: str = "full"     # full | save_dots (selective remat)

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def expert_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def pattern(self) -> Tuple[str, ...]:
        return self.block_pattern or ("attn",)

    def layer_kinds(self) -> Tuple[str, ...]:
        pat = self.pattern
        return tuple(pat[i % len(pat)] for i in range(self.n_layers))

    def is_moe_layer(self, i: int) -> bool:
        return (self.n_experts > 0
                and (i % self.moe_every) == self.moe_offset)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Sharding rules
# ---------------------------------------------------------------------------

Rules = Dict[str, Any]   # logical axis -> mesh axis (str | tuple | None)

# Production default: FSDP('data') x TP('model'); batch over data (+pod).
PROD_RULES: Rules = {
    # parameter axes
    "embed": "data",          # FSDP axis of 2D weights
    "ff": "model",
    "heads": "model",
    "kv_heads": "model",
    "qkv": "model",
    "vocab": "model",
    "experts": "data",
    "expert_ff": "model",
    "rnn": "model",
    "ssm_heads": "model",
    "conv": None,
    "layers": None,
    "pos": None,
    # activation axes
    "batch": "data",
    "seq": None,
    # residual stream between layers (the remat-saved carry): sequence-
    # sharded over the tensor axis (Megatron-style sequence parallelism) —
    # XLA inserts the gather/scatter at the norm <-> qkv/ff boundaries
    "seq_resid": "model",
    "act_embed": None,
    "act_heads": "model",
    "act_ff": "model",
    "cache_seq": None,
    "cache_heads": "model",
}


def multipod(rules: Rules) -> Rules:
    """Extend rules with a leading 'pod' pure-DP axis."""
    r = dict(rules)
    r["batch"] = ("pod", "data")
    return r


def with_axis_sizes(rules: Rules, mesh) -> Rules:
    """Attach mesh axis sizes so spec resolution can apply the
    divisibility fallback (a dim not divisible by its mesh axis product is
    left unsharded — the standard production behavior for e.g. 5 KV heads
    on a 16-way tensor axis)."""
    r = dict(rules)
    r["_axis_sizes"] = dict(zip(mesh.axis_names, mesh.devices.shape))
    return r


def _axis_product(rules: Rules, axis) -> int:
    sizes = rules.get("_axis_sizes")
    if not sizes or axis is None:
        return 1
    if isinstance(axis, tuple):
        n = 1
        for a in axis:
            n *= sizes.get(a, 1)
        return n
    return sizes.get(axis, 1)


def _resolve(rules: Rules, axis, dim: Optional[int]):
    """Logical axis -> mesh axis, dropped if ``dim`` is not divisible."""
    phys = rules.get(axis) if axis else None
    if phys is None:
        return None
    if dim is not None and "_axis_sizes" in rules:
        if dim % _axis_product(rules, phys) != 0:
            return None
    return phys


def spec(rules: Optional[Rules], *axes: Optional[str],
         shape: Optional[Tuple[int, ...]] = None) -> P:
    if rules is None:
        return P()
    dims = shape if shape is not None else (None,) * len(axes)
    out, used = [], set()
    for a, d in zip(axes, dims):
        phys = _resolve(rules, a, d)
        # a mesh axis may appear at most once per spec: first dim wins
        flat = phys if isinstance(phys, tuple) else (phys,)
        if phys is not None and any(f in used for f in flat):
            phys = None
        if phys is not None:
            used.update(flat)
        out.append(phys)
    return P(*out)


def shard(x: jax.Array, rules: Optional[Rules], *axes: Optional[str]):
    """with_sharding_constraint by logical axes; no-op without rules."""
    if rules is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, spec(rules, *axes, shape=x.shape))


# ---------------------------------------------------------------------------
# Parameter declaration
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"          # normal | zeros | ones
    scale: float = 1.0            # stddev multiplier for 'normal'

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def init_params(rng: jax.Array, defs, dtype=jnp.bfloat16):
    leaves, treedef = jax.tree_util.tree_flatten(
        defs, is_leaf=lambda x: isinstance(x, ParamDef))
    keys = jax.random.split(rng, len(leaves))
    arrs = []
    for k, d in zip(keys, leaves):
        if d.init == "zeros":
            arrs.append(jnp.zeros(d.shape, dtype))
        elif d.init == "ones":
            arrs.append(jnp.ones(d.shape, dtype))
        else:
            fan_in = d.shape[-2] if len(d.shape) >= 2 else d.shape[-1]
            std = d.scale / np.sqrt(max(1, fan_in))
            arrs.append((jax.random.normal(k, d.shape, jnp.float32)
                         * std).astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, arrs)


def abstract_params(defs, dtype=jnp.bfloat16):
    return jax.tree_util.tree_map(
        lambda d: jax.ShapeDtypeStruct(d.shape, dtype), defs,
        is_leaf=lambda x: isinstance(x, ParamDef))


def param_specs(defs, rules: Optional[Rules]):
    def to_spec(d: ParamDef) -> P:
        if rules is None:
            return P()
        return spec(rules, *d.axes, shape=d.shape)
    return jax.tree_util.tree_map(
        to_spec, defs, is_leaf=lambda x: isinstance(x, ParamDef))
