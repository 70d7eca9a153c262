"""Plain reference of a DSE answer for an LLM: GEMM + SIMD graphs.

It imports nothing of the system under test.  It writes out the model's
layer graph from the published numbers (``NETS``, keyed by the net name
a configuration's ``network`` entry gives), expands it for training, and
prices every candidate of the lattice with the scalar SimDIT model of
``bench/reference/simdit.py``: the same tiling walk, Conv quantities,
SIMD quantities, energy and reduction.

A GEMM ``out[m, n] = in[m, k] @ w[k, n]`` is priced as the paper's FC
layer, ``Conv(n=m, ic=k, ih=iw=oh=ow=kh=kw=1, oc=n, has_bias=False)``
(k on the array's rows, n on its columns, m streamed).  ``count``
repeats an identical GEMM (per head, per expert): it multiplies the
layer's row of cycles and its energy fields, never its tiling.
Identical layers are priced once and weighted by their multiplicity;
cycle counts and energy fields stay exact integers.

The conventions are the lowering's, by design of the cost model (full
S x S attention scores, no embedding lookup, balanced expert dispatch
with ``ceil(tokens * top_k / experts)`` tokens per routed expert, no
cost for the router's top-k or the expert combine), and the training
expansion is SimDIT's Table I for GEMMs: dX with n and k swapped, dW
with m and k swapped, a parameter update for weight GEMMs only, and a
backward that mirrors the forward's cost for RMSNorm, softmax, rotary,
the gated activation and the residual add.

``precision="float32"`` is the control, as in ``simdit.py``.
"""
from __future__ import annotations

import math
from collections import Counter
from dataclasses import dataclass, replace
from typing import Dict, List, Tuple

import numpy as np

from bench.reference import simdit
from bench.reference.simdit import Conv, Part, Simd, Tensor, _simd

# Published model numbers, under the keys of the model's config.json.
NETS: Dict[str, dict] = {
    # https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json
    "deepseek-v2-lite": {
        "num_hidden_layers": 27, "first_k_dense_replace": 1,
        "hidden_size": 2048, "num_attention_heads": 16,
        "q_lora_rank": None, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "v_head_dim": 128, "intermediate_size": 10944,
        "moe_intermediate_size": 1408, "n_routed_experts": 64,
        "num_experts_per_tok": 6, "n_shared_experts": 2,
        "vocab_size": 102400,
    },
}


@dataclass(frozen=True)
class Gemm:
    m: int
    n: int
    k: int
    count: int = 1
    param: bool = True          # False: both operands are activations
    phase: str = "fwd"

    def as_fc(self) -> Conv:
        return Conv(self.m, self.k, 1, 1, self.n, 1, 1, 1, 1, 1, False)


# ---------------------------------------------------------------------------
# SIMD ops of a transformer block (the paper's generic tile template, with
# the feature dimension on the lanes and the tokens on h)
# ---------------------------------------------------------------------------

def rmsnorm(tokens, d):
    """Sum of squares per token, finalised with a reciprocal sqrt; then
    the scale pass."""
    return _simd("rmsnorm", tokens, 1, 1, d, [
        Part((Tensor("4d", "in"), Tensor("1d", "out")), ("mul", "add"),
             ("mul", "rsqrt")),
        Part((Tensor("4d", "in"), Tensor("1d", "in"), Tensor("4d", "out")),
             ("mul", "mul"))])


def softmax(rows, cols):
    return _simd("softmax", rows, 1, 1, cols, [Part(
        (Tensor("4d", "in"), Tensor("4d", "out")),
        ("max", "sub", "exp", "add", "mul"))])


def rotary(tokens, d):
    """x * cos +- rot(x) * sin, reading x and the two tables."""
    return _simd("rotary", tokens, 1, 1, d, [Part(
        (Tensor("4d", "in"), Tensor("4d", "in"), Tensor("4d", "in"),
         Tensor("4d", "out")), ("mul", "mul", "add"))])


def gated_act(tokens, d):
    """silu(gate) * up."""
    return _simd("act_silu", tokens, 1, 1, d, [Part(
        (Tensor("4d", "in"), Tensor("4d", "in"), Tensor("4d", "out")),
        ("exp", "add", "div", "mul", "mul"))])


def residual(tokens, d):
    return simdit.tensor_add(tokens, 1, 1, d)


# ---------------------------------------------------------------------------
# The model and its training expansion
# ---------------------------------------------------------------------------

def mla_moe_model(p: dict, batch: int, seq: int) -> list:
    """DeepSeek-V2 (arXiv:2405.04434): multi-head latent attention in its
    training form (Sec. 2.1; no weight absorption), then a dense SwiGLU
    MLP in the first ``first_k_dense_replace`` layers and DeepSeekMoE
    (Sec. 2.2: softmax router, routed experts, shared experts run as one
    MLP of their summed width) in the rest; final RMSNorm and an untied
    lm_head."""
    D, H, t = p["hidden_size"], p["num_attention_heads"], batch * seq
    nope, rope = p["qk_nope_head_dim"], p["qk_rope_head_dim"]
    dv, R = p["v_head_dim"], p["kv_lora_rank"]
    E, K = p["n_routed_experts"], p["num_experts_per_tok"]
    Fe = p["moe_intermediate_size"]
    m_exp = max(1, math.ceil(t * K / E))

    def mlp(F, m=t, count=1):
        return [Gemm(m, F, D, count), Gemm(m, F, D, count),
                gated_act(m * count, F), Gemm(m, D, F, count)]

    net: list = []
    for i in range(p["num_hidden_layers"]):
        net += [rmsnorm(t, D), Gemm(t, H * (nope + rope), D),
                Gemm(t, R + rope, D), rmsnorm(t, R),
                Gemm(t, H * (nope + dv), R),
                rotary(t * H, rope), rotary(t, rope),
                Gemm(seq, seq, nope + rope, batch * H, param=False),
                softmax(batch * H * seq, seq),
                Gemm(seq, dv, seq, batch * H, param=False),
                Gemm(t, D, H * dv), residual(t, D)]
        net.append(rmsnorm(t, D))
        if i < p["first_k_dense_replace"]:
            net += mlp(p["intermediate_size"])
        else:
            net += [Gemm(t, E, D), softmax(t, E)]
            net += mlp(Fe, m_exp, E)
            net += mlp(p["n_shared_experts"] * Fe)
        net.append(residual(t, D))
    return net + [rmsnorm(t, D), Gemm(t, p["vocab_size"], D)]


MODELS = {"deepseek-v2-lite": mla_moe_model}
MIRRORED = ("rmsnorm", "softmax", "rotary", "act_silu", "tensor_add")


def training_graph(net: list) -> list:
    """Forward + backward + parameter updates, the backward walking the
    network in reverse."""
    out = list(net)
    for f in reversed(net):
        if isinstance(f, Gemm):
            out.append(replace(f, n=f.k, k=f.n, phase="bwd_dx"))
            out.append(replace(f, m=f.k, k=f.m, phase="bwd_dw"))
            if f.param:
                out.append(simdit.param_update(f.k * f.n * f.count))
        elif f.op in MIRRORED:
            out.append(replace(f, phase="bwd"))
            if f.op == "rmsnorm":
                out.append(simdit.param_update(f.c))
        else:
            raise ValueError(f"no backward for {f.op}")
    return out


def network(cfg: dict, batch: int) -> list:
    """The layer list of a configuration's ``network`` entry.  Where the
    configuration file also states one of the model's published numbers,
    it has to be the one this module prices."""
    spec = cfg["network"]
    p = NETS[spec["net"]]
    differ = sorted(k for k in p if k in cfg and cfg[k] != p[k])
    if differ:
        raise ValueError(f"{cfg.get('name')}: {differ} differ from the "
                         f"published numbers of {spec['net']}")
    net = MODELS[spec["net"]](p, batch, int(spec["seq"]))
    return training_graph(net) if spec["training"] else net


def weighted(layers: list) -> Tuple[List[Tuple[Conv, int]],
                                    List[Tuple[Simd, int]]]:
    """(FC layer, weight) and (SIMD layer, weight) pairs, one per
    distinct shape; a GEMM's weight is its multiplicity times its
    ``count``."""
    groups = Counter(replace(l, phase="") for l in layers)
    convs = [(g.as_fc(), w * g.count) for g, w in groups.items()
             if isinstance(g, Gemm)]
    simds = [(s, w) for s, w in groups.items() if isinstance(s, Simd)]
    return convs, simds


class Reference(simdit.Reference):
    """Answers queries of one LLM configuration (``simdit.Reference``
    with the weighted GEMM + SIMD grid)."""

    def _grid(self, size_budget_kb: int, bw_budget: int, batch: int):
        lat, tol = self.lattice, self.lattice["tol"]
        sizes = simdit._tuples(lat["sizes_kb"], size_budget_kb * (1 - tol),
                               size_budget_kb * (1 + tol))
        bws = simdit._tuples(lat["bws"], bw_budget * (1 - tol),
                             bw_budget * (1 + tol))
        convs, simds = weighted(network(self.cfg, batch))
        bw_w, bw_i, bw_o, bw_v = (np.array([b[j] for b in bws],
                                           dtype=np.int64) for j in range(4))
        n_s, n_b = len(sizes), len(bws)
        conv = np.zeros((n_s, n_b), dtype=np.int64)
        simd = np.zeros((n_s, n_b), dtype=np.int64)
        fields = {k: np.zeros(n_s, dtype=np.int64)
                  for k in ("busy", "wbuf", "ibuf", "obuf", "bbuf", "dram",
                            "s_busy", "vmem", "s_dram")}
        conv_rows: Dict[tuple, tuple] = {}
        simd_rows: Dict[int, tuple] = {}
        for si, st in enumerate(sizes):
            s3 = st[:3]
            if s3 not in conv_rows:
                row = np.zeros(n_b, dtype=np.int64)
                e = dict.fromkeys(("busy", "wbuf", "ibuf", "obuf", "bbuf",
                                   "dram"), 0)
                for l, w in convs:
                    q = self._cq(s3, l)
                    t_w = -(-q["w_bits"] // bw_w)
                    t_wb = -(-q["wb_bits"] // bw_w)
                    t_i = -(-q["i_bits"] // bw_i)
                    t_ps = -(-q["ps_bits"] // bw_o)
                    t_pls = -(-q["pls_bits"] // bw_o)
                    c = q["c_tile"]
                    seg1 = np.maximum(np.maximum(c, t_i), t_ps)
                    seg2 = np.maximum(np.maximum(c, t_i), t_pls)
                    seg4 = np.maximum(np.maximum(np.maximum(c, t_w), t_i),
                                      t_pls)
                    seg5 = np.maximum(np.maximum(np.maximum(c, t_wb), t_i),
                                      t_ps)
                    row += w * (q["o1"] * seg1 + q["o2"] * seg2
                                + q["o4"] * seg4 + q["o5"] * seg5)
                    for k in e:
                        e[k] += w * q[k]
                conv_rows[s3] = (row, e)
            row, e = conv_rows[s3]
            conv[si] = row
            for k, v in e.items():
                fields[k][si] = v
            vm = st[3]
            if vm not in simd_rows:
                row = np.zeros(n_b, dtype=np.int64)
                e = dict(s_busy=0, vmem=0, s_dram=0)
                for l, w in simds:
                    q = self._sq(vm, l)
                    stalls = np.zeros(n_b, dtype=np.int64)
                    for b4, b1, m_hwn, m_c in q["parts"]:
                        stall = (-(-b4 // bw_v)) * m_hwn
                        if b1:
                            stall = stall + (-(-b1 // bw_v))
                        stalls += stall * m_c
                    row += w * (q["compute"] + stalls)
                    e["s_busy"] += w * q["compute"]
                    e["vmem"] += w * q["vmem"]
                    e["s_dram"] += w * q["dram"]
                simd_rows[vm] = (row, e)
            row, e = simd_rows[vm]
            simd[si] = row
            for k, v in e.items():
                fields[k][si] = v
        return sizes, bws, conv, simd, fields
