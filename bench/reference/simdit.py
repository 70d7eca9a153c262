"""Plain reference of a DSE answer: the SimDIT cost model, written out.

It imports nothing of the system under test.  Given a configuration file
(hardware, energy constants, lattice, network) and one query (budget
pair, objective, batch), it prices every candidate of the lattice with
the scalar SimDIT model (paper Secs. IV-VI) and returns what the service
answers: the best and worst candidates, the within-15% frontier and the
(cycles, energy) Pareto set.

The model is evaluated layer by layer with Python integers: the greedy
tiling walk (Sec. IV-B), the Conv tile-segment stall model (Table IV,
Eqs. 15-18), the SIMD model (Eqs. 19-23) and the energy model
(Eqs. 29-32).  Cycle counts are exact integers; the only floats are the
energy terms, evaluated elementwise in float64 in the order Eq. 29 adds
them.  Candidate order is row-major over (size tuple, bandwidth tuple),
both enumerated in lattice order, and ties go to the first candidate.

``precision="float32"`` is the control: the same model with the grid,
the energy and the scores held in float32, the precision below the one
the configuration states (int64 cycles, float64 energy).
"""
from __future__ import annotations

import itertools
import math
from dataclasses import dataclass, replace
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

KB = 1024
PJ = 1e-12
FRONTIER_FRAC = 0.15


# ---------------------------------------------------------------------------
# Layers (paper Table I)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Conv:
    n: int
    ic: int
    ih: int
    iw: int
    oc: int
    oh: int
    ow: int
    kh: int
    kw: int
    s: int = 1
    has_bias: bool = True
    phase: str = "fwd"

    @property
    def weight_elems(self) -> int:
        return self.kh * self.kw * self.ic * self.oc

    @property
    def ofmap_elems(self) -> int:
        return self.n * self.oh * self.ow * self.oc


@dataclass(frozen=True)
class Tensor:
    rank: str            # "4d" | "1d"
    io: str              # "in" | "out"
    scale: float = 1.0


@dataclass(frozen=True)
class Part:
    tensors: Tuple[Tensor, ...]
    ops4d: Tuple[str, ...] = ()
    ops1d: Tuple[str, ...] = ()


@dataclass(frozen=True)
class Simd:
    op: str
    h: int
    w: int
    n: int
    c: int
    parts: Tuple[Part, ...]
    phase: str = "fwd"
    pool_r: int = 0
    pool_s: int = 0


def _simd(op, h, w, n, c, parts, phase="fwd", **kw) -> Simd:
    return Simd(op, h, w, n, c, tuple(parts), phase, **kw)


def tensor_add(h, w, n, c, phase="fwd"):
    return _simd("tensor_add", h, w, n, c, [Part(
        (Tensor("4d", "in"), Tensor("4d", "in"), Tensor("4d", "out")),
        ("add",))], phase)


def relu(h, w, n, c):
    return _simd("relu", h, w, n, c, [Part(
        (Tensor("4d", "in"), Tensor("4d", "out")), ("max",))])


def relu_back(h, w, n, c):
    return _simd("relu_back", h, w, n, c, [Part(
        (Tensor("4d", "in"), Tensor("4d", "in"), Tensor("4d", "out")),
        ("cmp", "mul"))], "bwd")


def max_pool(oh, ow, n, c, r, s):
    ih, iw = (oh - 1) * s + r, (ow - 1) * s + r
    scale = (ih * iw) / float(oh * ow)
    return _simd("pool_max", oh, ow, n, c, [Part(
        (Tensor("4d", "in", scale), Tensor("4d", "out")),
        ("max",) * (r * r - 1))], pool_r=r, pool_s=s)


def max_pool_back(oh, ow, n, c, r, s):
    ih, iw = (oh - 1) * s + r, (ow - 1) * s + r
    scale = (oh * ow) / float(ih * iw)
    return _simd("pool_max_back", ih, iw, n, c, [Part(
        (Tensor("4d", "in", scale), Tensor("4d", "in", scale),
         Tensor("4d", "out")), ("cmp", "mul"))], "bwd")


def global_avg_pool(ih, iw, n, c):
    return _simd("gap", ih, iw, n, c, [Part(
        (Tensor("4d", "in"), Tensor("1d", "out")), ("add",), ("mul",))])


def gap_back(ih, iw, n, c):
    return _simd("gap_back", ih, iw, n, c, [Part(
        (Tensor("1d", "in"), Tensor("4d", "out")), ("mul",))], "bwd")


def batch_norm(h, w, n, c):
    p1 = Part((Tensor("4d", "in"), Tensor("1d", "out"), Tensor("1d", "out")),
              ("add", "mul", "add"), ("mul", "mul", "sub", "rsqrt"))
    p2 = Part((Tensor("4d", "in"), Tensor("1d", "in"), Tensor("1d", "in"),
               Tensor("4d", "out")), ("mul", "add"), ("mul", "mul", "sub"))
    return _simd("bn", h, w, n, c, [p1, p2])


def bn_back(h, w, n, c):
    p1 = Part((Tensor("4d", "in"), Tensor("4d", "in"), Tensor("1d", "in"),
               Tensor("1d", "in"), Tensor("4d", "out"), Tensor("1d", "out"),
               Tensor("1d", "out")), ("sub", "mul", "mul", "add", "add"))
    p2 = Part((Tensor("4d", "in"), Tensor("4d", "in"), Tensor("1d", "in"),
               Tensor("4d", "out")), ("mul", "mul", "mul", "sub", "sub"),
              ("mul", "div"))
    return _simd("bn_back", h, w, n, c, [p1, p2], "bwd")


def param_update(numel):
    c = max(1, min(numel, 4096))
    rows = (numel + c - 1) // c
    return _simd("update", rows, 1, 1, c, [Part(
        (Tensor("4d", "in"), Tensor("4d", "in"), Tensor("4d", "out")),
        ("mul", "sub"))], "bwd")


def bias_grad(oh, ow, n, oc):
    return _simd("bias_grad", oh, ow, n, oc, [Part(
        (Tensor("4d", "in"), Tensor("1d", "out")), ("add",))], "bwd")


# ---------------------------------------------------------------------------
# Networks (torchvision ResNet-50 at 224x224) and the training expansion
# ---------------------------------------------------------------------------

def _conv(n, ic, ih, oc, k, s, pad, has_bias) -> Conv:
    oh = (ih + 2 * pad - k) // s + 1
    return Conv(n, ic, ih, ih, oc, oh, oh, k, k, s, has_bias)


def resnet50(batch: int, bn: bool) -> list:
    """Inference graphs fold BN into the convs (biased convs, no BN
    layers); training graphs keep BN and unbiased convs."""
    n, net = batch, []

    def bn_relu(c, h, with_relu=True):
        if bn:
            net.append(batch_norm(h, h, n, c))
        if with_relu:
            net.append(relu(h, h, n, c))

    net.append(_conv(n, 3, 224, 64, 7, 2, 3, not bn))
    bn_relu(64, 112)
    net.append(max_pool(56, 56, n, 64, 3, 2))
    h, cin = 56, 64
    for blocks, cmid, stride0 in ((3, 64, 1), (4, 128, 2), (6, 256, 2),
                                  (3, 512, 2)):
        for b in range(blocks):
            stride = stride0 if b == 0 else 1
            cout, h_out = cmid * 4, h // stride
            net.append(_conv(n, cin, h, cmid, 1, 1, 0, not bn))
            bn_relu(cmid, h)
            net.append(_conv(n, cmid, h, cmid, 3, stride, 1, not bn))
            bn_relu(cmid, h_out)
            net.append(_conv(n, cmid, h_out, cout, 1, 1, 0, not bn))
            bn_relu(cout, h_out, with_relu=False)
            if stride != 1 or cin != cout:
                net.append(_conv(n, cin, h, cout, 1, stride, 0, not bn))
                bn_relu(cout, h_out, with_relu=False)
            net.append(tensor_add(h_out, h_out, n, cout))
            net.append(relu(h_out, h_out, n, cout))
            h, cin = h_out, cout
    net.append(global_avg_pool(h, h, n, cin))
    net.append(Conv(n, cin, 1, 1, 1000, 1, 1, 1, 1, 1, True))     # fc
    return net


NETWORKS = {"resnet50": resnet50}


def training_graph(net: list) -> list:
    """Forward + backward + parameter updates (paper Table I, Table V):
    the backward walks the network in reverse."""
    out = list(net)
    first_conv = next(i for i, l in enumerate(net) if isinstance(l, Conv))
    for pos in range(len(net) - 1, -1, -1):
        f = net[pos]
        if isinstance(f, Conv):
            if pos != first_conv:                  # dX (Table V, top)
                out.append(Conv(
                    f.n, f.oc, f.s * (f.oh - 1) + 1 + 2 * (f.kh - 1),
                    f.s * (f.ow - 1) + 1 + 2 * (f.kw - 1), f.ic, f.ih, f.iw,
                    f.kh, f.kw, 1, False, "bwd_dx"))
            out.append(Conv(                       # dW (Table V, bottom)
                f.ic, f.n, f.ih, f.iw, f.oc, f.kh, f.kw,
                f.s * (f.oh - 1) + 1, f.s * (f.ow - 1) + 1, 1, False,
                "bwd_dw"))
            if f.has_bias:
                out.append(bias_grad(f.oh, f.ow, f.n, f.oc))
                out.append(param_update(f.oc))
            out.append(param_update(f.weight_elems))
        elif f.op == "bn":
            out += [bn_back(f.h, f.w, f.n, f.c), param_update(f.c),
                    param_update(f.c)]
        elif f.op == "relu":
            out.append(relu_back(f.h, f.w, f.n, f.c))
        elif f.op == "pool_max":
            out.append(max_pool_back(f.h, f.w, f.n, f.c, f.pool_r, f.pool_s))
        elif f.op == "gap":
            out.append(gap_back(f.h, f.w, f.n, f.c))
        elif f.op == "tensor_add":
            out.append(tensor_add(f.h, f.w, f.n, f.c, "bwd"))
        else:
            raise ValueError(f"no backward for {f.op}")
    return out


def network(spec: dict, batch: int) -> list:
    """The layer list a configuration's ``network`` entry names."""
    training = bool(spec["training"])
    net = NETWORKS[spec["net"]](batch, bn=training)
    return training_graph(net) if training else net


# ---------------------------------------------------------------------------
# Tiling (paper Sec. IV-B): the greedy walk
# ---------------------------------------------------------------------------

def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _align_down(v: int, a: int) -> int:
    return max(a, (v // a) * a) if v >= a else v


def _largest_fit(lo: int, hi: int, fits) -> int:
    while lo < hi:
        mid = (lo + hi + 1) // 2
        if fits(mid):
            lo = mid
        else:
            hi = mid - 1
    return lo


def _fill(cur: int, dim: int, fits) -> int:
    """Among the extents in [cur, largest that fits], the one with the
    smallest ceil-padded extent, ties to the largest."""
    if cur >= dim:
        return cur
    hi = _largest_fit(cur, dim, fits)
    best_t, best_ext = cur, ceil_div(dim, cur) * cur
    for t in range(cur, hi + 1):
        ext = ceil_div(dim, t) * t
        if ext < best_ext or (ext == best_ext and t > best_t):
            best_t, best_ext = t, ext
    return best_t


def conv_tiling(hw: dict, l: Conv) -> Tuple[int, ...]:
    """(T_oh, T_ow, T_n, T_kh, T_kw, T_ic, T_oc, t_ic, t_oc)."""
    J, K = hw["J"], hw["K"]
    wcap = hw["wbuf"] // 2 * 8 // hw["b_w"]
    icap = hw["ibuf"] // 2 * 8 // hw["b_i"]
    ocap = hw["obuf"] // 2 * 8 // hw["b_p"]
    T_kh, T_kw = l.kh, l.kw
    j0, k0 = min(J, l.ic), min(K, l.oc)
    while T_kh * T_kw * j0 * k0 > wcap and T_kw > 1:
        T_kw = max(1, T_kw // 2)
    while T_kh * T_kw * j0 * k0 > wcap and T_kh > 1:
        T_kh = max(1, T_kh // 2)
    T_ic = max(1, min(l.ic, _align_down(wcap // (T_kh * T_kw * k0), J)))

    def grow_oc(T_oc):
        while T_oc * 2 <= l.oc and T_kh * T_kw * T_ic * T_oc * 2 <= wcap:
            T_oc *= 2
        T_oc = min(T_oc, l.oc)
        cap = wcap // (T_kh * T_kw * T_ic)
        if cap >= l.oc:
            return l.oc
        if cap >= k0:
            return max(T_oc, min(l.oc, _align_down(cap, k0)))
        return T_oc

    T_oc = grow_oc(k0)
    while T_ic > 1 and T_kh * T_kw * T_ic > icap:
        T_ic = max(1, T_ic // 2)
    T_oc = grow_oc(T_oc)

    def fits(oh, ow, n):
        ih = (oh - 1) * l.s + T_kh
        iw = (ow - 1) * l.s + T_kw
        return ih * iw * n * T_ic <= icap and oh * ow * n * T_oc <= ocap

    T_oh = T_ow = T_n = 1
    grew = True
    while grew:
        grew = False
        if T_ow < l.ow and fits(T_oh, min(T_ow * 2, l.ow), T_n):
            T_ow = min(T_ow * 2, l.ow); grew = True
        if T_oh < l.oh and fits(min(T_oh * 2, l.oh), T_ow, T_n):
            T_oh = min(T_oh * 2, l.oh); grew = True
        if T_n < l.n and fits(T_oh, T_ow, min(T_n * 2, l.n)):
            T_n = min(T_n * 2, l.n); grew = True
    grew = True
    while grew:
        grew = False
        v = _fill(T_ow, l.ow, lambda x: fits(T_oh, x, T_n))
        if v > T_ow:
            T_ow = v; grew = True
        v = _fill(T_oh, l.oh, lambda x: fits(x, T_ow, T_n))
        if v > T_oh:
            T_oh = v; grew = True
        v = _fill(T_n, l.n, lambda x: fits(T_oh, T_ow, x))
        if v > T_n:
            T_n = v; grew = True
    t = (T_oh, T_ow, T_n, T_kh, T_kw, T_ic, T_oc, min(J, T_ic), min(K, T_oc))
    ok = (T_kh * T_kw * T_ic * T_oc * hw["b_w"] // 8 <= hw["wbuf"] // 2
          and ((T_oh - 1) * l.s + T_kh) * ((T_ow - 1) * l.s + T_kw) * T_n
          * T_ic * hw["b_i"] // 8 <= hw["ibuf"] // 2
          and T_oh * T_ow * T_n * T_oc * hw["b_p"] // 8 <= hw["obuf"] // 2
          and not (l.has_bias and T_oc * hw["b_b"] // 8 > hw["bbuf"] // 2)
          and all(1 <= v <= d for v, d in zip(
              t[:7], (l.oh, l.ow, l.n, l.kh, l.kw, l.ic, l.oc))))
    if not ok:                       # unit tiles on all but the lanes
        ic, oc = min(J, l.ic), min(K, l.oc)
        t = (1, 1, 1, 1, 1, ic, oc, ic, oc)
    return t


def _simd_bytes(hw: dict, l: Simd, th, tw, tn, tc) -> int:
    worst, v4 = 0, th * tw * tn * tc
    for part in l.parts:
        tot = 0
        for ref in part.tensors:
            if ref.rank == "4d":
                tot += int(math.ceil(v4 * ref.scale)) * hw["b_in"] // 8
            else:
                tot += tc * hw["b_in"] // 8
        worst = max(worst, tot)
    return worst


def simd_tiling(hw: dict, l: Simd) -> Tuple[int, int, int, int]:
    """(T_h, T_w, T_n, T_c); VMem is single buffered."""
    def fits(th, tw, tn, tc):
        return (1 <= th <= l.h and 1 <= tw <= l.w and 1 <= tn <= l.n
                and 1 <= tc <= l.c
                and _simd_bytes(hw, l, th, tw, tn, tc) <= hw["vmem"])

    tc = min(l.c, max(hw["K"], _align_down(l.c, hw["K"])))
    while not fits(1, 1, 1, tc) and tc > 1:
        tc = max(1, tc // 2)
    tc = _fill(tc, l.c, lambda x: fits(1, 1, 1, x))
    t = [1, 1, 1]                          # h, w, n
    dims = (l.h, l.w, l.n)
    grew = True
    while grew:
        grew = False
        for i in (1, 0, 2):                # w, h, n
            cand = list(t)
            cand[i] = min(t[i] * 2, dims[i])
            if cand != t and fits(*cand, tc):
                t = cand; grew = True
    grew = True
    while grew:
        grew = False
        for i in (1, 0, 2):
            if t[i] >= dims[i]:
                continue

            def f(x, i=i):
                c = list(t); c[i] = x
                return fits(*c, tc)
            v = _fill(t[i], dims[i], f)
            if v > t[i]:
                t[i] = v; grew = True
    return (t[0], t[1], t[2], tc)


# ---------------------------------------------------------------------------
# Per-layer quantities (Secs. IV-C..IV-E)
# ---------------------------------------------------------------------------

def conv_quantities(hw: dict, l: Conv) -> dict:
    """Bandwidth-independent quantities of one Conv layer: per-tile
    compute, the four segment counts, per-stream tile bits (Table IV),
    busy cycles, DRAM bits (Eqs. 4-11) and SRAM bits (Table III)."""
    T_oh, T_ow, T_n, T_kh, T_kw, T_ic, T_oc, t_ic, t_oc = conv_tiling(hw, l)
    m_oh, m_ow, m_n = ceil_div(l.oh, T_oh), ceil_div(l.ow, T_ow), \
        ceil_div(l.n, T_n)
    m_kh, m_kw = ceil_div(l.kh, T_kh), ceil_div(l.kw, T_kw)
    m_ic, m_oc = ceil_div(l.ic, T_ic), ceil_div(l.oc, T_oc)
    m_w = m_kh * m_kw * m_ic * m_oc
    m_sp = m_oh * m_ow * m_n
    m_acc = m_kh * m_kw * m_ic
    m_outer = m_sp * m_w
    m_inner = (T_oh * T_ow * T_n * T_kh * T_kw * ceil_div(T_ic, t_ic)
               * ceil_div(T_oc, t_oc))
    c_tile = (T_oh * T_ow * T_n * T_kh * T_kw * ceil_div(T_ic, hw["J"])
              * ceil_div(T_oc, hw["K"])) + (hw["J"] - 1) + (hw["K"] - 1)
    o5 = m_oc
    o4 = m_w - m_oc
    o1 = m_oc * (m_sp - 1)
    o2 = (m_outer - m_sp * m_oc) - o4
    w_el = T_kh * T_kw * T_ic * T_oc
    i_el = ((T_oh - 1) * l.s + T_kh) * ((T_ow - 1) * l.s + T_kw) * T_n * T_ic
    p_el = T_oh * T_ow * T_n * T_oc
    w_bits = w_el * hw["b_w"]
    b_bits = T_oc * hw["b_b"] if l.has_bias else 0
    iters = m_inner * m_outer
    return dict(
        c_tile=c_tile, o1=o1, o2=o2, o4=o4, o5=o5,
        w_bits=w_bits, wb_bits=w_bits + b_bits, i_bits=i_el * hw["b_i"],
        ps_bits=p_el * hw["b_p"], pls_bits=2 * p_el * hw["b_p"],
        busy=c_tile * m_outer,
        dram=(w_el * m_w * hw["b_w"] + i_el * m_outer * hw["b_i"]
              + p_el * m_sp * m_oc * (2 * m_acc - 1) * hw["b_p"]
              + (T_oc * m_oc * hw["b_b"] if l.has_bias else 0)),
        wbuf=t_ic * t_oc * iters * hw["b_w"],
        ibuf=t_ic * iters * hw["b_i"],
        obuf=(t_oc * 2 * iters - l.ofmap_elems) * hw["b_p"],
        bbuf=l.ofmap_elems * hw["b_b"] if l.has_bias else 0)


def simd_quantities(hw: dict, l: Simd) -> dict:
    """Compute cycles, VMem and DRAM bits of one SIMD layer, and per part
    the tile bits its bandwidth stalls come from (Eqs. 19-23)."""
    th, tw, tn, tc = simd_tiling(hw, l)
    m_hwn = ceil_div(l.h, th) * ceil_div(l.w, tw) * ceil_div(l.n, tn)
    m_c = ceil_div(l.c, tc)
    v4 = th * tw * tn * tc
    lanes = ceil_div(tc, hw["K"])
    pso = (6 - 1) + (hw["K"] - 1)
    compute = dram = sram = 0
    parts = []
    for part in l.parts:
        b4 = sum(int(math.ceil(v4 * r.scale))
                 * (hw["b_in"] if r.io == "in" else hw["b_out"])
                 for r in part.tensors if r.rank == "4d")
        b1 = sum(tc * (hw["b_in"] if r.io == "in" else hw["b_out"])
                 for r in part.tensors if r.rank == "1d")
        dram += (b4 * m_hwn + b1) * m_c
        n_ops = (len(part.ops4d) * v4 * m_hwn * m_c
                 + len(part.ops1d) * tc * m_c)
        sram += n_ops * 3 * hw["b_in"]
        lam4 = sum(hw["lat"][op] for op in part.ops4d)
        lam1 = sum(hw["lat"][op] for op in part.ops1d)
        if lam4:
            compute += (th * tw * tn * lanes * lam4 + pso) * m_hwn * m_c
        if lam1:
            compute += lanes * lam1 * m_c
        parts.append((b4, b1, m_hwn, m_c))
    return dict(compute=compute, dram=dram, vmem=sram, parts=parts)


# ---------------------------------------------------------------------------
# The answer to one query
# ---------------------------------------------------------------------------

@dataclass
class Answer:
    """What a query returns, as plain tuples: points are
    (sizes_kb, bws, cycles)."""
    best: tuple
    worst: tuple
    points: List[tuple]
    pareto: List[tuple]
    n_candidates: int


def _tuples(values, lo, hi):
    return [t for t in itertools.product(values, repeat=4)
            if lo <= sum(t) <= hi]


def _e_sram(size):
    """Per-bit SRAM energy (pJ): scalar sizes in Python floats, arrays
    in numpy, as the configuration's energy model states them."""
    if np.ndim(size) == 0:
        kb = max(1.0, size / 1024.0)
        return 0.035 * (kb / 32.0) ** 0.25
    kb = np.maximum(1.0, np.asarray(size, dtype=float) / 1024.0)
    return 0.035 * (kb / 32.0) ** 0.25


class Reference:
    """Answers queries of one configuration; per-layer quantities are
    memoized by (buffer sizes, layer), and grids by (budget pair, batch),
    within the instance."""

    def __init__(self, cfg: dict, precision: str = "exact"):
        if precision not in ("exact", "float32"):
            raise ValueError(f"unknown precision {precision!r}")
        self.cfg = cfg
        self.hw = dict(cfg["hardware"])
        self.em = dict(cfg["energy"])
        self.lattice = cfg["lattice"]
        self.precision = precision
        self._conv: Dict[tuple, dict] = {}
        self._simd: Dict[tuple, dict] = {}
        self._grids: Dict[tuple, tuple] = {}

    def _cq(self, s3, layer):
        key = (s3, replace(layer, phase=""))
        q = self._conv.get(key)
        if q is None:
            hw = dict(self.hw, wbuf=s3[0] * KB, ibuf=s3[1] * KB,
                      obuf=s3[2] * KB)
            q = self._conv[key] = conv_quantities(hw, layer)
        return q

    def _sq(self, vm, layer):
        key = (vm, replace(layer, phase=""))
        q = self._simd.get(key)
        if q is None:
            q = self._simd[key] = simd_quantities(
                dict(self.hw, vmem=vm * KB), layer)
        return q

    def answer(self, size_budget_kb: int, bw_budget: int, objective: str,
               batch: int) -> Answer:
        key = (size_budget_kb, bw_budget, batch)
        if key not in self._grids:
            self._grids[key] = self._grid(*key)
        return self._reduce(*self._grids[key], objective)

    def _grid(self, size_budget_kb: int, bw_budget: int, batch: int):
        """Per candidate: the Conv and SIMD cycles and the energy fields
        (busy cycles, SRAM and DRAM bits) of one budget pair and batch."""
        lat, tol = self.lattice, self.lattice["tol"]
        sizes = _tuples(lat["sizes_kb"], size_budget_kb * (1 - tol),
                        size_budget_kb * (1 + tol))
        bws = _tuples(lat["bws"], bw_budget * (1 - tol),
                      bw_budget * (1 + tol))
        layers = network(self.cfg["network"], batch)
        convs = [l for l in layers if isinstance(l, Conv)]
        simds = [l for l in layers if isinstance(l, Simd)]

        # conv side: one row per size tuple, one column per bw tuple
        bw_w = np.array([b[0] for b in bws], dtype=np.int64)
        bw_i = np.array([b[1] for b in bws], dtype=np.int64)
        bw_o = np.array([b[2] for b in bws], dtype=np.int64)
        bw_v = np.array([b[3] for b in bws], dtype=np.int64)
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
                for l in convs:
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
                    row += (q["o1"] * seg1 + q["o2"] * seg2
                            + q["o4"] * seg4 + q["o5"] * seg5)
                    for k in e:
                        e[k] += q[k]
                conv_rows[s3] = (row, e)
            row, e = conv_rows[s3]
            conv[si] = row
            for k, v in e.items():
                fields[k][si] = v
            vm = st[3]
            if vm not in simd_rows:
                row = np.zeros(n_b, dtype=np.int64)
                e = dict(s_busy=0, vmem=0, s_dram=0)
                for l in simds:
                    q = self._sq(vm, l)
                    row += q["compute"]
                    for b4, b1, m_hwn, m_c in q["parts"]:
                        stall = (-(-b4 // bw_v)) * m_hwn
                        if b1:
                            stall = stall + (-(-b1 // bw_v))
                        row += stall * m_c
                    e["s_busy"] += q["compute"]
                    e["vmem"] += q["vmem"]
                    e["s_dram"] += q["dram"]
                simd_rows[vm] = (row, e)
            row, e = simd_rows[vm]
            simd[si] = row
            for k, v in e.items():
                fields[k][si] = v
        return sizes, bws, conv, simd, fields

    def _reduce(self, sizes, bws, conv, simd, f, objective) -> Answer:
        em, hw = self.em, self.hw
        sz = np.array(sizes, dtype=np.int64)
        col = lambda v: v[:, None]                       # noqa: E731
        per_mac = em["mac_dyn_w_16b"] if hw["b_w"] >= 16 \
            else em["mac_dyn_w_8b"]
        p_sa = hw["J"] * hw["K"] * per_mac
        p_simd = hw["K"] * em["alu_dyn_w"]
        bits = [(col(f["wbuf"]), col(sz[:, 0] * KB)),
                (col(f["ibuf"]), col(sz[:, 1] * KB)),
                (col(f["obuf"]), col(sz[:, 2] * KB)),
                (col(f["bbuf"]), hw["bbuf"]),
                (col(f["vmem"]), col(sz[:, 3] * KB))]
        if self.precision == "exact":
            cycles = conv + simd
            l_tot = cycles
            e_sram = [(b, _e_sram(s)) for b, s in bits]
            c_sa, c_simd = col(f["busy"]), col(f["s_busy"])
            dram = col(f["dram"] + f["s_dram"])
            t_clk, leak, e_d = em["t_clk_s"], em["leak_frac"], \
                em["e_dram_pj_per_bit"]
            pj = PJ
        else:
            f32 = np.float32
            cycles = conv.astype(f32) + simd.astype(f32)
            l_tot = cycles
            e_sram = [(b.astype(f32), f32(_e_sram(s))) for b, s in bits]
            c_sa = col(f["busy"]).astype(f32)
            c_simd = col(f["s_busy"]).astype(f32)
            dram = col(f["dram"] + f["s_dram"]).astype(f32)
            t_clk, leak, e_d = f32(em["t_clk_s"]), f32(em["leak_frac"]), \
                f32(em["e_dram_pj_per_bit"])
            p_sa, p_simd, pj = f32(p_sa), f32(p_simd), f32(PJ)
        e_tot = (c_sa * p_sa + l_tot * (leak * p_sa)) * t_clk
        e_tot = e_tot + (c_simd * p_simd + l_tot * (leak * p_simd)) * t_clk
        e_s = 0.0
        for b, e in e_sram:
            e_s = e_s + (b * e * pj)
        e_tot = e_tot + e_s
        e_tot = e_tot + dram * e_d * pj
        if objective == "cycles":
            scores = None
        elif objective == "energy":
            scores = e_tot
        elif objective == "edp":
            runtime = np.asarray(l_tot, dtype=e_tot.dtype) * t_clk
            scores = e_tot * runtime
        else:
            raise ValueError(f"unknown objective {objective!r}")

        n_b = len(bws)
        flat = cycles.ravel()

        def point(i):
            return (sizes[i // n_b], bws[i % n_b], int(flat[i]))

        if scores is None:
            best_i, worst_i = int(np.argmin(flat)), int(np.argmax(flat))
            limit = int(flat[best_i]) * (1 + FRONTIER_FRAC)
            within = flat <= limit
        else:
            sc = scores.ravel()
            finite = np.isfinite(sc)
            best_i = int(np.argmin(np.where(finite, sc, np.inf)))
            worst_i = int(np.argmax(np.where(finite, sc, -np.inf)))
            limit = float(sc[best_i]) * (1 + FRONTIER_FRAC)
            within = sc <= limit
        # Pareto: by cycles, then energy, then grid order; keep each
        # candidate whose energy is below every one before it
        energy = e_tot.ravel()
        pareto, best_e = [], math.inf
        for i in np.lexsort((np.arange(len(flat)), energy, flat)).tolist():
            if energy[i] < best_e:
                pareto.append(i)
                best_e = energy[i]
        return Answer(best=point(best_i), worst=point(worst_i),
                      points=[point(int(i)) for i in np.nonzero(within)[0]],
                      pareto=[point(i) for i in sorted(pareto)],
                      n_candidates=len(flat))
