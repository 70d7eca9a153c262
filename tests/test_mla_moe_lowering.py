"""The cost-model lowering of latent attention and of experts narrower
than the dense MLP (DeepSeek-V2-Lite), checked three ways: its widths at
the published sizes, its GEMM MACs against the dot MACs that
``launch/costmodel.jaxpr_cost`` counts in a plain ``jax.numpy`` forward
of the same blocks, and its spans; plus a pin of every registered
config's lowering."""
import dataclasses
import hashlib
import math

import jax
import jax.numpy as jnp
import pytest

from repro.configs import _MODULES
from repro.core import TRAIN_PRESETS, Study, Workload, tracing
from repro.core.dse import clear_table_caches
from repro.core.layers import GemmLayer, SimdLayer
from repro.launch.costmodel import jaxpr_cost
from repro.models.frontends import lower_llm, resolve_llm_config

DSV2 = resolve_llm_config("deepseek-v2-lite")


def _by_name(layers):
    return {l.name: l for l in layers}


# ---------------------------------------------------------------------------
# (a) published widths
# ---------------------------------------------------------------------------

def test_published_widths_of_block0_and_block1():
    B, S = 4, 4096
    t = B * S
    layers = lower_llm(DSV2, batch=B, seq=S)
    g = _by_name(layers)
    for blk in ("blk0", "blk1"):
        a = f"{blk}.attn"
        assert (g[f"{a}.q"].m, g[f"{a}.q"].n, g[f"{a}.q"].k) == \
            (t, 16 * (128 + 64), 2048)
        assert g[f"{a}.q"].n == 3072
        assert (g[f"{a}.kv_a"].n, g[f"{a}.kv_a"].k) == (576, 2048)
        assert (g[f"{a}.kv_norm"].h, g[f"{a}.kv_norm"].c) == (t, 512)
        assert (g[f"{a}.kv_b"].n, g[f"{a}.kv_b"].k) == (4096, 512)
        assert (g[f"{a}.o"].n, g[f"{a}.o"].k) == (2048, 2048)
        assert (g[f"{a}.rope_q"].h, g[f"{a}.rope_q"].c) == (t * 16, 64)
        assert (g[f"{a}.rope_k"].h, g[f"{a}.rope_k"].c) == (t, 64)
        sc, av = g[f"{a}.scores"], g[f"{a}.av"]
        assert (sc.m, sc.n, sc.k, sc.count, sc.param) == \
            (S, S, 192, B * 16, False)
        assert (av.m, av.n, av.k, av.count, av.param) == \
            (S, 128, S, B * 16, False)
    # layer 0 alone is dense, at 10,944
    assert (g["blk0.mlp.gate"].n, g["blk0.mlp.down"].k) == (10944, 10944)
    assert not any(n.startswith("blk0.moe") for n in g)
    assert not any(".mlp." in n for n in g if not n.startswith("blk0."))
    # layers 1-26: 64 routed experts of 1,408, top-6, 2 shared as 2,816
    m_exp = math.ceil(t * 6 / 64)
    assert m_exp == 1536
    for name in ("e_gate", "e_up"):
        e = g[f"blk1.moe.{name}"]
        assert (e.m, e.n, e.k, e.count) == (m_exp, 1408, 2048, 64)
    e = g["blk1.moe.e_down"]
    assert (e.m, e.n, e.k, e.count) == (m_exp, 2048, 1408, 64)
    assert (g["blk1.moe.router"].n, g["blk1.moe.route_sm"].c) == (64, 64)
    assert (g["blk1.moe.s_gate"].n, g["blk1.moe.s_down"].k) == (2816, 2816)
    assert g["blk26.moe.e_up"] == dataclasses.replace(
        g["blk1.moe.e_up"], name="blk26.moe.e_up")
    assert "blk27.attn.q" not in g
    assert (g["lm_head"].m, g["lm_head"].n, g["lm_head"].k) == \
        (t, 102400, 2048)


# ---------------------------------------------------------------------------
# (b) a plain jax.numpy forward of the blocks, at reduced widths
# ---------------------------------------------------------------------------

SMALL = DSV2.replace(
    n_layers=2, block_pattern=("attn", "attn+moe"), d_model=64, n_heads=4,
    n_kv_heads=4, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=12, d_ff=96, moe_d_ff=24, n_experts=8, top_k=2,
    n_shared_experts=2, vocab_size=101)
B, S = 2, 8                 # 16 tokens x top-2 = 32, divisible by 8


def _rms(x, g):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * g


def _rope(x):
    """Rotary over the last dim of (B, S, heads, d)."""
    d = x.shape[-1]
    pos = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None]
    ang = pos * (1e4 ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[None, :, None]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[None, :, None]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + half * sin


def _params(cfg, key):
    D, H, R = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
        cfg.v_head_dim
    E, F, Fe = cfg.n_experts, cfg.d_ff, cfg.expert_ff
    Fs = cfg.n_shared_experts * Fe
    shapes = {
        "wq": (D, H * (nope + rope)), "wkv_a": (D, R + rope),
        "wkv_b": (R, H * (nope + dv)), "wo": (H * dv, D),
        "w_gate": (D, F), "w_up": (D, F), "w_down": (F, D),
        "router": (D, E), "e_gate": (E, D, Fe), "e_up": (E, D, Fe),
        "e_down": (E, Fe, D), "s_gate": (D, Fs), "s_up": (D, Fs),
        "s_down": (Fs, D)}
    keys = jax.random.split(key, len(shapes))
    p = {k: jax.random.normal(kk, s, jnp.float32) / math.sqrt(s[-2])
         for kk, (k, s) in zip(keys, sorted(shapes.items()))}
    for k, d in (("attn_norm", D), ("kv_norm", R), ("ffn_norm", D)):
        p[k] = jnp.ones((d,), jnp.float32)
    return p


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b)


def _no_dot(spec, a, b):
    """The einsum's result shape with no dot: what the forward costs
    without its matrix products."""
    return jnp.zeros(jax.eval_shape(lambda a, b: jnp.einsum(spec, a, b),
                                    a, b).shape, a.dtype)


def _mla(cfg, p, x, mm):
    """DeepSeek-V2 Sec. 2.1, training form (no weight absorption)."""
    Bx, Sx, D = x.shape
    H, R = cfg.n_heads, cfg.kv_lora_rank
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
        cfg.v_head_dim
    h = _rms(x, p["attn_norm"])
    q = mm("bsd,df->bsf", h, p["wq"]).reshape(Bx, Sx, H, nope + rope)
    kv = mm("bsd,df->bsf", h, p["wkv_a"])
    c, k_pe = _rms(kv[..., :R], p["kv_norm"]), kv[..., R:]
    kvb = mm("bsr,rf->bsf", c, p["wkv_b"]).reshape(Bx, Sx, H, nope + dv)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:])], -1)
    k_pe = jnp.broadcast_to(_rope(k_pe[:, :, None]), (Bx, Sx, H, rope))
    k = jnp.concatenate([kvb[..., :nope], k_pe], -1)
    v = kvb[..., nope:]
    s = mm("bshd,bthd->bhst", q, k) / math.sqrt(nope + rope)
    s = jnp.where(jnp.tril(jnp.ones((Sx, Sx), bool)), s, -jnp.inf)
    a = jax.nn.softmax(s, -1)
    o = mm("bhst,bthd->bshd", a, v).reshape(Bx, Sx, H * dv)
    return x + mm("bsf,fd->bsd", o, p["wo"])


def _swiglu(h, wg, wu, wd, mm):
    return mm("tf,fd->td", jax.nn.silu(mm("td,df->tf", h, wg))
              * mm("td,df->tf", h, wu), wd)


def _dense(cfg, p, x, mm):
    h = _rms(x, p["ffn_norm"]).reshape(-1, cfg.d_model)
    y = _swiglu(h, p["w_gate"], p["w_up"], p["w_down"], mm)
    return x + y.reshape(x.shape)


def _moe(cfg, p, x, mm):
    """DeepSeek-V2 Sec. 2.2: softmax router, greedy top-k with
    unnormalised gates, every (token, choice) through its expert's
    SwiGLU, the weighted sum, plus the shared experts on every token."""
    h = _rms(x, p["ffn_norm"]).reshape(-1, cfg.d_model)
    probs = jax.nn.softmax(mm("td,de->te", h, p["router"]), -1)
    gates, idx = jax.lax.top_k(probs, cfg.top_k)             # (t, K)
    hk = jnp.repeat(h, cfg.top_k, axis=0)                    # (t*K, D)
    e = idx.reshape(-1)
    g = jax.nn.silu(mm("td,tdf->tf", hk, p["e_gate"][e])) \
        * mm("td,tdf->tf", hk, p["e_up"][e])
    y = mm("tf,tfd->td", g, p["e_down"][e]).reshape(-1, cfg.top_k,
                                                    cfg.d_model)
    y = (y * gates[..., None]).sum(1)
    y = y + _swiglu(h, p["s_gate"], p["s_up"], p["s_down"], mm)
    return x + y.reshape(x.shape)


def _block(kind):
    def block(cfg, p, x, mm):
        x = _mla(cfg, p, x, mm)
        return (_dense if kind == "dense" else _moe)(cfg, p, x, mm)
    return block


def _dot_macs(fn, *args):
    cost = jaxpr_cost(lambda *a: fn(*a, _einsum), *args)
    bare = jaxpr_cost(lambda *a: fn(*a, _no_dot), *args)
    return (cost.flops - bare.flops) / 2


@pytest.mark.parametrize("blk,kind", [("blk0", "dense"), ("blk1", "moe")])
def test_jnp_forward_dot_macs_equal_the_lowered_gemm_macs(blk, kind):
    cfg = SMALL
    p = _params(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model),
                          jnp.float32)
    fn = _block(kind)
    with jax.default_matmul_precision("highest"):
        y = jax.jit(lambda p, x: fn(cfg, p, x, _einsum))(p, x)
        macs = _dot_macs(lambda p, x, mm: fn(cfg, p, x, mm), p, x)
    assert y.shape == x.shape and bool(jnp.all(jnp.isfinite(y)))
    gemms = [l for l in lower_llm(cfg, batch=B, seq=S)
             if isinstance(l, GemmLayer) and l.name.startswith(f"{blk}.")]
    assert macs == sum(l.macs for l in gemms) > 0
    if kind == "moe":
        tokens = B * S
        assert tokens * cfg.top_k % cfg.n_experts == 0
        for name in ("e_gate", "e_up", "e_down"):
            e = next(l for l in gemms if l.name == f"{blk}.moe.{name}")
            assert e.m * e.count == tokens * cfg.top_k


# ---------------------------------------------------------------------------
# (e) the spans
# ---------------------------------------------------------------------------

@pytest.fixture
def fresh_spans():
    clear_table_caches()
    tracing.clear()
    yield
    tracing.clear()
    clear_table_caches()


def test_lowering_and_gemm_composition_record_their_spans(fresh_spans):
    layers = lower_llm(DSV2, batch=1, seq=64)
    (lower,) = tracing.spans("repro.models.lower")
    assert lower.n == len(layers) > 0
    tracing.clear()
    study = Study(TRAIN_PRESETS[64], sizes=(256, 512, 1024, 2048),
                  bws=(256, 512, 1024, 2048), backend="numpy", store=None,
                  workers=0)
    wl = Workload("deepseek-v2-lite", training=True, batch=1, seq=64)
    res = study.search(wl, 2048, 2048)
    # the search's expansion lowers once, inside its repro.study.layers
    (expand,) = tracing.spans("repro.study.layers")
    (lower,) = tracing.spans("repro.models.lower")
    assert lower.parent == expand.sid
    assert lower.n == len(lower_llm(DSV2, batch=1, seq=64))
    (compose,) = tracing.spans("repro.dse.gemm_compose")
    union = {dataclasses.replace(l, name="", phase="fwd", param=True)
             for l in wl.layers() if isinstance(l, GemmLayer)}
    n_s3 = compose.n // len(union)
    assert compose.n == n_s3 * len(union) and n_s3 > 0
    inner = [f for f in tracing.spans("repro.dse.table_fetch")
             if f.parent == compose.sid]
    assert len(inner) == 1 and inner[0].n == n_s3
    assert compose.parent == tracing.spans("repro.dse.compose")[0].sid
    assert res.n_candidates > 0


def test_cnn_search_records_no_llm_span(fresh_spans):
    Study(TRAIN_PRESETS[64], sizes=(256, 512, 1024, 2048),
          bws=(256, 512, 1024, 2048), backend="numpy", store=None,
          workers=0).search(Workload("resnet18", batch=2), 2048, 2048)
    assert tracing.spans("repro.models.lower") == []
    assert tracing.spans("repro.dse.gemm_compose") == []


# ---------------------------------------------------------------------------
# Every registered config lowers as it did before latent attention and
# expert widths were added: (layers, GEMM MACs, digest of the layer
# tuples) at batch 1, seq 64, inference and training
# ---------------------------------------------------------------------------

PINS = {
    ("deepseek_v2_lite", False): (644, 157449977856, "2402c0b0611341e3"),
    ("deepseek_v2_lite", True): (2012, 472349933568, "f7c3d8c52c0f7e7f"),
    ("gemma3_27b", False): (1180, 1730569830400, "72833fb74453c036"),
    ("gemma3_27b", True): (3603, 5191709491200, "5f7b4af6029334c9"),
    ("granite_moe_1b", False): (458, 27632271360, "3196612e9b797363"),
    ("granite_moe_1b", True): (1399, 82896814080, "81d9ea77ffd870f9"),
    ("llama4_maverick", False): (962, 1035594629120, "8f53734c1becc153"),
    ("llama4_maverick", True): (2983, 3106783887360, "5abfbce620ffc92b"),
    ("mamba2_130m", False): (242, 8841461760, "47e8de554f91ed4a"),
    ("mamba2_130m", True): (679, 26524385280, "45a4a09e3889d9bc"),
    ("pixtral_12b", False): (682, 1487132426240, "7ca8b1dc473575ab"),
    ("pixtral_12b", True): (2087, 4461397278720, "d7a6cd843bd69b54"),
    ("qwen3_0_6b", False): (534, 38612762624, "e4db45fde0a35452"),
    ("qwen3_0_6b", True): (1631, 115838287872, "7951e961580972d5"),
    ("recurrentgemma_9b", False): (570, 601698074624, "09eff6a722337d6f"),
    ("recurrentgemma_9b", True): (1723, 1805094223872, "efda684ce71c7713"),
    ("smollm_360m", False): (546, 23404216320, "110033e1bbe8924f"),
    ("smollm_360m", True): (1671, 70212648960, "c42c5cc249f1670a"),
    ("stablelm_1_6b", False): (410, 92476014592, "1fe387eff1243dc3"),
    ("stablelm_1_6b", True): (1304, 277428043776, "81197bc03b7af523"),
    ("whisper_tiny", False): (150, 21408915456, "f099d55ad80cf815"),
    ("whisper_tiny", True): (496, 64226746368, "a3dd7291b179adda"),
}


def test_every_registered_config_is_pinned():
    assert {m for m, _ in PINS} == set(_MODULES.values())


@pytest.mark.parametrize("net,training", sorted(PINS))
def test_registered_lowering_is_pinned(net, training):
    layers = Workload(net, training=training, seq=64).layers()
    digest = hashlib.sha256(repr(tuple(
        dataclasses.astuple(l) for l in layers)).encode()).hexdigest()[:16]
    macs = sum(l.macs for l in layers if isinstance(l, GemmLayer))
    assert all(isinstance(l, (GemmLayer, SimdLayer)) for l in layers)
    assert (len(layers), macs, digest) == PINS[net, training]
