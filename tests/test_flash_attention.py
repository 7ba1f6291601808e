"""The Pallas flash-attention kernel against the O(S²) oracle, forward and
vjp, in interpret mode; and which path the attention layers take.

Under ``highest`` the kernel agrees with ``reference_attention`` to float32
rounding.  Under ``default`` its products take bfloat16 operands (one MXU
pass, as XLA's default does on a TPU), so it agrees with the oracle to
bfloat16's rounding: on the CPU XLA's default is float32, which only
widens the gap the tolerance has to hold.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist import sharding
from repro.kernels import flash_attention as fa
from repro.nn import attention
from repro.nn.attention import Attention, MLAttention, reference_attention


@dataclasses.dataclass(frozen=True)
class Case:
    b: int = 1
    h: int = 2
    kvh: int = 2
    s: int = 256
    dk: int = 64
    dv: int = 64
    causal: bool = True
    blocks: tuple = (128, 128)
    precision: str = "highest"


CASES = {
    "causal": Case(),
    "non_causal": Case(b=2, causal=False),
    "gqa_rep2": Case(h=4, kvh=2),
    "mla_widths": Case(dk=48, dv=32),
    # a query block wider than a kv block: for kv block 1 the first query
    # block's rows 0-127 see none of its keys
    "q_block_wider": Case(s=512, blocks=(256, 128)),
    "kv_block_wider": Case(s=512, blocks=(128, 256)),
    "four_blocks_non_causal": Case(s=512, causal=False),
    "default": Case(s=512, precision="default"),
    "default_gqa_mla": Case(h=4, kvh=2, dk=48, dv=32, precision="default"),
}
# max |kernel − oracle| over max |oracle|
TOLERANCE = {"highest": 1e-5, "default": 3e-2}


def _inputs(c: Case, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (c.b, c.h, c.s, c.dk))
    k = jax.random.normal(ks[1], (c.b, c.kvh, c.s, c.dk))
    v = jax.random.normal(ks[2], (c.b, c.kvh, c.s, c.dv))
    ct = jax.random.normal(ks[3], (c.b, c.h, c.s, c.dv))
    return q, k, v, ct


def _oracle(c: Case):
    """``reference_attention`` in the kernel's (B, H, S, D) layout."""
    pos = jnp.broadcast_to(jnp.arange(c.s)[None], (c.b, c.s))
    t = lambda x: jnp.swapaxes(x, 1, 2)

    def f(q, k, v):
        return t(reference_attention(t(q), t(k), t(v), q_pos=pos, kv_pos=pos,
                                     causal=c.causal))
    return f


def _kernel(c: Case):
    return lambda q, k, v: fa.flash_attention(q, k, v, causal=c.causal, blocks=c.blocks)


def _close(got, want, precision):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape and got.dtype == want.dtype
    gap = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert gap < TOLERANCE[precision], gap


@pytest.mark.parametrize("name", list(CASES))
def test_forward_matches_the_oracle(name):
    c = CASES[name]
    q, k, v, _ = _inputs(c)
    with jax.default_matmul_precision(c.precision):
        got = jax.jit(_kernel(c))(q, k, v)
        want = jax.jit(_oracle(c))(q, k, v)
    _close(got, want, c.precision)


@pytest.mark.parametrize("name", list(CASES))
def test_vjp_matches_the_oracle(name):
    """dq, dk and dv of the kernel's custom vjp against ``jax.vjp`` of the
    oracle, with the same cotangent."""
    c = CASES[name]
    q, k, v, ct = _inputs(c, seed=1)

    def grads(f):
        _, vjp = jax.vjp(f, q, k, v)
        return vjp(ct)

    with jax.default_matmul_precision(c.precision):
        got = jax.jit(lambda: grads(_kernel(c)))()
        want = jax.jit(lambda: grads(_oracle(c)))()
    for g, w in zip(got, want):
        _close(g, w, c.precision)


@pytest.mark.parametrize("precision,mxu", [
    (None, jnp.bfloat16), ("default", jnp.bfloat16), ("bfloat16", jnp.bfloat16),
    ("high", jnp.float32), ("highest", jnp.float32), ("float32", jnp.float32),
])
def test_products_run_at_the_traced_precision(precision, mxu):
    """bfloat16 operands where the setting is XLA's default single pass,
    float32 wherever more is asked: never less than the setting."""
    with jax.default_matmul_precision(precision):
        assert fa._mxu_dtype() == mxu


def test_default_precision_rounds_the_operands():
    """At ``default`` the kernel's forward is the oracle's on bfloat16
    operands (to float32 rounding), not the float32 oracle's."""
    c = dataclasses.replace(CASES["causal"], precision="default")
    q, k, v, _ = _inputs(c)
    with jax.default_matmul_precision("default"):
        got = jax.jit(_kernel(c))(q, k, v)
    r = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        rounded = jax.jit(_oracle(c))(r(q), r(k), r(v))
        exact = jax.jit(_oracle(c))(q, k, v)
    gap = lambda a: float(jnp.max(jnp.abs(got - a)))
    assert gap(rounded) < gap(exact) / 2


@pytest.mark.parametrize("sq,skv,kvh", [
    (200, 200, 4),   # ragged: no block of 128 or more tiles S
    (256, 512, 4),   # self-attention: as many keys as queries
    (256, 256, 3),   # 4 query heads do not group over 3 kv heads
])
def test_shapes_the_kernel_refuses(sq, skv, kvh):
    kv = jnp.zeros((1, kvh, skv, 64))
    with pytest.raises(ValueError):
        fa.flash_attention(jnp.zeros((1, 4, sq, 64)), kv, kv, causal=True)


# --------------------------------------------------------------------------
# which path the layers take
# --------------------------------------------------------------------------

def _mla(**kw):
    return MLAttention(d_model=64, n_heads=2, q_lora_rank=None, kv_lora_rank=32,
                       qk_nope_dim=32, qk_rope_dim=16, v_head_dim=32, **kw)


def _gqa(**kw):
    return Attention(d_model=64, n_heads=4, n_kv_heads=2, qkv_bias=True, **kw)


PATHS = {
    # name: (layer, S, does the kernel take it)
    "tiles": (_gqa(), 256, True),
    "non_causal": (_gqa(causal=False), 256, True),
    "mla": (_mla(), 256, True),
    "window": (_gqa(window=64), 256, False),
    "logit_softcap": (_gqa(logit_softcap=30.0), 256, False),
    "ragged": (_gqa(), 200, False),
    "mla_ragged": (_mla(), 200, False),
    "under_a_mesh": (_gqa(), 256, False),
}


@pytest.mark.parametrize("name", list(PATHS))
def test_layers_pick_the_kernel_only_where_it_fits(name, monkeypatch):
    """On a TPU (faked) the layers call the kernel for self-attention whose
    S tiles, with no window, no soft-cap and no mesh; everything else takes
    the XLA path, and gives the same output as without a TPU."""
    layer, s, takes = PATHS[name]
    params = layer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, s, 64))
    xla_out = layer(params, x)

    calls = []

    def spy(q, k, v, **kw):
        calls.append(q.shape)
        return _xla_path(q, k, v, **kw)

    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    monkeypatch.setattr(attention, "kernel_attention", spy)
    if name == "under_a_mesh":
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        with sharding.use_mesh(mesh):
            out = layer(params, x)
    else:
        out = layer(params, x)
    assert bool(calls) == takes
    np.testing.assert_allclose(out, xla_out, rtol=1e-5, atol=1e-5)


def _xla_path(q, k, v, *, causal=True, scale=None):
    """The oracle in the layers' layout, standing in for the kernel."""
    b, s = q.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    if v.shape[-1] != q.shape[-1]:
        pad = q.shape[-1] - v.shape[-1]
        vp = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, pad)))
        out = reference_attention(q, k, vp, q_pos=pos, kv_pos=pos, causal=causal, scale=scale)
        return out[..., : v.shape[-1]]
    return reference_attention(q, k, v, q_pos=pos, kv_pos=pos, causal=causal, scale=scale)


@pytest.mark.parametrize("name", ["tiles", "non_causal", "mla"])
def test_layers_on_the_kernel_match_the_xla_path(name, monkeypatch):
    """The layers' kernel path (the kernel in interpret mode, in place of a
    TPU) against their XLA path: output and the parameters' gradients,
    GQA and MLA's unpadded v included."""
    layer, s, _ = PATHS[name]
    params = layer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, s, 64))
    ct = jax.random.normal(jax.random.PRNGKey(2), (2, s, 64))

    def run():
        with jax.default_matmul_precision("highest"):
            out, vjp = jax.vjp(lambda p: layer(p, x), params)
            return out, vjp(ct)[0]

    want = run()
    monkeypatch.setattr(fa, "engages", lambda s: fa.block_sizes(s, 0) is not None)
    calls = []
    real = attention.kernel_attention
    monkeypatch.setattr(attention, "kernel_attention",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    got = run()
    assert calls
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)
