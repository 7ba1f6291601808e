"""§Perf feature correctness: every optimisation must be semantics-
preserving (or its documented trade explicit)."""

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs, nn
from repro.algos import dfa
from repro.models.mamba import MambaConfig, MambaLM
from repro.utils.tree import tree_allclose


def _moe_dense(moe, p, x):
    """The MoE written out: every expert densely on every token, weighted by
    its normalised top-k softmax score (0 where not chosen)."""
    xt = x.reshape(-1, x.shape[-1])
    scores = jax.nn.softmax(xt @ p["router"]["w"], -1)
    w, idx = jax.lax.top_k(scores, moe.top_k)
    w = w / w.sum(-1, keepdims=True)
    y = jnp.zeros_like(xt)
    for e in range(moe.n_experts):
        pe = jax.tree_util.tree_map(lambda a, e=e: a[e], p["experts"])
        g = xt @ pe["gate"]["w"]
        h = (g * jax.nn.sigmoid(g) * (xt @ pe["up"]["w"])) @ pe["down"]["w"]
        y = y + jnp.sum(jnp.where(idx == e, w, 0.0), -1)[:, None] * h
    return y.reshape(x.shape)


def test_moe_gather_equals_einsum_dispatch():
    """The sorted dispatch through the grouped kernel equals the dense
    per-expert formula, outputs and gradients."""
    moe = nn.MoE(d_model=16, d_ff_expert=32, n_experts=4, top_k=2)
    p = moe.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16))
    y1, _ = moe(p, x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(_moe_dense(moe, p, x)),
                               rtol=1e-5, atol=1e-6)
    g1 = jax.grad(lambda pp: jnp.sum(moe(pp, x)[0] ** 2))(p)
    g2 = jax.grad(lambda pp: jnp.sum(_moe_dense(moe, pp, x) ** 2))(p)
    assert tree_allclose(g1, g2, rtol=1e-4, atol=1e-5)


def test_moe_gather_equals_einsum_with_drops():
    """Where a capacity used to drop tokens (every token on one expert), no
    token is dropped: the layer still equals the dense formula."""
    moe = nn.MoE(d_model=16, d_ff_expert=32, n_experts=4, top_k=2)
    p = moe.init(jax.random.PRNGKey(0))
    p["router"]["w"] = p["router"]["w"].at[:, 0].add(100.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16)))
    y1, aux = moe(p, x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(_moe_dense(moe, p, x)),
                               rtol=1e-5, atol=1e-6)
    assert float(aux["rows_routed"]) == 64.0
    assert float(aux["load_max_over_mean"]) >= 2.0   # expert 0 takes every token


def test_mamba_split_proj_decode_parity():
    mb = nn.Mamba2Block(d_model=32, d_state=16, head_dim=16, chunk=8,
                        split_proj=True)
    p = mb.init(jax.random.PRNGKey(0))
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    full = mb(p, u)
    cache = mb.init_cache(2)
    outs = []
    for t in range(16):
        o, cache = mb.decode(p, u[:, t:t+1], cache, jnp.zeros((2,), jnp.int32) + t)
        outs.append(o)
    np.testing.assert_allclose(np.asarray(full),
                               np.asarray(jnp.concatenate(outs, 1)),
                               rtol=1e-4, atol=2e-5)


def test_vocab_padding_loss_invariant_to_pad_columns():
    """Padded logits are masked to -inf — CE over real labels unaffected by
    the pad region's parameters."""
    cfg = dict(name="t", n_layers=2, d_model=32, vocab_size=100,
               d_state=16, head_dim=16, chunk=8)
    m = MambaLM(MambaConfig(pad_vocab_to=128, **cfg))
    p = m.init(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32),
             "labels": jnp.ones((2, 16), jnp.int32)}
    loss1, _ = m.loss(p, batch)
    # perturb ONLY pad rows/cols
    p2 = jax.tree_util.tree_map(lambda x: x, p)
    p2["head"]["out"]["w"] = p["head"]["out"]["w"].at[:, 100:].add(7.0)
    loss2, _ = m.loss(p2, batch)
    np.testing.assert_allclose(float(loss1), float(loss2), rtol=1e-6)
    logits = m.head_logits(p, m.run_segments(p, m.embed(p, batch))[0], batch)
    assert logits.shape[-1] == 128
    assert float(logits[..., 100:].max()) < -1e29


def test_freeze_norms_zeroes_norm_grads_only():
    model = configs.get("qwen3-1.7b").make_smoke()
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32),
             "labels": jnp.ones((2, 16), jnp.int32)}
    cfg_f = dfa.DFAConfig(freeze_norms=True)
    fb = dfa.init_feedback(model, key, cfg_f)
    (_, _), g = dfa.value_and_grad(model, cfg_f)(params, fb, batch, key)
    # norm scales in blocks get exactly zero grads
    assert float(jnp.abs(g["blocks"]["norm1"]["scale"]).max()) == 0.0
    assert float(jnp.abs(g["blocks"]["norm2"]["scale"]).max()) == 0.0
    # non-norm params still train
    assert float(jnp.abs(g["blocks"]["attn"]["q"]["w"]).max()) > 0.0


def test_opt_variants_instantiate_and_train():
    """Every arch with a make_opt variant still runs a DFA step (reduced
    via eval_shape for the big ones: structure check only)."""
    for name in configs.ASSIGNED:
        arch = configs.get(name)
        if arch.make_opt is None:
            continue
        model = arch.make_opt(jnp.bfloat16)
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        assert len(jax.tree_util.tree_leaves(shapes)) > 0
