"""The expert layer, MLA without a query low-rank, and Moonlight's dense +
MoE stacks, at smoke size on the CPU (the grouped-matmul kernel runs in
the Pallas interpreter)."""

import dataclasses
import importlib.util
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, configs
from repro.kernels import moe_gmm
from repro.nn.attention import MLAttention
from repro.nn.linear import GatedMLP
from repro.nn.moe import MoE
from repro.train.trainer import Trainer

ROOT = Path(__file__).resolve().parents[1]
D, F, E, K = 16, 32, 16, 4


def _moe(**kw):
    base = dict(d_model=D, d_ff_expert=F, n_experts=E, top_k=K, n_shared_experts=2,
                scoring="sigmoid", routed_scale=2.446)
    return MoE(**{**base, **kw})


def _x(t=24, key=1):
    return jax.random.normal(jax.random.PRNGKey(key), (2, t // 2, D))


def _swiglu(p, x):
    g = x @ p["gate"]["w"]
    return (g * jax.nn.sigmoid(g) * (x @ p["up"]["w"])) @ p["down"]["w"]


def _plain(m, p, x):
    """The layer written out: every held expert densely on every token,
    masked by its routing weight, plus the shared experts."""
    xt = x.reshape(-1, D)
    scores = jax.nn.sigmoid(xt @ p["router"]["w"])
    _, idx = jax.lax.top_k(scores + p["router"]["bias"], K)
    w = jnp.take_along_axis(scores, idx, -1)
    w = w / w.sum(-1, keepdims=True) * m.routed_scale
    lo, hi = m.held
    y = _swiglu(p["shared"], xt)
    for j, e in enumerate(range(lo, hi)):
        comb = jnp.sum(jnp.where(idx == e, w, 0.0), -1)
        pe = jax.tree_util.tree_map(lambda a, j=j: a[j], p["experts"])
        y = y + comb[:, None] * _swiglu(pe, xt)
    return y.reshape(x.shape)


def test_mla_without_q_low_rank_matches_plain_forward():
    mla = MLAttention(d_model=32, n_heads=4, q_lora_rank=None, kv_lora_rank=16,
                      qk_nope_dim=8, qk_rope_dim=8, v_head_dim=8, rope_theta=5e4,
                      norm_eps=1e-5)
    p = mla.init(jax.random.PRNGKey(0))
    assert "q" in p and "q_down" not in p and p["q"]["w"].shape == (32, 4 * 16)
    b, s = 2, 10
    x = jax.random.normal(jax.random.PRNGKey(1), (b, s, 32))
    y = mla(p, x)

    pos = jnp.arange(s, dtype=jnp.float32)
    freqs = 5e4 ** (-jnp.arange(4, dtype=jnp.float32) / 4)
    cos, sin = jnp.cos(pos[:, None] * freqs), jnp.sin(pos[:, None] * freqs)

    def rot(t):  # (b, s, h, 8), half-split pairs
        c, sn = cos[:, None, :], sin[:, None, :]
        return jnp.concatenate([t[..., :4] * c - t[..., 4:] * sn,
                                t[..., 4:] * c + t[..., :4] * sn], -1)

    q = (x @ p["q"]["w"]).reshape(b, s, 4, 16)
    kv = x @ p["kv_down"]["w"]
    lat = kv[..., :16] / jnp.sqrt(jnp.mean(kv[..., :16] ** 2, -1, keepdims=True) + 1e-5)
    lat = lat * p["kv_norm_scale"]
    k_rope = rot(kv[..., 16:][:, :, None, :])
    k = jnp.concatenate([(lat @ p["k_up"]["w"]).reshape(b, s, 4, 8),
                         jnp.broadcast_to(k_rope, (b, s, 4, 8))], -1)
    v = (lat @ p["v_up"]["w"]).reshape(b, s, 4, 8)
    q = jnp.concatenate([q[..., :8], rot(q[..., 8:])], -1)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(16)
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -1e30)
    att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v).reshape(b, s, 32)
    np.testing.assert_allclose(np.asarray(y), np.asarray(att @ p["o"]["w"]),
                               rtol=1e-4, atol=1e-5)


def test_sigmoid_bias_selects_but_does_not_weigh():
    m = _moe()
    p = m.init(jax.random.PRNGKey(0))
    xt = _x().reshape(-1, D)
    _, scores, chosen0, w0 = m.route({**p, "router": {**p["router"], "bias": jnp.zeros(E)}}, xt)
    bias = jnp.zeros(E).at[jnp.arange(0, E, 2)].set(1.0)   # even experts win
    _, scores_b, chosen, w = m.route({**p, "router": {**p["router"], "bias": bias}}, xt)
    np.testing.assert_array_equal(np.asarray(scores), np.asarray(scores_b))
    assert not np.array_equal(np.asarray(chosen0), np.asarray(chosen))
    assert np.all(np.asarray(chosen) % 2 == 0)
    # weights: the chosen scores without the bias, normalised, times the scale
    want = jnp.take_along_axis(scores, chosen, -1)
    want = want / want.sum(-1, keepdims=True) * 2.446
    np.testing.assert_allclose(np.asarray(w), np.asarray(want), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 2.446, rtol=1e-6)
    # the bias is not trained: its gradient is stopped
    g = jax.grad(lambda pp: jnp.sum(m.route(pp, xt)[3] * scores[:, :K]))(p)
    assert float(jnp.max(jnp.abs(g["router"]["bias"]))) == 0.0
    assert float(jnp.max(jnp.abs(g["router"]["w"]))) > 0.0


def test_softmax_routing_keeps_aux_losses():
    """qwen2-moe's routing on the same path: softmax scores, Switch
    load-balancing and z-losses; no selection bias, scale 1."""
    m = _moe(scoring="softmax", routed_scale=1.0)
    p = m.init(jax.random.PRNGKey(0))
    y, aux = m(p, _x())
    assert "bias" not in p["router"]
    assert float(aux["lb_loss"]) > 0 and float(aux["z_loss"]) > 0
    _, _, _, w = m.route(p, _x().reshape(-1, D))
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-6)


def test_shares_add_up_to_the_uncut_layer():
    """Four chips' shares of 16 experts: each share's routed part, plus the
    shared experts counted once, equals the layer that holds them all."""
    whole = _moe()
    key = jax.random.PRNGKey(0)
    p = whole.init(key)
    x = _x()
    y_whole, aux = whole(p, x)
    assert float(aux["routed_here"]) == 1.0
    shared = GatedMLP(D, 2 * F)(p["shared"], x)
    total = shared
    rows = 0.0
    for lo in range(0, E, 4):
        share = dataclasses.replace(whole, experts_held=(lo, lo + 4))
        ps = share.init(key)
        for name in ("gate", "up", "down"):
            np.testing.assert_array_equal(np.asarray(ps["experts"][name]["w"]),
                                          np.asarray(p["experts"][name]["w"][lo:lo + 4]))
        y, aux = share(ps, x)
        total = total + (y - shared)
        rows += float(aux["rows_routed"])
    np.testing.assert_allclose(np.asarray(total), np.asarray(y_whole), rtol=1e-4, atol=1e-5)
    assert rows == x.shape[0] * x.shape[1] * K


def test_no_token_dropped_under_adversarial_routing():
    """A bias that sends every token to the experts held here: all T·K
    assignments land on 4 experts, and none is dropped."""
    m = _moe(experts_held=(0, 4))
    p = m.init(jax.random.PRNGKey(0))
    p["router"]["bias"] = jnp.zeros(E).at[:4].set(10.0)
    x = _x(t=40)
    y, aux = m(p, x)
    assert float(aux["routed_here"]) == 1.0
    assert float(aux["rows_routed"]) == 40 * K
    np.testing.assert_allclose(np.asarray(y), np.asarray(_plain(m, p, x)), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("sizes", [[5, 0, 20, 3], [0, 0, 0, 0], [0, 9, 0, 0]],
                         ids=["ragged", "none-routed", "one-group"])
def test_grouped_kernel_matches_masked_dense(sizes):
    m, c, n = 40, 16, 24
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (m, c))
    w = jax.random.normal(jax.random.fold_in(key, 1), (len(sizes), c, n))
    gs = jnp.array(sizes, jnp.int32)
    g = jax.random.normal(jax.random.fold_in(key, 2), (m, n))
    routed = sum(sizes)   # rows past these are not results
    out, vjp = jax.vjp(lambda a, w: moe_gmm.grouped_matmul(a, w, gs), a, w)
    ref, vjp_ref = jax.vjp(lambda a, w: moe_gmm.grouped_matmul_reference(a, w, gs), a, w)
    np.testing.assert_allclose(np.asarray(out)[:routed], np.asarray(ref)[:routed],
                               rtol=1e-5, atol=1e-5)
    (da, dw), (ra, rw) = vjp(g), vjp_ref(g)
    np.testing.assert_allclose(np.asarray(da)[:routed], np.asarray(ra)[:routed],
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(rw), rtol=1e-5, atol=1e-4)


def test_rows_past_the_routed_ones_are_never_read(monkeypatch):
    """The kernel leaves the rows past the routed ones unwritten; poisoned
    with NaN there, the layer's output and gradients still equal the plain
    formula, so nothing downstream reads them."""
    call = moe_gmm._gmm_call

    def poisoned(lhs, rhs, group_sizes, **kw):
        out = call(lhs, rhs, group_sizes, **kw)
        rows = jnp.arange(out.shape[0])[:, None]
        return jnp.where(rows < jnp.sum(group_sizes), out, jnp.nan)

    monkeypatch.setattr(moe_gmm, "_gmm_call", poisoned)
    m = _moe(experts_held=(4, 8))
    p = m.init(jax.random.PRNGKey(0))
    x = _x()
    y, aux = m(p, x)
    assert 0.0 < float(aux["routed_here"]) < 1.0
    np.testing.assert_allclose(np.asarray(y), np.asarray(_plain(m, p, x)), rtol=1e-4, atol=1e-5)
    g = jax.grad(lambda pp, xx: jnp.sum(m(pp, xx)[0] ** 2), argnums=(0, 1))(p, x)
    g_ref = jax.grad(lambda pp, xx: jnp.sum(_plain(m, pp, xx) ** 2), argnums=(0, 1))(p, x)
    for got, want in zip(jax.tree_util.tree_leaves(g), jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def _load_ref():
    path = ROOT / "perfbench" / "configs" / "moonlight-16b-a3b.ref.py"
    spec = importlib.util.spec_from_file_location("moonlight_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _batch(b=2, s=16, vocab=128, seed=0):
    toks = np.random.default_rng(seed).integers(0, vocab, (b, s + 1)).astype(np.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def test_moonlight_fit_step_matches_reference():
    """One ``Session.fit`` DFA step of the smoke model against the plain
    reference the benchmark keeps: same weights and feedback from the seed,
    the loss and the first gradient (the momentum after one step)."""
    config = json.loads((ROOT / "perfbench" / "tests" / "data" / "moonlight-smoke.json").read_text())
    ref = _load_ref()
    session = api.build_session(arch="moonlight-16b-a3b", smoke=True, algo="dfa",
                                hardware="emu_ideal", backend="emu")
    trainer = session.trainer
    key = jax.random.PRNGKey(7)
    trainer.init_state = lambda _key=None: Trainer.init_state(trainer, key)
    batch = _batch()
    state, metrics = session.fit(lambda step: batch, 1, verbose=False)

    c = ref._dims(config)
    with jax.default_matmul_precision("highest"):
        params, fb = ref._init(key, tuple(sorted(c.items())), jnp.float32)
        loss, grads = ref.make_grads_fn(c, fb)(params, batch)
    np.testing.assert_allclose(float(metrics["loss"]), float(loss), rtol=1e-5)
    got = jax.tree_util.tree_flatten_with_path(state["opt"]["mom"])[0]
    want = dict(jax.tree_util.tree_flatten_with_path(grads)[0])
    assert len(got) == len(want)
    for path, g in got:
        r = want[path]
        scale = float(jnp.max(jnp.abs(r)))
        if "bias" in jax.tree_util.keystr(path):
            assert scale == 0.0 and float(jnp.max(jnp.abs(g))) == 0.0
            continue
        err = float(jnp.max(jnp.abs(g - r)))
        assert err <= 1e-4 * scale + 1e-7, (jax.tree_util.keystr(path), err, scale)


def test_decode_and_prefill_through_dense_and_moe_stacks():
    model = configs.get("moonlight-16b-a3b").make_smoke()
    params = model.init(jax.random.PRNGKey(0))
    toks = jnp.asarray(_batch(b=2, s=8)["tokens"])
    @jax.jit
    def forward(params, toks):
        x_final, _, _ = model.run_segments(params, model.embed(params, {"tokens": toks}))
        return model.head_logits(params, x_final, None)

    full = forward(params, toks)
    caches = model.init_caches(2, 16)
    assert set(caches) == {"dense", "blocks"}
    steps = []
    c = caches
    decode = jax.jit(model.decode_step)
    for t in range(8):
        logits, c = decode(params, toks[:, t:t + 1], c, jnp.full((2,), t, jnp.int32))
        steps.append(logits)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(steps, 1)), np.asarray(full),
                               rtol=1e-4, atol=1e-4)
    pre, _ = jax.jit(model.prefill_step)(params, toks, caches, jnp.zeros((2,), jnp.int32),
                                         jnp.full((2,), 8, jnp.int32))
    np.testing.assert_allclose(np.asarray(pre), np.asarray(full), rtol=1e-4, atol=1e-4)


def test_moe_counter_in_an_observed_fit():
    session = api.build_session(arch="moonlight-16b-a3b", smoke=True, algo="dfa",
                                log_every=2, observe=True)
    batch = _batch()
    _, metrics = session.fit(lambda step: batch, 2, verbose=False)
    assert 0.0 < float(metrics["moe_routed_here"]) < 1.0
    moe = [e for e in session.observer.trace.events if e["name"] == "moe"]
    assert len(moe) == 1 and moe[0]["ph"] == "C"
    args = moe[0]["args"]
    assert set(args) == {"moe_routed_here", "moe_load_max_over_mean", "moe_rows_routed"}
    assert set(args) == set(session.model.counters()["moe"])
    assert args["moe_rows_routed"] == pytest.approx(args["moe_routed_here"] * 2 * 16 * 4)
