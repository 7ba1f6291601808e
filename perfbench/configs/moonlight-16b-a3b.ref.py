"""Plain reference of Moonlight-16B-A3B DFA training on one chip's share,
in jax.numpy.

Independent of the program: it imports nothing of ``repro`` and takes
nothing the program made.  It draws the weights and the fixed feedback
matrices from the seed by the initialisers the program documents, and runs
the published DeepSeek-V3 block (pre-norm, RMSNorm with eps 1e-5):

* multi-head latent attention with no query low-rank: q = x·W_q (16 heads
  of 128 nope + 64 rope), a kv latent c = RMSNorm((x·W_kv)[:512]) with a
  shared rope key (x·W_kv)[512:], k_nope = c·W_k, v = c·W_v (128 a head),
  rotary with θ = 5e4 on the half-split pairs of the rope parts, causal
  softmax at scale 1/√192, output x·W_o;
* the first layer's SwiGLU FFN of width 11264;
* the MoE of the other layers: router logits over all 64 experts,
  sigmoid scores, the top 6 chosen by score plus the selection bias, their
  weights the chosen scores normalised to sum 1 and scaled by 2.446; only
  the experts held here (0–7) are computed, each densely over every token
  and masked by its routing weight (0 where the token did not choose it);
  plus the 2 shared experts (one SwiGLU of width 2816) on every token;
* an untied unembedding over the 20480-row vocabulary slice.

``train_reference`` trains three steps of Direct Feedback Alignment with
SGD and momentum, as ``qwen1.5-0.5b.ref.py`` does: the head gets its exact
gradient; e = ∂L/∂x_final is projected through each layer's fixed feedback
matrix, δ = e·Bᵀ, exactly and without photonic noise; each layer's
weights get the vjp of that layer alone at its own input; the token table
gets the embedding's vjp of e·B_embedᵀ.  The selection bias gets no
gradient.

Matmuls run at ``highest`` precision.  ``dtype`` ("float32" or
"bfloat16") is the storage and compute type of weights and activations;
norms, router scores and attention scores are float32 in either.  To fit
a 16 GB chip at 2 × 8192 tokens, attention runs in blocks of queries and
the experts in blocks of tokens, each rematerialised in the vjp, and the
head in blocks of rows.
"""

from __future__ import annotations

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import refkit  # noqa: E402

HEAD_ROWS = 1024    # tokens per block of the head
Q_BLOCK = 1024      # queries per block of attention
TOKEN_BLOCK = 2048  # tokens per block of the experts
BIAS_STD = 0.05     # the selection bias's init scale (the config's ``assumed``)


def _dims(c):
    lo, hi = c["experts_held"]
    return dict(d=c["hidden_size"], f=c["intermediate_size"], fe=c["moe_intermediate_size"],
                v=c["vocab_size"], n_layers=c["num_hidden_layers"],
                n_dense=c["first_k_dense_replace"], heads=c["num_attention_heads"],
                nope=c["qk_nope_head_dim"], rope=c["qk_rope_head_dim"],
                vd=c["v_head_dim"], r=c["kv_lora_rank"], eps=c["rms_norm_eps"],
                theta=float(c["rope_theta"]), e=c["router_outputs"], lo=lo, hi=hi,
                k=c["num_experts_per_tok"], shared=c["n_shared_experts"],
                scale=c["routed_scaling_factor"])


def _normal(key, shape, std):
    return jax.random.normal(key, shape) * std


def _linear(key, fan_in, fan_out):
    return _normal(refkit.fold_name(key, "w"), (fan_in, fan_out), 1.0 / np.sqrt(fan_in))


def _swiglu_init(key, d, f):
    fn = refkit.fold_name
    return {"gate": {"w": _linear(fn(key, "gate"), d, f)},
            "up": {"w": _linear(fn(key, "up"), d, f)},
            "down": {"w": _linear(fn(key, "down"), f, d)}}


@functools.partial(jax.jit, static_argnames=("c", "dtype"))
def _init(root, c, dtype):
    c = dict(c)
    d, h = c["d"], c["heads"]
    fn = refkit.fold_name

    def attn(k):
        return {"q": {"w": _linear(fn(k, "q"), d, h * (c["nope"] + c["rope"]))},
                "kv_down": {"w": _linear(fn(k, "kv_down"), d, c["r"] + c["rope"])},
                "kv_norm_scale": jnp.ones((c["r"],)),
                "k_up": {"w": _linear(fn(k, "k_up"), c["r"], h * c["nope"])},
                "v_up": {"w": _linear(fn(k, "v_up"), c["r"], h * c["vd"])},
                "o": {"w": _linear(fn(k, "o"), h * c["vd"], d)}}

    def block(k, ffn):
        return {"norm1": {"scale": jnp.ones((d,))}, "attn": attn(fn(k, "attn")),
                "norm2": {"scale": jnp.ones((d,))}, "ffn": ffn(fn(k, "ffn"))}

    def moe(k):
        keys = jax.random.split(fn(k, "experts"), c["e"])[c["lo"]:c["hi"]]
        return {"router": {"w": _linear(fn(k, "router"), d, c["e"]),
                           "bias": _normal(fn(k, "select_bias"), (c["e"],), BIAS_STD)},
                "experts": jax.vmap(lambda kk: _swiglu_init(kk, d, c["fe"]))(keys),
                "shared": _swiglu_init(fn(k, "shared"), d, c["fe"] * c["shared"])}

    n_moe = c["n_layers"] - c["n_dense"]
    params = {
        "embed": {"tok": {"table": _normal(fn(root, "tok"), (c["v"], d), 0.02)}},
        "dense": jax.vmap(lambda k: block(k, lambda kk: _swiglu_init(kk, d, c["f"])))(
            jax.random.split(fn(root, "dense"), c["n_dense"])),
        "blocks": jax.vmap(lambda k: block(k, moe))(
            jax.random.split(fn(root, "blocks"), n_moe)),
        "head": {"norm": {"scale": jnp.ones((d,))},
                 "out": {"w": _linear(fn(root, "out"), d, c["v"])}},
    }
    fk = fn(root, "feedback")

    def feedback(k, n):
        keys = jax.random.split(fn(k, "layers"), n)
        return jax.vmap(lambda kk: jax.random.normal(kk, (d, d)) * (1.0 / jnp.sqrt(d)))(keys)

    fb = {"dense": feedback(fn(fk, "dense"), c["n_dense"]),
          "blocks": feedback(fn(fk, "blocks"), n_moe),
          "embed": feedback(fn(fk, "embed"), 1)[0]}
    cast = lambda t: jax.tree_util.tree_map(lambda x: x.astype(dtype), t)  # noqa: E731
    return cast(params), cast(fb)


def _rms(x, eps):
    x32 = x.astype(jnp.float32)
    return x32 * (jnp.mean(jnp.square(x32), -1, keepdims=True) + eps) ** -0.5


def _rmsnorm(x, scale, eps):
    return (_rms(x, eps) * scale.astype(jnp.float32)).astype(x.dtype)


def _rotary(x, pos, theta):
    """Half-split rotation of x (..., S, H, D) at positions pos (S,)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


def _swiglu(p, x):
    g = x @ p["gate"]["w"]
    return ((g * jax.nn.sigmoid(g)) * (x @ p["up"]["w"])) @ p["down"]["w"]


def _mla(c, a, x):
    b, s, _ = x.shape
    h, nope, rope = c["heads"], c["nope"], c["rope"]
    pos = jnp.arange(s)
    q = (x @ a["q"]["w"]).reshape(b, s, h, nope + rope)
    kv = x @ a["kv_down"]["w"]
    lat = (_rms(kv[..., :c["r"]], c["eps"]) * a["kv_norm_scale"].astype(jnp.float32)).astype(x.dtype)
    k_rope = _rotary(kv[..., c["r"]:][:, :, None, :], pos, c["theta"])       # (b, s, 1, rope)
    k_nope = (lat @ a["k_up"]["w"]).reshape(b, s, h, nope)
    v = (lat @ a["v_up"]["w"]).reshape(b, s, h, c["vd"])
    q = jnp.concatenate([q[..., :nope], _rotary(q[..., nope:], pos, c["theta"])], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (b, s, h, rope))], -1)
    qf, kf, vf = (t.astype(jnp.float32) for t in (q, k, v))
    scale = 1.0 / np.sqrt(nope + rope)

    qn = min(Q_BLOCK, s)

    @jax.checkpoint
    def rows(q0):
        qb = jax.lax.dynamic_slice_in_dim(qf, q0, qn, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, kf) * scale
        causal = (q0 + jnp.arange(qn))[:, None] >= jnp.arange(s)[None, :]
        w = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", w, vf)

    blocks = jax.lax.map(rows, jnp.arange(0, s, qn))                       # (n, b, Q, h, vd)
    att = jnp.moveaxis(blocks, 0, 1).reshape(b, s, h * c["vd"]).astype(x.dtype)
    return att @ a["o"]["w"]


def _moe(c, p, x):
    """The held experts' share of the MoE plus the shared experts."""
    b, s, d = x.shape
    xt = x.reshape(-1, d)
    logits = xt.astype(jnp.float32) @ p["router"]["w"].astype(jnp.float32)
    scores = jax.nn.sigmoid(logits)
    choice = scores + jax.lax.stop_gradient(p["router"]["bias"].astype(jnp.float32))
    _, idx = jax.lax.top_k(choice, c["k"])
    w = jnp.take_along_axis(scores, idx, -1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * c["scale"]
    # each held expert's routing weight for every token (0: not chosen)
    held = jnp.arange(c["lo"], c["hi"])
    comb = jnp.sum(jnp.where(idx[:, :, None] == held[None, None, :], w[:, :, None], 0.0), 1)

    @jax.checkpoint
    def tokens(args):
        xb, cb = args
        y = jnp.zeros(xb.shape, jnp.float32)
        for e in range(c["hi"] - c["lo"]):
            pe = jax.tree_util.tree_map(lambda t, e=e: t[e], p["experts"])
            y = y + cb[:, e, None] * _swiglu(pe, xb).astype(jnp.float32)
        return y

    tn = min(TOKEN_BLOCK, xt.shape[0])
    n = xt.shape[0] // tn
    routed = jax.lax.map(tokens, (xt.reshape(n, tn, d), comb.reshape(n, tn, -1)))
    y = routed.reshape(-1, d).astype(x.dtype) + _swiglu(p["shared"], xt)
    return y.reshape(b, s, d)


def _block(c, p, x, dense):
    x = x + _mla(c, p["attn"], _rmsnorm(x, p["norm1"]["scale"], c["eps"]))
    h = _rmsnorm(x, p["norm2"]["scale"], c["eps"])
    return x + (_swiglu(p["ffn"], h) if dense else _moe(c, p["ffn"], h))


@functools.partial(jax.jit, static_argnames=("c", "dense"))
def _block_fwd(c, p, x, dense):
    return _block(dict(c), p, x, dense)


@functools.partial(jax.jit, static_argnames=("c", "dense"))
def _block_grads(c, p, x, e, bmat, dense):
    """The layer's weight gradient for the cotangent δ = e·Bᵀ."""
    delta = (e.reshape(-1, e.shape[-1]) @ bmat.T).reshape(x.shape)
    _, vjp = jax.vjp(lambda pp: _block(dict(c), pp, x, dense), p)
    return vjp(delta.astype(x.dtype))[0]


@functools.partial(jax.jit, static_argnames=("c", "n_tokens"))
def _head_rows(c, head, x_rows, labels, n_tokens):
    """Loss share, head gradient and error of a block of rows."""
    c = dict(c)

    def loss(hp, xr):
        h = _rmsnorm(xr, hp["norm"]["scale"], c["eps"])
        logits = (h @ hp["out"]["w"]).astype(jnp.float32)
        nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
        return jnp.sum(nll) / n_tokens

    val, vjp = jax.vjp(loss, head, x_rows)
    g_head, e = vjp(jnp.float32(1.0))
    return val, g_head, e


@jax.jit
def _embed_grad(table, tokens, e, bmat):
    delta = e.reshape(-1, e.shape[-1]) @ bmat.T
    return jnp.zeros_like(table).at[tokens.reshape(-1)].add(delta.astype(table.dtype))


def _layer(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


def _stacks(c):
    return (("dense", c["n_dense"], True), ("blocks", c["n_layers"] - c["n_dense"], False))


def make_grads_fn(c, fb):
    ch = tuple(sorted(c.items()))

    def grads_fn(params, batch):
        tokens = jnp.asarray(batch["tokens"])
        labels = jnp.asarray(batch["labels"])
        b, s = tokens.shape
        x = params["embed"]["tok"]["table"][tokens]
        xs = {}
        for name, n, dense in _stacks(c):
            xs[name] = []
            for i in range(n):
                xs[name].append(x)
                x = _block_fwd(ch, _layer(params[name], i), x, dense)
        rows = x.reshape(b * s, -1)
        lab = labels.reshape(-1)
        loss, g_head, es = 0.0, None, []
        for r0 in range(0, b * s, HEAD_ROWS):
            val, g, e = _head_rows(ch, params["head"], rows[r0:r0 + HEAD_ROWS],
                                   lab[r0:r0 + HEAD_ROWS], b * s)
            loss = loss + val
            g_head = g if g_head is None else jax.tree_util.tree_map(jnp.add, g_head, g)
            es.append(e)
        e = jnp.concatenate(es).reshape(x.shape)
        del es, rows
        grads = {"head": g_head}
        for name, n, dense in _stacks(c):
            g_layers = [_block_grads(ch, _layer(params[name], i), xs[name][i], e,
                                     fb[name][i], dense) for i in range(n)]
            grads[name] = jax.tree_util.tree_map(lambda *g: jnp.stack(g), *g_layers)
        del xs
        grads["embed"] = {"tok": {"table": _embed_grad(params["embed"]["tok"]["table"],
                                                       tokens, e, fb["embed"])}}
        return loss, grads

    return grads_fn


def train_reference(config: dict, batches: list, seed: int, prog: dict, *,
                    dtype: str = "float32", steps: int = 3) -> dict:
    """Three DFA steps from the weights of ``seed`` over ``batches``."""
    c = _dims(config)
    dt = jnp.dtype(dtype)
    with jax.default_matmul_precision("highest"):
        params, fb = _init(jax.random.PRNGKey(seed), tuple(sorted(c.items())), dt)
        return refkit.sgdm_reference(params, make_grads_fn(c, fb), batches, prog, steps)
