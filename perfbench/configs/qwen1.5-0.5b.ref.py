"""Plain reference of qwen1.5-0.5b DFA training, in jax.numpy.

Independent of the program: it imports nothing of ``repro`` and takes
nothing the program made.  It draws the weights and the fixed feedback
matrices from the seed by the initialisers the program documents, runs
the published architecture (pre-norm decoder, RMSNorm, rotary on the
half-split pairs with θ = 1e6, causal softmax attention with QKV bias,
SwiGLU, an untied unembedding).  ``train_reference`` trains three steps
of Direct Feedback Alignment with SGD and momentum:

* the head (final norm and unembedding) gets its exact gradient;
* the error e = ∂L/∂x_final is projected through each block's fixed
  feedback matrix, δ_k = e·B_kᵀ, exactly and without any photonic noise;
* each block's weights get the vjp of that block alone at its own input,
  with δ_k as the cotangent of its output; the token table gets the
  embedding's vjp of e·B_embedᵀ.

``serve_logits`` runs the plain forward over served sequences.

Training's matmuls run at ``highest`` precision.  The serving forward
runs at the precision the configuration states (``matmul_precision``:
JAX's ``default``, one bfloat16 pass with float32 accumulation on a TPU,
as the program runs), so that a served token that is not the reference's
first choice shows a departure from that precision and not the
precision itself.  ``dtype`` ("float32" or
"bfloat16") is the storage and compute type of weights and activations,
as the model's own ``dtype`` would be; norms and attention scores are
computed in float32 in either, as the model defines them.  The head runs
in blocks of rows and the blocks one at a time, so the whole step fits a
16 GB chip at 8 × 1024 tokens.
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

HEAD_ROWS = 1024  # tokens per block of the head
SERVE_BATCH = 8   # sequences per forward of the serving reference


def _dims(c):
    return dict(d=c["hidden_size"], f=c["intermediate_size"],
                v=c["vocab_size"], n_layers=c["num_hidden_layers"],
                heads=c["num_attention_heads"],
                kv=c["num_key_value_heads"], hd=c["head_dim"],
                eps=c["rms_norm_eps"], theta=c["rope_theta"])


def _normal(key, shape, std):
    return jax.random.normal(key, shape) * std


def _linear(key, fan_in, fan_out):
    return _normal(refkit.fold_name(key, "w"), (fan_in, fan_out),
                   1.0 / np.sqrt(fan_in))


@functools.partial(jax.jit, static_argnames=("c", "dtype"))
def _init(root, c, dtype):
    c = dict(c)
    d, f, v = c["d"], c["f"], c["v"]
    q_w, kv_w = c["heads"] * c["hd"], c["kv"] * c["hd"]
    fn = refkit.fold_name

    def block(k):
        ka, kf = fn(k, "attn"), fn(k, "ffn")
        return {
            "norm1": {"scale": jnp.ones((d,))},
            "attn": {
                "q": {"w": _linear(fn(ka, "q"), d, q_w), "b": jnp.zeros((q_w,))},
                "k": {"w": _linear(fn(ka, "k"), d, kv_w), "b": jnp.zeros((kv_w,))},
                "v": {"w": _linear(fn(ka, "v"), d, kv_w), "b": jnp.zeros((kv_w,))},
                "o": {"w": _linear(fn(ka, "o"), q_w, d)},
            },
            "norm2": {"scale": jnp.ones((d,))},
            "ffn": {"gate": {"w": _linear(fn(kf, "gate"), d, f)},
                    "up": {"w": _linear(fn(kf, "up"), d, f)},
                    "down": {"w": _linear(fn(kf, "down"), f, d)}},
        }

    params = {
        "embed": {"tok": {"table": _normal(fn(root, "tok"), (v, d), 0.02)}},
        "blocks": jax.vmap(block)(
            jax.random.split(fn(root, "blocks"), c["n_layers"])),
        "head": {"norm": {"scale": jnp.ones((d,))},
                 "out": {"w": _linear(fn(root, "out"), d, v)}},
    }
    fk = fn(root, "feedback")

    def feedback(k, n):
        keys = jax.random.split(fn(k, "layers"), n)
        return jax.vmap(lambda kk: jax.random.normal(kk, (d, d))
                        * (1.0 / jnp.sqrt(d)))(keys)

    fb = {"blocks": feedback(fn(fk, "blocks"), c["n_layers"]),
          "embed": feedback(fn(fk, "embed"), 1)[0]}
    cast = lambda t: jax.tree_util.tree_map(lambda x: x.astype(dtype), t)  # noqa: E731
    return cast(params), cast(fb)


def _rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * (jnp.mean(jnp.square(x32), -1, keepdims=True) + eps) ** -0.5
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rotary(x, theta):
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _block(c, p, x):
    b, s, _ = x.shape
    h = _rmsnorm(x, p["norm1"]["scale"], c["eps"])
    a = p["attn"]
    q = (h @ a["q"]["w"] + a["q"]["b"]).reshape(b, s, c["heads"], c["hd"])
    k = (h @ a["k"]["w"] + a["k"]["b"]).reshape(b, s, c["kv"], c["hd"])
    v = (h @ a["v"]["w"] + a["v"]["b"]).reshape(b, s, c["kv"], c["hd"])
    q, k = _rotary(q, c["theta"]), _rotary(k, c["theta"])
    rep = c["heads"] // c["kv"]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / np.sqrt(c["hd"])
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    att = jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(jnp.float32))
    att = att.astype(x.dtype).reshape(b, s, c["heads"] * c["hd"])
    x = x + att @ a["o"]["w"]
    h = _rmsnorm(x, p["norm2"]["scale"], c["eps"])
    ff = p["ffn"]
    g = h @ ff["gate"]["w"]
    return x + ((g * jax.nn.sigmoid(g)) * (h @ ff["up"]["w"])) @ ff["down"]["w"]


@functools.partial(jax.jit, static_argnames=("c",))
def _block_fwd(c, p, x):
    return _block(dict(c), p, x)


@functools.partial(jax.jit, static_argnames=("c",))
def _block_grads(c, p, x, e, bmat):
    """The block's weight gradient for the cotangent δ = e·Bᵀ."""
    delta = (e.reshape(-1, e.shape[-1]) @ bmat.T).reshape(x.shape)
    _, vjp = jax.vjp(lambda pp: _block(dict(c), pp, x), p)
    return vjp(delta.astype(x.dtype))[0]


@functools.partial(jax.jit, static_argnames=("c", "n_tokens"))
def _head_rows(c, head, x_rows, labels, n_tokens):
    """Loss share, head gradient and error of a block of rows."""
    c = dict(c)

    def loss(hp, xr):
        h = _rmsnorm(xr, hp["norm"]["scale"], c["eps"])
        logits = (h @ hp["out"]["w"]).astype(jnp.float32)
        nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, labels[:, None], -1)[:, 0]
        return jnp.sum(nll) / n_tokens

    val, vjp = jax.vjp(loss, head, x_rows)
    g_head, e = vjp(jnp.float32(1.0))
    return val, g_head, e


@jax.jit
def _embed_grad(table, tokens, e, bmat):
    delta = e.reshape(-1, e.shape[-1]) @ bmat.T
    return jnp.zeros_like(table).at[tokens.reshape(-1)].add(
        delta.astype(table.dtype))


def _layer(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


def make_grads_fn(c, fb):
    ch = tuple(sorted(c.items()))

    def grads_fn(params, batch):
        tokens = jnp.asarray(batch["tokens"])
        labels = jnp.asarray(batch["labels"])
        b, s = tokens.shape
        x = params["embed"]["tok"]["table"][tokens]
        xs = []
        for i in range(c["n_layers"]):
            xs.append(x)
            x = _block_fwd(ch, _layer(params["blocks"], i), x)
        rows = x.reshape(b * s, -1)
        lab = labels.reshape(-1)
        loss, g_head, es = 0.0, None, []
        for r0 in range(0, b * s, HEAD_ROWS):
            val, g, e = _head_rows(ch, params["head"], rows[r0:r0 + HEAD_ROWS],
                                   lab[r0:r0 + HEAD_ROWS], b * s)
            loss = loss + val
            g_head = g if g_head is None else jax.tree_util.tree_map(
                jnp.add, g_head, g)
            es.append(e)
        e = jnp.concatenate(es).reshape(x.shape)
        del es, rows
        g_blocks = [_block_grads(ch, _layer(params["blocks"], i), xs[i], e,
                                 fb["blocks"][i])
                    for i in range(c["n_layers"])]
        del xs
        g_blocks = jax.tree_util.tree_map(lambda *g: jnp.stack(g), *g_blocks)
        g_table = _embed_grad(params["embed"]["tok"]["table"], tokens, e,
                              fb["embed"])
        grads = {"embed": {"tok": {"table": g_table}}, "blocks": g_blocks,
                 "head": g_head}
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


@functools.partial(jax.jit, static_argnames=("c",))
def _forward(c, params, tokens):
    x = params["embed"]["tok"]["table"][tokens]

    def body(x, p):
        return _block(dict(c), p, x), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    return x


@functools.partial(jax.jit, static_argnames=("c",))
def _head_stats(c, head, x_rows, cols):
    """Largest logit of each row, and the logits at ``cols`` (R, k)."""
    h = _rmsnorm(x_rows, head["norm"]["scale"], dict(c)["eps"])
    logits = (h @ head["out"]["w"]).astype(jnp.float32)
    return (jnp.max(logits, -1), jnp.take_along_axis(logits, cols, -1),
            jnp.argmax(logits, -1))


def serve_logits(config: dict, seed: int, sequences: list, max_len: int, *,
                 dtype: str = "float32", cols=None) -> dict:
    """The plain forward over each ``(prompt, served)`` pair, padded to
    ``max_len`` (causal attention: the padding changes no earlier logit).

    -> for every served token, in order: ``best`` the largest logit at its
    position, ``served`` the logit of the served token, ``top`` the token
    this forward puts first, and ``at_cols`` the logits at ``cols`` (one
    list of extra token ids per served token, optional)."""
    c = _dims(config)
    ch = tuple(sorted(c.items()))
    dt = jnp.dtype(dtype)
    rows, targets = [], []
    toks = np.zeros((len(sequences), max_len), np.int32)
    for i, (prompt, served) in enumerate(sequences):
        seq = list(prompt) + list(served[:-1])
        toks[i, :len(seq)] = seq
        for j, t in enumerate(served):
            rows.append(i * max_len + len(prompt) - 1 + j)
            targets.append(t)
    extra = np.zeros((len(rows), 0), np.int32) if cols is None else np.asarray(cols, np.int32)
    want = np.concatenate([np.asarray(targets, np.int32)[:, None], extra], 1)
    out = {"best": [], "served": [], "top": [], "at_cols": []}
    with jax.default_matmul_precision(config["matmul_precision"]):
        params, _ = _init(jax.random.PRNGKey(seed), ch, dt)
        for b0 in range(0, len(sequences), SERVE_BATCH):
            part = toks[b0:b0 + SERVE_BATCH]
            part = np.pad(part, ((0, SERVE_BATCH - len(part)), (0, 0)))
            x = _forward(ch, params, jnp.asarray(part)).reshape(-1, c["d"])
            lo, hi = b0 * max_len, (b0 + SERVE_BATCH) * max_len
            idx = [k for k, r in enumerate(rows) if lo <= r < hi]
            for r0 in range(0, len(idx), HEAD_ROWS):
                sel = idx[r0:r0 + HEAD_ROWS]
                pad = HEAD_ROWS - len(sel)
                r_idx = np.array([rows[k] - lo for k in sel] + [0] * pad)
                w = np.concatenate([want[sel], np.zeros((pad, want.shape[1]), np.int32)])
                best, at, top = jax.device_get(_head_stats(
                    ch, params["head"], x[jnp.asarray(r_idx)], jnp.asarray(w)))
                n = len(sel)
                out["best"] += best[:n].tolist()
                out["served"] += at[:n, 0].tolist()
                out["top"] += top[:n].tolist()
                out["at_cols"] += at[:n, 1:].tolist()
            del x
    return out
