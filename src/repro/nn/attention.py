"""Attention layers: MHA/GQA (+bias, +qk_norm, +local window), MLA, cross.

Two execution regimes:

* ``flash_attention`` — chunked online-softmax attention for training /
  prefill.  Q is processed in static chunks (Python loop ⇒ static bounds);
  for each Q chunk only the causally-reachable / in-window K chunks are
  scanned (``lax.scan``), so causal compute is the exact triangle (no 2×
  overcount in the roofline) and peak memory is O(chunk²), never O(S²).

* ``decode_attention`` — single-query attention against a KV cache with a
  length mask.  The sequence-sharded (model-axis) variant with logsumexp
  combine lives in ``repro/serve/decode.py``; this is the per-shard core.

GQA broadcasts KV heads over query groups.  MLA (MiniCPM3 / DeepSeek-style)
keeps a compressed latent cache and uses the absorbed form at decode time.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from repro.core.photonics import forward_matmul
from repro.nn.embeddings import apply_rotary, rotary_angles
from repro.nn.linear import Linear
from repro.nn.module import Module, named_key
from repro.nn.norms import rms_normalize

NEG_INF = -1e30


def _gqa_expand(kv, n_heads: int):
    """(B, S, KVH, D) -> (B, S, H, D) by repeating each kv head."""
    b, s, kvh, d = kv.shape
    if kvh == n_heads:
        return kv
    rep = n_heads // kvh
    return jnp.repeat(kv, rep, axis=2)


def reference_attention(q, k, v, *, q_pos, kv_pos, causal=True, window=None,
                        scale=None, logit_softcap=None):
    """O(S²) oracle used by tests.  q:(B,Sq,H,D) k,v:(B,Skv,KVH,D)."""
    b, sq, h, d = q.shape
    k = _gqa_expand(k, h)
    v = _gqa_expand(v, h)
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    if logit_softcap:
        scores = logit_softcap * jnp.tanh(scores / logit_softcap)
    mask = jnp.ones((b, sq, kv_pos.shape[1]), bool)
    if causal:
        mask &= kv_pos[:, None, :] <= q_pos[:, :, None]
    if window is not None:
        mask &= q_pos[:, :, None] - kv_pos[:, None, :] < window
    scores = jnp.where(mask[:, None, :, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _attend_chunk(q, k, v, q_pos, k_pos, scale, causal, window, logit_softcap,
                  acc, m_prev, l_prev):
    """Online-softmax update for one (q-chunk, k-chunk) tile. All f32."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if logit_softcap:
        scores = logit_softcap * jnp.tanh(scores / logit_softcap)
    mask = jnp.ones(scores.shape[-2:], bool)[None]  # (1, Sq, Sk)
    mask = jnp.broadcast_to(mask, (q.shape[0],) + mask.shape[1:])
    if causal:
        mask &= k_pos[:, None, :] <= q_pos[:, :, None]
    if window is not None:
        mask &= q_pos[:, :, None] - k_pos[:, None, :] < window
    scores = jnp.where(mask[:, None, :, :], scores, NEG_INF)
    m_cur = jnp.max(scores, axis=-1)  # (B, H, Sq)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked rows (m_new == NEG_INF) against NaN
    safe_m = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(scores - safe_m[..., None])
    p = jnp.where(mask[:, None, :, :], p, 0.0)
    alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, jnp.exp(m_prev - safe_m))
    l_new = alpha * l_prev + jnp.sum(p, axis=-1)
    acc = acc * jnp.transpose(alpha, (0, 2, 1))[..., None]
    acc = acc + jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return acc, m_new, l_new


def flash_attention(q, k, v, *, q_pos, kv_pos, causal=True, window=None,
                    scale=None, logit_softcap=None,
                    q_chunk: int = 2048, k_chunk: int = 1024):
    """Chunked online-softmax attention.  Shapes as reference_attention.

    Static per-q-chunk K ranges: for causal attention q-chunk j only scans
    K chunks [win_lo(j) .. j]; compute is the exact causal triangle.
    """
    b, sq, h, d = q.shape
    skv = k.shape[1]
    k = _gqa_expand(k, h)
    v = _gqa_expand(v, h)
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    q_chunk = min(q_chunk, sq)
    k_chunk = min(k_chunk, skv)
    if sq % q_chunk or skv % k_chunk:
        # fall back to a single-tile pass (ragged sizes only appear in tests)
        acc = jnp.zeros((b, sq, h, d), jnp.float32)
        m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, sq), jnp.float32)
        acc, m, l = _attend_chunk(
            q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
            q_pos, kv_pos, scale, causal, window, logit_softcap, acc, m0, l0)
        out = acc / jnp.maximum(jnp.transpose(l, (0, 2, 1))[..., None], 1e-30)
        return out.astype(q.dtype)

    n_q = sq // q_chunk
    n_k = skv // k_chunk
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    # Conservative alignment assumption for static chunk-range pruning:
    # q_pos/kv_pos are monotone per row. When causal, chunk j of Q can only
    # see K chunks whose start position <= max q_pos in chunk j.  With the
    # standard layouts used here (prefill: q_pos == kv_pos; training:
    # both are arange) chunk ranges below are exact.
    def q_chunk_out(j, qf, kf, vf, q_pos, kv_pos):
        qj = jax.lax.dynamic_slice_in_dim(qf, j * q_chunk, q_chunk, axis=1)
        qpj = jax.lax.dynamic_slice_in_dim(q_pos, j * q_chunk, q_chunk, axis=1)
        if causal and sq == skv and q_chunk % k_chunk == 0:
            hi = (j + 1) * (q_chunk // k_chunk)
        else:
            hi = n_k
        if window is not None and causal and sq == skv:
            lo = max(0, ((j * q_chunk - window) // k_chunk))
        else:
            lo = 0
        n_steps = hi - lo
        k_slab = jax.lax.dynamic_slice_in_dim(kf, lo * k_chunk, n_steps * k_chunk, axis=1)
        v_slab = jax.lax.dynamic_slice_in_dim(vf, lo * k_chunk, n_steps * k_chunk, axis=1)
        kp_slab = jax.lax.dynamic_slice_in_dim(kv_pos, lo * k_chunk, n_steps * k_chunk, axis=1)
        k_steps = k_slab.reshape(b, n_steps, k_chunk, h, d).transpose(1, 0, 2, 3, 4)
        v_steps = v_slab.reshape(b, n_steps, k_chunk, h, d).transpose(1, 0, 2, 3, 4)
        kp_steps = kp_slab.reshape(b, n_steps, k_chunk).transpose(1, 0, 2)

        # rematerialised in the vjp: a (q chunk, k chunk) tile's scores
        # are recomputed from the carry rather than saved
        @functools.partial(jax.checkpoint, prevent_cse=False)
        def body(carry, xs):
            acc, m_p, l_p = carry
            k_c, v_c, kp_c = xs
            acc, m_n, l_n = _attend_chunk(
                qj, k_c, v_c, qpj, kp_c, scale, causal, window, logit_softcap,
                acc, m_p, l_p)
            return (acc, m_n, l_n), None

        acc0 = jnp.zeros((b, q_chunk, h, d), jnp.float32)
        m0 = jnp.full((b, h, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, q_chunk), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), (k_steps, v_steps, kp_steps))
        return acc / jnp.maximum(jnp.transpose(l, (0, 2, 1))[..., None], 1e-30)

    # each q chunk is rematerialised whole in the vjp too: a backward keeps
    # q, k and v, and holds one chunk's per-step carries at a time, so it
    # needs O(S·d) memory where autodiff of the loops needs O(S²)
    out_chunks = [
        jax.checkpoint(functools.partial(q_chunk_out, j), prevent_cse=False)(
            qf, kf, vf, q_pos, kv_pos)
        for j in range(n_q)
    ]
    return jnp.concatenate(out_chunks, axis=1).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, *, cache_len, window=None,
                     q_pos=None, scale=None, logit_softcap=None):
    """Single-step attention vs cache.

    q: (B, 1, H, D); caches: (B, Smax, KVH, D); cache_len: (B,) valid lengths
    (the new token's K/V must already be written at index cache_len-1).
    Returns (B, 1, H, D).
    """
    b, _, h, d = q.shape
    smax = k_cache.shape[1]
    k = _gqa_expand(k_cache, h)
    v = _gqa_expand(v_cache, h)
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    if logit_softcap:
        scores = logit_softcap * jnp.tanh(scores / logit_softcap)
    kv_pos = jnp.arange(smax)[None, :]
    valid = kv_pos < cache_len[:, None]
    if window is not None:
        qp = (cache_len - 1) if q_pos is None else q_pos
        valid &= qp[:, None] - kv_pos < window
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(jnp.float32))
    return out.astype(q.dtype)


@dataclasses.dataclass(frozen=True)
class Attention(Module):
    """MHA / GQA self-attention with rotary, optional qkv-bias / qk_norm /
    sliding window — covers qwen1.5 (bias), qwen3 (qk_norm), granite/llama,
    qwen2-moe, kimi (GQA per assignment), recurrentgemma local layers,
    internvl LM, whisper (rope disabled, bias on)."""

    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int | None = None
    qkv_bias: bool = False
    out_bias: bool = False
    qk_norm: bool = False
    rope: bool = True
    rope_theta: float = 10000.0
    causal: bool = True
    window: int | None = None
    logit_softcap: float | None = None
    dtype: jnp.dtype = jnp.float32

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def init(self, key):
        hd = self.hd
        mk = lambda n, i, o, b: Linear(i, o, use_bias=b, dtype=self.dtype).init(named_key(key, n))
        return {
            "q": mk("q", self.d_model, self.n_heads * hd, self.qkv_bias),
            "k": mk("k", self.d_model, self.n_kv_heads * hd, self.qkv_bias),
            "v": mk("v", self.d_model, self.n_kv_heads * hd, self.qkv_bias),
            "o": mk("o", self.n_heads * hd, self.d_model, self.out_bias),
        }

    def qkv(self, params, x, positions):
        b, s, _ = x.shape
        hd = self.hd
        lin = lambda p, o, bias: (forward_matmul(x, p["w"]) + (p["b"] if bias else 0.0))
        q = lin(params["q"], None, self.qkv_bias).reshape(b, s, self.n_heads, hd)
        k = lin(params["k"], None, self.qkv_bias).reshape(b, s, self.n_kv_heads, hd)
        v = lin(params["v"], None, self.qkv_bias).reshape(b, s, self.n_kv_heads, hd)
        if self.qk_norm:
            q = rms_normalize(q)
            k = rms_normalize(k)
        if self.rope:
            cos, sin = rotary_angles(positions, hd, self.rope_theta)
            q = apply_rotary(q, cos, sin)
            k = apply_rotary(k, cos, sin)
        return q, k, v

    def __call__(self, params, x, *, positions=None, q_chunk=2048, k_chunk=1024):
        b, s, _ = x.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        q, k, v = self.qkv(params, x, positions)
        if s <= 2 * k_chunk:
            out = reference_attention(q, k, v, q_pos=positions, kv_pos=positions,
                                      causal=self.causal, window=self.window,
                                      logit_softcap=self.logit_softcap)
        else:
            out = flash_attention(q, k, v, q_pos=positions, kv_pos=positions,
                                  causal=self.causal, window=self.window,
                                  logit_softcap=self.logit_softcap,
                                  q_chunk=q_chunk, k_chunk=k_chunk)
        out = out.reshape(b, s, self.n_heads * self.hd)
        y = forward_matmul(out, params["o"]["w"])
        if self.out_bias:
            y = y + params["o"]["b"]
        return y

    # ---- decode path ------------------------------------------------------
    def init_cache(self, batch: int, max_len: int, dtype=None):
        hd = self.hd
        dt = dtype or self.dtype
        eff = min(max_len, self.window) if self.window is not None else max_len
        return {
            "k": jnp.zeros((batch, eff, self.n_kv_heads, hd), dt),
            "v": jnp.zeros((batch, eff, self.n_kv_heads, hd), dt),
        }

    def decode(self, params, x, cache, cache_len):
        """One token: x (B, 1, d). Returns (y, new_cache).

        For windowed layers the cache is a ring buffer of size ``window``.
        """
        b = x.shape[0]
        positions = cache_len[:, None]  # new token's absolute position
        q, k, v = self.qkv(params, x, positions)
        smax = cache["k"].shape[1]
        if self.window is not None and smax == self.window:
            slot = (cache_len % smax)
        else:
            slot = cache_len
        bidx = jnp.arange(b)
        k_cache = cache["k"].at[bidx, slot].set(k[:, 0])
        v_cache = cache["v"].at[bidx, slot].set(v[:, 0])
        if self.window is not None and smax == self.window:
            # ring buffer: every stored slot is within the window by
            # construction; validity = stored count
            valid_len = jnp.minimum(cache_len + 1, smax)
            out = decode_attention(q, k_cache, v_cache, cache_len=valid_len,
                                   window=None, logit_softcap=self.logit_softcap)
        else:
            out = decode_attention(q, k_cache, v_cache, cache_len=cache_len + 1,
                                   window=self.window, logit_softcap=self.logit_softcap)
        y = forward_matmul(out.reshape(b, 1, self.n_heads * self.hd), params["o"]["w"])
        if self.out_bias:
            y = y + params["o"]["b"]
        return y, {"k": k_cache, "v": v_cache}

    def prefill(self, params, x, cache, cache_len, n_valid):
        """Chunked cache fill: x (B, C, d) is the next C prompt tokens of
        every slot (per-slot validity ``n_valid``), written at absolute
        positions ``cache_len + j`` and attended causally against the whole
        cache in ONE batched forward.  Invalid positions scatter out of
        bounds and are dropped (``mode="drop"``), so slots past their
        prompt (n_valid == 0 included) leave the cache untouched.  Only for
        absolute-indexed caches — windowed ring buffers take the engine's
        scan fallback (``serve.decode.make_prefill_step``)."""
        assert self.window is None, "windowed caches prefill via decode-scan"
        b, c, _ = x.shape
        positions = cache_len[:, None] + jnp.arange(c)[None, :]
        q, k, v = self.qkv(params, x, positions)
        smax = cache["k"].shape[1]
        valid = jnp.arange(c)[None, :] < n_valid[:, None]
        slot = jnp.where(valid, positions, smax)  # smax = out of bounds
        bidx = jnp.arange(b)[:, None]
        k_cache = cache["k"].at[bidx, slot].set(k, mode="drop")
        v_cache = cache["v"].at[bidx, slot].set(v, mode="drop")
        kv_pos = jnp.broadcast_to(jnp.arange(smax)[None, :], (b, smax))
        out = reference_attention(q, k_cache, v_cache, q_pos=positions,
                                  kv_pos=kv_pos, causal=True,
                                  logit_softcap=self.logit_softcap)
        y = forward_matmul(out.reshape(b, c, self.n_heads * self.hd), params["o"]["w"])
        if self.out_bias:
            y = y + params["o"]["b"]
        return y, {"k": k_cache, "v": v_cache}


@dataclasses.dataclass(frozen=True)
class CrossAttention(Module):
    """Encoder-decoder cross attention (whisper)."""

    d_model: int
    n_heads: int
    use_bias: bool = True
    dtype: jnp.dtype = jnp.float32

    @property
    def hd(self) -> int:
        return self.d_model // self.n_heads

    def init(self, key):
        mk = lambda n, b: Linear(self.d_model, self.d_model, use_bias=b,
                                 dtype=self.dtype).init(named_key(key, n))
        return {"q": mk("q", self.use_bias), "k": mk("k", False),
                "v": mk("v", self.use_bias), "o": mk("o", self.use_bias)}

    def __call__(self, params, x, enc, q_chunk: int = 2048):
        b, s, _ = x.shape
        se = enc.shape[1]
        hd = self.hd
        q = (x @ params["q"]["w"]
             + (params["q"].get("b", 0.0) if self.use_bias else 0.0)
             ).reshape(b, s, self.n_heads, hd)
        k = (enc @ params["k"]["w"]).reshape(b, se, self.n_heads, hd)
        v = (enc @ params["v"]["w"]
             + (params["v"].get("b", 0.0) if self.use_bias else 0.0)
             ).reshape(b, se, self.n_heads, hd)
        kp = jnp.broadcast_to(jnp.arange(se)[None], (b, se))

        def attend(qc, qpc):
            return reference_attention(qc, k, v, q_pos=qpc, kv_pos=kp, causal=False)

        if s > q_chunk and s % q_chunk == 0:
            # chunk queries so score tensors stay O(q_chunk * se)
            nq = s // q_chunk
            qs = q.reshape(b, nq, q_chunk, self.n_heads, hd).transpose(1, 0, 2, 3, 4)
            qp = jnp.broadcast_to(jnp.arange(q_chunk)[None], (b, q_chunk))
            out = jax.lax.map(lambda qc: attend(qc, qp), qs)
            out = out.transpose(1, 0, 2, 3, 4).reshape(b, s, self.n_heads, hd)
        else:
            qp = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            out = attend(q, qp)
        y = out.reshape(b, s, self.d_model) @ params["o"]["w"]
        if self.use_bias:
            y = y + params["o"]["b"]
        return y


@dataclasses.dataclass(frozen=True)
class MLAttention(Module):
    """Multi-head Latent Attention (MiniCPM3 / DeepSeek-V2 style).

    Projections:
      q:  x → q_lora → (per head) [nope | rope]; with ``q_lora_rank`` None
          (Moonlight / DeepSeek-V2-Lite) q comes straight from x
      kv: x → (kv_lora ‖ shared rope key)
          kv_lora → (per head) [k_nope | v]
    Cache stores only (kv_lora, k_rope): (r_kv + r_rope) floats/token.
    Decode uses the absorbed form (q_nope folded through W_uk; output read
    back through W_uv) so per-step work is O(S·(r_kv + r_rope)) per head.
    """

    d_model: int
    n_heads: int
    q_lora_rank: int | None = 768
    kv_lora_rank: int = 256
    qk_nope_dim: int = 64
    qk_rope_dim: int = 32
    v_head_dim: int = 64
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6  # the latents' RMSNorms
    dtype: jnp.dtype = jnp.float32

    @property
    def qk_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    def init(self, key):
        mk = lambda n, i, o: Linear(i, o, dtype=self.dtype).init(named_key(key, n))
        h = self.n_heads
        if self.q_lora_rank is None:
            q = {"q": mk("q", self.d_model, h * self.qk_dim)}
        else:
            q = {
                "q_down": mk("q_down", self.d_model, self.q_lora_rank),
                "q_norm_scale": jnp.ones((self.q_lora_rank,), self.dtype),
                "q_up": mk("q_up", self.q_lora_rank, h * self.qk_dim),
            }
        return {
            **q,
            "kv_down": mk("kv_down", self.d_model, self.kv_lora_rank + self.qk_rope_dim),
            "kv_norm_scale": jnp.ones((self.kv_lora_rank,), self.dtype),
            "k_up": mk("k_up", self.kv_lora_rank, h * self.qk_nope_dim),
            "v_up": mk("v_up", self.kv_lora_rank, h * self.v_head_dim),
            "o": mk("o", h * self.v_head_dim, self.d_model),
        }

    def _latents(self, params, x, positions):
        """Return (q (B,S,H,qk_dim), c_kv (B,S,r), k_rope (B,S,rope))."""
        b, s, _ = x.shape
        h = self.n_heads
        if self.q_lora_rank is None:
            q = forward_matmul(x, params["q"]["w"])
        else:
            ql = forward_matmul(x, params["q_down"]["w"])
            ql = rms_normalize(ql, self.norm_eps) * params["q_norm_scale"]
            q = forward_matmul(ql, params["q_up"]["w"])
        q = q.reshape(b, s, h, self.qk_dim)
        kv = forward_matmul(x, params["kv_down"]["w"])
        c_kv = (rms_normalize(kv[..., : self.kv_lora_rank], self.norm_eps)
                * params["kv_norm_scale"])
        k_rope = kv[..., self.kv_lora_rank:]
        cos, sin = rotary_angles(positions, self.qk_rope_dim, self.rope_theta)
        q_nope, q_rope = q[..., : self.qk_nope_dim], q[..., self.qk_nope_dim:]
        q_rope = apply_rotary(q_rope, cos, sin)
        k_rope = apply_rotary(k_rope[:, :, None, :], cos, sin)[:, :, 0, :]
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        return q, c_kv, k_rope

    def __call__(self, params, x, *, positions=None, q_chunk=2048, k_chunk=1024):
        b, s, _ = x.shape
        h = self.n_heads
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        q, c_kv, k_rope = self._latents(params, x, positions)
        k_nope = forward_matmul(c_kv, params["k_up"]["w"]).reshape(b, s, h, self.qk_nope_dim)
        v = forward_matmul(c_kv, params["v_up"]["w"]).reshape(b, s, h, self.v_head_dim)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                      (b, s, h, self.qk_rope_dim))], axis=-1)
        scale = 1.0 / math.sqrt(self.qk_dim)
        # v_head_dim != qk_dim → pad V for the shared kernels, slice after
        pad = self.qk_dim - self.v_head_dim
        v_p = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, pad))) if pad > 0 else v
        if s <= 2 * k_chunk:
            out = reference_attention(q, k, v_p, q_pos=positions,
                                      kv_pos=positions, causal=True, scale=scale)
        else:
            out = flash_attention(q, k, v_p, q_pos=positions, kv_pos=positions, causal=True,
                                  scale=scale, q_chunk=q_chunk, k_chunk=k_chunk)
        out = out[..., : self.v_head_dim].reshape(b, s, h * self.v_head_dim)
        return forward_matmul(out, params["o"]["w"])

    def init_cache(self, batch: int, max_len: int, dtype=None):
        dt = dtype or self.dtype
        return {
            "c_kv": jnp.zeros((batch, max_len, self.kv_lora_rank), dt),
            "k_rope": jnp.zeros((batch, max_len, self.qk_rope_dim), dt),
        }

    def decode(self, params, x, cache, cache_len):
        """Absorbed-form single-token decode. x: (B, 1, d)."""
        b = x.shape[0]
        h = self.n_heads
        positions = cache_len[:, None]
        q, c_kv_new, k_rope_new = self._latents(params, x, positions)
        bidx = jnp.arange(b)
        c_cache = cache["c_kv"].at[bidx, cache_len].set(c_kv_new[:, 0])
        r_cache = cache["k_rope"].at[bidx, cache_len].set(k_rope_new[:, 0])
        q_nope, q_rope = q[..., : self.qk_nope_dim], q[..., self.qk_nope_dim:]
        # absorb q_nope through W_uk:  (B,1,H,nope) x (r,H,nope) -> (B,1,H,r)
        w_uk = params["k_up"]["w"].reshape(self.kv_lora_rank, h, self.qk_nope_dim)
        q_abs = jnp.einsum("bqhn,rhn->bqhr", q_nope.astype(jnp.float32), w_uk.astype(jnp.float32))
        scores = jnp.einsum("bqhr,bkr->bhqk", q_abs, c_cache.astype(jnp.float32))
        scores += jnp.einsum("bqhp,bkp->bhqk", q_rope.astype(jnp.float32),
                             r_cache.astype(jnp.float32))
        scores *= 1.0 / math.sqrt(self.qk_dim)
        valid = jnp.arange(c_cache.shape[1])[None, :] < (cache_len + 1)[:, None]
        scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1)
        out_lat = jnp.einsum("bhqk,bkr->bqhr", w, c_cache.astype(jnp.float32))
        w_uv = params["v_up"]["w"].reshape(self.kv_lora_rank, h, self.v_head_dim)
        out = jnp.einsum("bqhr,rhv->bqhv", out_lat, w_uv.astype(jnp.float32)).astype(x.dtype)
        y = forward_matmul(out.reshape(b, 1, h * self.v_head_dim), params["o"]["w"])
        return y, {"c_kv": c_cache, "k_rope": r_cache}

    def prefill(self, params, x, cache, cache_len, n_valid):
        """Chunked absorbed-form prefill: C queries per slot against the
        latent cache — the decode math with a query axis (see
        ``Attention.prefill`` for the scatter/validity semantics)."""
        b, c, _ = x.shape
        h = self.n_heads
        positions = cache_len[:, None] + jnp.arange(c)[None, :]
        q, c_kv_new, k_rope_new = self._latents(params, x, positions)
        smax = cache["c_kv"].shape[1]
        valid = jnp.arange(c)[None, :] < n_valid[:, None]
        slot = jnp.where(valid, positions, smax)
        bidx = jnp.arange(b)[:, None]
        c_cache = cache["c_kv"].at[bidx, slot].set(c_kv_new, mode="drop")
        r_cache = cache["k_rope"].at[bidx, slot].set(k_rope_new, mode="drop")
        q_nope, q_rope = q[..., : self.qk_nope_dim], q[..., self.qk_nope_dim:]
        w_uk = params["k_up"]["w"].reshape(self.kv_lora_rank, h, self.qk_nope_dim)
        q_abs = jnp.einsum("bqhn,rhn->bqhr", q_nope.astype(jnp.float32), w_uk.astype(jnp.float32))
        scores = jnp.einsum("bqhr,bkr->bhqk", q_abs, c_cache.astype(jnp.float32))
        scores += jnp.einsum("bqhp,bkp->bhqk", q_rope.astype(jnp.float32),
                             r_cache.astype(jnp.float32))
        scores *= 1.0 / math.sqrt(self.qk_dim)
        causal = jnp.arange(smax)[None, None, :] <= positions[:, :, None]  # (B,C,S)
        scores = jnp.where(causal[:, None], scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1)
        out_lat = jnp.einsum("bhqk,bkr->bqhr", w, c_cache.astype(jnp.float32))
        w_uv = params["v_up"]["w"].reshape(self.kv_lora_rank, h, self.v_head_dim)
        out = jnp.einsum("bqhr,rhv->bqhv", out_lat, w_uv.astype(jnp.float32)).astype(x.dtype)
        y = forward_matmul(out.reshape(b, c, h * self.v_head_dim), params["o"]["w"])
        return y, {"c_kv": c_cache, "k_rope": r_cache}
