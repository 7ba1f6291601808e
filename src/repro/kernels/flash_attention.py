"""Flash attention for training: one Pallas kernel forward, one for the vjp.

``flash_attention(q, k, v, causal=..., scale=...)``: self-attention of
q (B, H, S, Dk) over k (B, KVH, S, Dk) and v (B, KVH, S, Dv) -> (B, H, S, Dv).  Query head
h reads kv head h // (H // KVH) through the kv blocks' index maps (GQA
without repeating k and v), and v's width is its own (MLA: q/k 192, v 128).
The score matrix never leaves VMEM:

* ``flash_attention`` (the forward) walks the kv blocks of a query block
  with an online softmax (running max and sum in VMEM scratch) and writes
  the output and each row's logsumexp;
* ``flash_attention_bwd`` (the vjp) walks the query blocks of a kv block,
  recomputes the block's probabilities from the logsumexp, accumulates dk
  and dv in VMEM scratch and adds each block's dq into the head's whole dq,
  which stays resident in VMEM across the head's grid steps.  Its scores
  are computed transposed (kv rows, query lanes), so the logsumexp and
  Σ o·do enter as lane rows and the products need no transposed operand
  but ds's for dq.

Causal attention masks by index (query i sees key j ≤ i): a
block wholly above the diagonal is skipped, and its grid step keeps the
block indices of the last (first) needed step, so it issues no DMA; only
blocks across the diagonal build the mask.

Precision is the one JAX's ``default_matmul_precision`` states at trace
time, as for XLA's float32 matmuls: at ``default`` (unset) the products
take bfloat16 operands and accumulate in float32, one MXU pass; at any
higher setting they run float32 at ``HIGHEST``.  Softmax statistics and
accumulators are float32 throughout; operands are cast block by block in
VMEM, so nothing is stored in HBM at a lower precision.  The two calls
name themselves in their op text (``name`` and ``metadata={"kernel":
...}``) so a profile finds them; both return tuples, so neither reads as
the emu bank kernel's 3-D result.  Off the TPU the kernels run in the
Pallas interpreter (tests only).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
MASK = -0.7 * float(np.finfo(np.float32).max)   # exp(MASK - finite) == 0
_NN = (((1,), (0,)), ((), ()))                   # a · b
_NT = (((1,), (1,)), ((), ()))                   # a · bᵀ
_VMEM_CAP = 100 * 2 ** 20
_LOW_PRECISION = (None, "default", "fastest", "bfloat16", "DEFAULT")


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def block_sizes(s: int, dk: int) -> tuple[int, int] | None:
    """(query rows, kv rows) of a block for S tokens of q/k width Dk, or
    None where S does not tile into blocks of at least 128."""
    bq = next((b for b in (512, 256, 128) if s % b == 0), None)
    # wider heads amortise a longer kv block over fewer online-softmax steps
    bks = (1024, 512, 256, 128) if dk > 128 else (512, 256, 128)
    bk = next((b for b in bks if s % b == 0), None)
    return None if bq is None or bk is None else (bq, bk)


def engages(s: int) -> bool:
    """Does self-attention over S tokens run on the kernel here: on a TPU,
    with S tiling into its blocks."""
    return _on_tpu() and block_sizes(s, 0) is not None


def _mxu_dtype():
    """The operand type of the kernels' products at the traced precision."""
    prec = jax.config.jax_default_matmul_precision
    return jnp.bfloat16 if prec in _LOW_PRECISION else jnp.float32


def _dot(a, b, dims, mxu):
    prec = lax.Precision.HIGHEST if mxu == jnp.float32 else None
    return lax.dot_general(a.astype(mxu), b.astype(mxu), dims, precision=prec,
                           preferred_element_type=jnp.float32)


def _needed(qi, kj, bq, bk):
    """Does query block qi see any key of kv block kj (causal)?"""
    return kj * bk <= (qi + 1) * bq - 1


def _crosses(qi, kj, bq, bk):
    """Does kv block kj reach past query block qi's first row (so that
    some of its keys are masked)?"""
    return (kj + 1) * bk - 1 > qi * bq


def _vmem_limit(nbytes: int) -> int:
    return int(min(_VMEM_CAP, nbytes * 1.5 + 8 * 2 ** 20))


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, q_scr, m_scr, l_scr, acc_scr,
                *, causal, scale, bq, bk, n_kv, mxu):
    qi, kj = pl.program_id(2), pl.program_id(3)

    @pl.when(kj == 0)
    def _():
        q_scr[...] = q_ref[...].astype(q_scr.dtype)
        m_scr[...] = jnp.full(m_scr.shape, MASK, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def step(masked):
        s = _dot(q_scr[...], k_ref[...], _NT, mxu) * scale          # (bq, bk)
        if masked:
            rows = qi * bq + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = kj * bk + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(cols <= rows, s, MASK)
        m_prev = m_scr[...]                                         # (bq, 128)
        m_next = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - jnp.tile(m_next, (1, bk // LANES)))
        alpha = jnp.exp(m_prev - m_next)
        l_scr[...] = alpha * l_scr[...] + p.sum(axis=-1, keepdims=True)
        m_scr[...] = m_next
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + _dot(p, v_ref[...], _NN, mxu)

    if causal:
        pl.when(_needed(qi, kj, bq, bk) & _crosses(qi, kj, bq, bk))(lambda: step(True))
        pl.when(jnp.logical_not(_crosses(qi, kj, bq, bk)))(lambda: step(False))
    else:
        step(False)

    @pl.when(kj == n_kv - 1)
    def _():
        l = l_scr[...]
        o_ref[...] = (acc_scr[...] / l[:, :1]).astype(o_ref.dtype)
        lse = m_scr[...] + jnp.log(l)                               # lanes equal
        lse_ref[...] = lse.T[:SUBLANES]                             # (8, bq) rows


def _forward(q, k, v, causal, scale, blocks, mxu):
    """-> (o (B, H, S, Dv) float32, lse (B, H, 8, S) float32)."""
    b, h, s, dk = q.shape
    kvh, dv = k.shape[1], v.shape[3]
    rep = h // kvh
    bq, bk = blocks
    n_q, n_kv = s // bq, s // bk

    def kv_index(bi, hi, qi, kj):
        if causal:  # past the last needed block: hold it, no DMA
            kj = jnp.minimum(kj, ((qi + 1) * bq - 1) // bk)
        return bi, hi // rep, kj, 0

    itemsize = 4
    vmem = itemsize * (2 * (bq * dk + bk * dk + bk * dv + bq * dv + SUBLANES * bq)
                       + bq * dk + 2 * bq * LANES + bq * dv + 4 * bq * bk)
    kernel = functools.partial(_fwd_kernel, causal=causal, scale=scale, bq=bq,
                               bk=bk, n_kv=n_kv, mxu=mxu)
    return pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((b, h, s, dv), jnp.float32),
                   jax.ShapeDtypeStruct((b, h, SUBLANES, s), jnp.float32)),
        grid=(b, h, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((None, None, bq, dk), lambda bi, hi, qi, kj: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, bk, dk), kv_index),
            pl.BlockSpec((None, None, bk, dv), kv_index),
        ],
        out_specs=(
            pl.BlockSpec((None, None, bq, dv), lambda bi, hi, qi, kj: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, SUBLANES, bq), lambda bi, hi, qi, kj: (bi, hi, 0, qi)),
        ),
        scratch_shapes=[
            pltpu.VMEM((bq, dk), mxu),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(vmem)),
        interpret=not _on_tpu(),
        name="flash_attention",
        metadata={"kernel": "flash_attention"},
    )(q, k, v)


# --------------------------------------------------------------------------
# vjp
# --------------------------------------------------------------------------

def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                dq_ref, dk_ref, dv_ref, k_scr, v_scr, dk_scr, dv_scr,
                *, causal, scale, bq, bk, n_q, mxu):
    kj, qi = pl.program_id(2), pl.program_id(3)

    @pl.when((kj == 0) & (qi == 0))
    def _():
        dq_ref[...] = jnp.zeros(dq_ref.shape, dq_ref.dtype)

    @pl.when(qi == 0)
    def _():
        k_scr[...] = k_ref[...].astype(k_scr.dtype)
        v_scr[...] = v_ref[...].astype(v_scr.dtype)
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    def step(masked):
        q, do, k = q_ref[...].astype(mxu), do_ref[...].astype(mxu), k_scr[...]
        s_t = _dot(k, q, _NT, mxu) * scale                          # (bk, bq)
        if masked:
            keys = kj * bk + lax.broadcasted_iota(jnp.int32, s_t.shape, 0)
            rows = qi * bq + lax.broadcasted_iota(jnp.int32, s_t.shape, 1)
            s_t = jnp.where(keys <= rows, s_t, MASK)
        p_t = jnp.exp(s_t - lse_ref[:1, :])
        dv_scr[...] += _dot(p_t, do, _NN, mxu)                      # (bk, dv)
        dp_t = _dot(v_scr[...], do, _NT, mxu)                       # (bk, bq)
        ds_t = p_t * (dp_t - di_ref[:1, :]) * scale
        dk_scr[...] += _dot(ds_t, q, _NN, mxu)                      # (bk, dk)
        rows = pl.ds(pl.multiple_of(qi * bq, bq), bq)
        dq_ref[rows, :] += _dot(ds_t.T, k, _NN, mxu)                # (bq, dk)

    if causal:
        pl.when(_needed(qi, kj, bq, bk) & _crosses(qi, kj, bq, bk))(lambda: step(True))
        pl.when(jnp.logical_not(_crosses(qi, kj, bq, bk)))(lambda: step(False))
    else:
        step(False)

    @pl.when(qi == n_q - 1)
    def _():
        dk_ref[...] = dk_scr[...]
        dv_ref[...] = dv_scr[...]


def _backward(q, k, v, do, lse, di, causal, scale, blocks, mxu):
    """-> (dq (B, H, S, Dk), dk and dv per query head (B, H, S, D)), all
    float32."""
    b, h, s, dk = q.shape
    kvh, dv = k.shape[1], v.shape[3]
    rep = h // kvh
    bq, bk = blocks
    n_q, n_kv = s // bq, s // bk

    def q_index(bi, hi, kj, qi):
        if causal:  # before the first needed block: hold it, no DMA
            qi = jnp.maximum(qi, (kj * bk) // bq)
        return bi, hi, qi, 0

    def row_index(bi, hi, kj, qi):
        _, _, qi, _ = q_index(bi, hi, kj, qi)
        return bi, hi, 0, qi

    kv_index = lambda bi, hi, kj, qi: (bi, hi // rep, kj, 0)
    out_index = lambda bi, hi, kj, qi: (bi, hi, kj, 0)
    itemsize = 4
    vmem = itemsize * (2 * (bq * dk + bk * dk + bk * dv + bq * dv + 2 * SUBLANES * bq
                            + s * dk + bk * dk + bk * dv)
                       + 2 * bk * (dk + dv) + 6 * bq * bk)
    kernel = functools.partial(_bwd_kernel, causal=causal, scale=scale, bq=bq,
                               bk=bk, n_q=n_q, mxu=mxu)
    return pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((b, h, s, dk), jnp.float32),
                   jax.ShapeDtypeStruct((b, h, s, dk), jnp.float32),
                   jax.ShapeDtypeStruct((b, h, s, dv), jnp.float32)),
        grid=(b, h, n_kv, n_q),
        in_specs=[
            pl.BlockSpec((None, None, bq, dk), q_index),
            pl.BlockSpec((None, None, bk, dk), kv_index),
            pl.BlockSpec((None, None, bk, dv), kv_index),
            pl.BlockSpec((None, None, bq, dv), q_index),
            pl.BlockSpec((None, None, SUBLANES, bq), row_index),
            pl.BlockSpec((None, None, SUBLANES, bq), row_index),
        ],
        out_specs=(
            # the head's whole dq stays resident while its blocks add in
            pl.BlockSpec((None, None, s, dk), lambda bi, hi, kj, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, bk, dk), out_index),
            pl.BlockSpec((None, None, bk, dv), out_index),
        ),
        scratch_shapes=[
            pltpu.VMEM((bk, dk), mxu),
            pltpu.VMEM((bk, dv), mxu),
            pltpu.VMEM((bk, dk), jnp.float32),
            pltpu.VMEM((bk, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(vmem)),
        interpret=not _on_tpu(),
        name="flash_attention_bwd",
        metadata={"kernel": "flash_attention_bwd"},
    )(q, k, v, do, lse, di)


# --------------------------------------------------------------------------
# the differentiable entry
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _attention(q, k, v, causal, scale, blocks, mxu):
    o, _ = _forward(q, k, v, causal, scale, blocks, mxu)
    return o.astype(q.dtype)


def _attention_fwd(q, k, v, causal, scale, blocks, mxu):
    o, lse = _forward(q, k, v, causal, scale, blocks, mxu)
    o = o.astype(q.dtype)
    return o, (q, k, v, o, lse)


def _attention_bwd(causal, scale, blocks, mxu, res, do):
    q, k, v, o, lse = res
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    di = jnp.broadcast_to(di[:, :, None, :], lse.shape)
    dq, dk, dv = _backward(q, k, v, do, lse, di, causal, scale, blocks, mxu)
    b, kvh, s = k.shape[:3]
    rep = q.shape[1] // kvh
    if rep > 1:  # sum the query heads that share a kv head
        dk = dk.reshape(b, kvh, rep, s, -1).sum(axis=2)
        dv = dv.reshape(b, kvh, rep, s, -1).sum(axis=2)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_attention.defvjp(_attention_fwd, _attention_bwd)


def flash_attention(q, k, v, *, causal: bool = True, scale: float | None = None,
                    blocks: tuple[int, int] | None = None):
    """Softmax self-attention of q (B, H, S, Dk) over k (B, KVH, S, Dk) and
    v (B, KVH, S, Dv) -> (B, H, S, Dv) in q's dtype, differentiable in q,
    k and v.  ``scale`` defaults to 1/√Dk; ``blocks`` (query rows, kv
    rows) to ``block_sizes``'s choice."""
    b, h, s, dk = q.shape
    kvh = k.shape[1]
    if h % kvh:
        raise ValueError(f"{h} query heads do not group over {kvh} kv heads")
    if k.shape[2] != s or v.shape[2] != s:
        raise ValueError(f"self-attention: keys {k.shape[2]} and values "
                         f"{v.shape[2]} for {s} queries")
    blocks = blocks or block_sizes(s, dk)
    if blocks is None or s % blocks[0] or s % blocks[1]:
        raise ValueError(f"{s} tokens do not tile into blocks {blocks}")
    scale = 1.0 / math.sqrt(dk) if scale is None else float(scale)
    return _attention(q, k, v, causal, scale, tuple(blocks), _mxu_dtype())
