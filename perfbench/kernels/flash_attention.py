"""The Pallas flash-attention kernel of training's self-attention
(``repro.kernels.flash_attention``): which device ops are its calls, and
the operations and bytes of one call.

The kernel names itself in its op text: ``kernel_metadata={"kernel":
"flash_attention"}`` (the forward) and ``"flash_attention_bwd"`` (its vjp:
dq, dk and dv in one call); the HLO names of the calls carry the same
words.  Both return tuples, so neither is the emu bank kernel's 3-D result.

A forward over B·H heads of S queries and S keys (q and k of width Dk, v of
Dv) makes two products per visible (query, key) pair, qkᵀ and pv:
2·(Dk + Dv) operations a pair; the vjp makes five (the scores again, dv,
dp, dk, dq): 2·(2·Dk + Dv) + 2·(Dk + Dv).  Causal attention sees
S·(S + 1)/2 pairs a head, the rest S².  The least traffic reads q, k and v
once (float32) and writes the output and the row statistics once; the vjp
reads q, k, v, do and the statistics and writes dq, dk and dv.  The blocks
the kernel skips above the diagonal are not work and are not counted, nor
the masked entries of the blocks across it.
"""

import re

_NAME = re.compile(r'"kernel":\s*"flash_attention(_bwd)?"')


def match(op: str) -> bool:
    """Is this device op (its HLO text in the trace) a call of the kernel?"""
    return "tpu_custom_call" in op and bool(_NAME.search(op))


def cost(call: dict) -> tuple[float, float]:
    """-> (operations, bytes) of one call described by ``heads`` (B·H),
    ``s``, ``dk``, ``dv``, ``causal`` and ``kind`` ("fwd" or "bwd")."""
    bh, s, dk, dv = call["heads"], call["s"], call["dk"], call["dv"]
    pairs = bh * (s * (s + 1) / 2 if call["causal"] else s * s)
    qkv = 4.0 * bh * s * (2 * dk + dv)
    if call["kind"] == "bwd":
        ops = pairs * 2.0 * (3 * dk + 2 * dv)
        nbytes = 2 * qkv + 4.0 * bh * s * (dv + 2)
    else:
        ops = pairs * 2.0 * (dk + dv)
        nbytes = qkv + 4.0 * bh * s * (dv + 1)
    return ops, nbytes
