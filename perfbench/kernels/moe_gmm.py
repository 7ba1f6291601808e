"""The grouped matmul over the experts a chip holds
(``repro.kernels.moe_gmm``): which device ops are its calls, and the
operations and bytes of one call.

The kernel names itself in its op text: ``kernel_metadata={"kernel":
"moe_gmm"}`` (the product), ``"moe_gmm_dlhs"`` and ``"moe_gmm_drhs"``
(its vjp's products for the rows and for the weights); the HLO names of
the calls carry the same words.

A call of R routed rows multiplies them, in groups of one expert each,
by G weight blocks (C, N): 2·R·C·N operations.  Its least traffic reads
the rows once (bfloat16, as the kernel takes them on a TPU), the G
float32 weight blocks once and writes the R result rows in float32;
``drhs`` reads the rows and their gradient (both bfloat16) and writes the
G float32 weight gradients.  The rows of the static buffer past R (those
of assignments held elsewhere, which the kernel leaves unwritten) are not
work and are not counted.  R is the configuration's expected routed rows
(``expert_gemms``), not the rows a run routes.
"""

import re

_NAME = re.compile(r'"kernel":\s*"moe_gmm(_dlhs|_drhs)?"')


def match(op: str) -> bool:
    """Is this device op (its HLO text in the trace) a call of the kernel?"""
    return "tpu_custom_call" in op and bool(_NAME.search(op))


def cost(call: dict) -> tuple[float, float]:
    """-> (operations, bytes) of one call described as in the configuration's
    ``expert_gemms``."""
    r, c, n, g = call["rows"], call["c"], call["n"], call["groups"]
    ops = 2.0 * r * c * n
    if call["kind"] == "drhs":
        nbytes = 2.0 * r * c + 2.0 * r * n + 4.0 * g * c * n
    else:
        nbytes = 2.0 * r * c + 4.0 * g * c * n + 4.0 * r * n
    return ops, nbytes
