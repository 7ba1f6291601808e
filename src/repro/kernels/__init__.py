from repro.kernels import emu_matmul, ops, ref
from repro.kernels.emu_matmul import fused_bank_product
from repro.kernels.photonic_matmul import photonic_matmul_pallas

__all__ = [
    "emu_matmul",
    "fused_bank_product",
    "ops",
    "ref",
    "photonic_matmul_pallas",
]
