"""Paper Fig. 3(c): per-MAC multiplication error → effective resolution.

Simulates the single-MRR multiplication experiment (3900 random operand
pairs) through the photonic execution model and reports the error std /
effective bits for each hardware preset, against the paper's measured
values (σ=0.019 → 6.72 b single MRR; 0.098 → 4.35 b off-chip BPD;
0.202 → 3.31 b on-chip BPD)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import photonics

PAPER = {"single_mrr": (0.019, 6.72), "offchip_bpd": (0.098, 4.35),
         "onchip_bpd": (0.202, 3.31)}


def run(n: int = 3900, seed: int = 0):
    rows = []
    key = jax.random.PRNGKey(seed)
    for preset, (sigma, bits) in PAPER.items():
        cfg = photonics.preset(preset)
        # random multiplications: 1-element inner products
        ka, kb, kn = jax.random.split(jax.random.fold_in(key, hash(preset) % 2**31), 3)
        a = jax.random.uniform(ka, (n, 1), minval=-1, maxval=1)
        b = jax.random.uniform(kb, (1, 1), minval=-1, maxval=1)
        outs = photonics.photonic_matmul(a, b, cfg, key=kn)
        err = np.asarray(outs - a @ b.T).ravel()
        meas_std = float(err.std())
        scale = float(jnp.max(jnp.abs(a)) * jnp.max(jnp.abs(b)))
        meas_bits = photonics.sigma_to_resolution(meas_std / scale)
        rows.append({
            "preset": preset, "paper_sigma": sigma, "paper_bits": bits,
            "measured_sigma": meas_std, "measured_bits": meas_bits,
        })
    return rows


def main():
    print("fig3c_mac_noise: preset,paper_sigma,paper_bits,measured_bits")
    for r in run():
        print(f"{r['preset']},{r['paper_sigma']},{r['paper_bits']},{r['measured_bits']:.2f}")


if __name__ == "__main__":
    main()
