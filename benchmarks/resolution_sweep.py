"""Paper Fig. 5(c): test accuracy vs effective resolution of the gradient
calculation.  Noise σ = 2^(1-bits) is injected into every B(k)·e product;
the paper's dashed lines sit at 4.35 b (off-chip) and 3.31 b (on-chip)."""

from __future__ import annotations

import argparse

from repro import api
from repro.core import photonics
from repro.data import mnist, pipeline
from repro.models.mlp import MLPClassifier
from repro.train import SGDM


def run(bits_list=(2.0, 3.0, 3.31, 4.35, 6.0, 8.0), train_n=6144, test_n=1536,
        steps=384, hidden=(256, 256), seed=0):
    data = mnist.load((train_n, test_n), seed=seed)
    xtr, ytr = data["train"]
    xte, yte = data["test"]
    rows = []
    for bits in bits_list:
        cfg = photonics.PhotonicConfig(noise_std=photonics.resolution_to_sigma(bits))
        pipe = pipeline.ArrayClassification(xtr, ytr, batch_size=64, seed=seed)
        session = api.build_session(
            arch=MLPClassifier(hidden=hidden), algo="dfa", hardware=cfg,
            optimizer=SGDM(lr=0.01, momentum=0.9), seed=seed, log_every=10**9)
        state, _ = session.fit(pipe.batch, total_steps=steps, verbose=False)
        ev = session.evaluate(state, pipe.eval_batches(xte, yte, 256))
        rows.append({"bits": bits, "noise_std": cfg.noise_std,
                     "test_accuracy": 100 * ev["accuracy"]})
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    kw = dict(bits_list=(3.31, 4.35, 8.0), steps=192) if args.quick else {}
    print("fig5c_resolution: bits,noise_std,test_acc_%")
    for r in run(**kw):
        print(f"{r['bits']},{r['noise_std']:.4f},{r['test_accuracy']:.2f}")


if __name__ == "__main__":
    main()
