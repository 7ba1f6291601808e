"""Direct Feedback Alignment training engine (the paper's algorithm).

For every block k the gradient is computed from the *output error only*
(paper Eq. 1):   δ(k) = B(k)·e  ⊙ local-derivative, realised as

    δ(k) = photonic_project(e, B(k))       # the MRR weight-bank product,
                                           # with measured analog noise
    grads(k) = local_vjp(block_k, x_k)(δ(k))   # exact *within* the block

The per-layer loop is a ``lax.map`` with **no loop-carried dependency** —
unlike backprop there is no sequential chain, which is the systems property
the paper exploits (all layers updated in parallel during the backward
pass).  The error is computed once and broadcast; under a sharded mesh this
is ONE collective instead of backprop's L chained backward matmuls.

For an MLP of DenseBlocks this reduces *exactly* to the paper's update:
local vjp through the activation contributes the ⊙ g'(a) Hadamard, and
grad_W = (B e ⊙ g'(a)) · h_inᵀ.

Error compression (`ternary` per the paper's ref [48], or `int8`) is applied
to e before projection/broadcast — the gradient-compression knob for
distributed training.

This module registers the ``dfa`` algorithm: value_and_grad per Eq. 1.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.algos import base
from repro.core import feedback as fb_lib
from repro.core import photonics
from repro.dist.sharding import unshard_fsdp
from repro.models.base import segment_stats
from repro.utils import prng
from repro.utils.tree import path_map


@dataclasses.dataclass(frozen=True)
class DFAConfig:
    """Config for the whole DFA algorithm family (bp ignores it)."""

    photonics: photonics.PhotonicConfig = dataclasses.field(
        default_factory=lambda: photonics.PRESETS["ideal"]
    )
    feedback: fb_lib.FeedbackConfig = dataclasses.field(
        default_factory=fb_lib.FeedbackConfig
    )
    error_compress: str = "none"  # none | ternary | int8
    # photonic execution backend: auto | ref | pallas | a PhotonicBackend
    # instance (see core.photonics.register_backend / get_backend)
    backend: str | photonics.PhotonicBackend = "auto"
    # Freeze norm scales in DFA blocks.  The cotangent at each norm output
    # exists ONLY to produce the norm-scale gradient (DFA discards input
    # cotangents), yet it costs a (B,S,D) model-axis all-reduce per matmul
    # group per layer.  Freezing norms DCEs those all-reduces (§Perf G1);
    # norm scales stay at init (a documented training-semantics trade).
    freeze_norms: bool = False


_NORM_PAT = ("norm", "ln1", "ln2", "ln3", "ln_enc", "/ln/")


def _is_norm_path(path: str) -> bool:
    return any(p in path for p in _NORM_PAT)


def freeze_norm_leaves(tree):
    """stop_gradient on norm-scale leaves: their grads become zero and XLA
    dead-code-eliminates the (B,S,D) all-reduces that fed them."""
    return path_map(
        lambda p, x: jax.lax.stop_gradient(x) if _is_norm_path(p) else x, tree)


def compress_error(e, mode: str):
    """Compress the error before broadcast/projection (ref [48])."""
    if mode == "none":
        return e
    if mode == "ternary":
        # sparse ternarisation: keep only errors well above the mean
        # (swept in EXPERIMENTS.md — tau=2.0 best at 0.25 B/element;
        # denser ternary loses more accuracy at equal steps)
        a = jnp.abs(e)
        tau = 2.0 * jnp.mean(a)
        keep = a > tau
        scale = jnp.sum(a * keep) / jnp.maximum(jnp.sum(keep), 1.0)
        return jnp.sign(e) * keep * scale
    if mode == "int8":
        amax = jnp.maximum(jnp.max(jnp.abs(e)), 1e-12)
        q = jnp.round(jnp.clip(e / amax, -1, 1) * 127.0)
        return (q / 127.0 * amax).astype(e.dtype)
    raise ValueError(f"unknown error_compress {mode!r}")


def init_feedback(model, key, cfg: DFAConfig):
    """Fixed random feedback for every segment + the embed path."""
    d_tap = model.d_tap
    fb = {}
    for spec in model.segment_specs():
        fb[spec.name] = fb_lib.make_feedback(
            prng.fold_name(key, spec.name), spec.n_layers, spec.d_inject, d_tap,
            cfg.feedback,
        )
    # embed feedback: inject at embed output (d_inject of first segment)
    first = model.segment_specs()[0]
    fb["embed"] = fb_lib.make_feedback(
        prng.fold_name(key, "embed"), 1, first.d_inject, d_tap, cfg.feedback
    )[0]
    return fb


def _project(e, bmat, cfg: DFAConfig, key):
    """δ = e·Bᵀ through the photonic execution model."""
    return photonics.photonic_project(
        e, bmat, cfg.photonics, key, backend=cfg.backend)


def forward_with_error(model, params, cfg: DFAConfig, batch):
    """Shared forward: embed → segments → head → loss, returning everything
    the DFA-family backwards need.  Head gradients are exact; the error is
    tapped per model.error_tap, compressed, and stop_gradient'd (on hardware
    e is fetched from SRAM & re-encoded each cycle — never differentiated).
    """
    has_embed_params = len(jax.tree_util.tree_leaves(params.get("embed", {}))) > 0
    if has_embed_params:
        x0, embed_vjp = jax.vjp(
            lambda pe: model.embed({**params, "embed": pe}, batch),
            params["embed"],
        )
    else:
        x0 = model.embed(params, batch)
        embed_vjp = None

    x_final, saved, auxes = model.run_segments(params, x0)

    logits, head_vjp = jax.vjp(
        lambda ph, xf: model.head_logits({**params, "head": ph}, xf, batch),
        params["head"], x_final,
    )
    loss, loss_vjp, metrics = jax.vjp(
        lambda lg: model.loss_from_logits(lg, batch), logits, has_aux=True
    )
    (e_logits,) = loss_vjp(jnp.float32(1.0))
    g_head, e_hidden = head_vjp(e_logits)

    e_tap = e_logits if model.error_tap == "logits" else e_hidden
    if model.error_tap == "hidden":
        # broadcast e in the model's compute dtype (the analog encoding
        # is <= 7 effective bits anyway — f32 error transport is waste)
        e_tap = e_tap.astype(x_final.dtype)
    e_tap = compress_error(e_tap, cfg.error_compress)
    e_tap = jax.lax.stop_gradient(e_tap)
    return dict(x0=x0, embed_vjp=embed_vjp, saved=saved, auxes=auxes,
                g_head=g_head, e_tap=e_tap, loss=loss, metrics=metrics)


def segment_grads(model, params, cfg: DFAConfig, fwd, fb, rng, delta_fn):
    """Layer-parallel backward over every segment (no loop-carried deps).

    ``delta_fn(spec, e_seg, bmat, key, y)`` produces the cotangent injected
    at the block output — the only point where DFA variants differ."""
    grads = {}
    for spec in model.segment_specs():
        tape = fwd["saved"][spec.name]
        fb_seg = fb[spec.name]
        seg_key = prng.fold_name(rng, spec.name)
        e_seg = spec.adapt_error(fwd["e_tap"]) if spec.adapt_error else fwd["e_tap"]

        def per_layer(xs, spec=spec, fb_seg=fb_seg, seg_key=seg_key,
                      extras=tape.extras, e_seg=e_seg):
            bp, xk, idx = xs
            bmat = fb_lib.feedback_for(fb_seg, idx)
            kk = jax.random.fold_in(seg_key, idx)

            def local(p):
                if cfg.freeze_norms:
                    p = freeze_norm_leaves(p)
                return spec.apply(unshard_fsdp(p), xk, extras)

            (y, _aux), vjp = jax.vjp(local, bp)
            delta = delta_fn(spec, e_seg, bmat, kk, y)
            (g,) = vjp((delta.astype(y.dtype), jnp.float32(1.0)))
            return g

        xs = (params[spec.name], tape.inputs, jnp.arange(spec.n_layers))
        grads[spec.name] = jax.lax.map(per_layer, xs)
    return grads


def dfa_delta(cfg: DFAConfig):
    """Eq. 1's cotangent: the global error projected through B(k)."""

    def delta_fn(spec, e_seg, bmat, key, y):
        delta = _project(e_seg, bmat, cfg, key)
        if spec.expand_delta is not None:
            return spec.expand_delta(delta, y.shape)
        return delta.reshape(y.shape)

    return delta_fn


def embed_grads(model, params, cfg: DFAConfig, fwd, fb, rng):
    """DFA cotangent at the embed output (or zeros if embed has params but
    no feedback path applies)."""
    if fwd["embed_vjp"] is not None:
        delta0 = model.embed_feedback(
            fwd["e_tap"], fb["embed"], fwd["x0"],
            lambda e, b: _project(e, b, cfg, prng.fold_name(rng, "embed")),
        )
        (g_embed,) = fwd["embed_vjp"](delta0)
        return g_embed
    if "embed" in params:
        return jax.tree_util.tree_map(jnp.zeros_like, params["embed"])
    return None


def _totals(fwd):
    aux_total = sum(fwd["auxes"].values()) if fwd["auxes"] else 0.0
    total = fwd["loss"] + aux_total
    metrics = {**fwd["metrics"], **segment_stats(fwd["saved"])}
    metrics["loss"] = total
    if fwd["auxes"]:
        metrics["aux_loss"] = aux_total
    return total, metrics


def value_and_grad(model, cfg: DFAConfig):
    """Returns fn(params, fb, batch, rng) -> ((loss, metrics), grads).

    ``grads`` matches the structure of ``params``.  Head gradients are exact;
    segment/embed gradients are DFA (photonic-noisy) per Eq. 1.
    """

    def fn(params, fb, batch, rng):
        fwd = forward_with_error(model, params, cfg, batch)
        grads = {"head": fwd["g_head"]}
        grads.update(segment_grads(model, params, cfg, fwd, fb, rng,
                                   dfa_delta(cfg)))
        g_embed = embed_grads(model, params, cfg, fwd, fb, rng)
        if g_embed is not None:
            grads["embed"] = g_embed
        total, metrics = _totals(fwd)
        return (total, metrics), grads

    return fn


def tree_cosine(a, b):
    """cos(a, b) over all leaves of two same-structure pytrees, in f32.
    0.0 for leafless trees (a parameter-free segment has no direction)."""
    f32 = lambda t: t.astype(jnp.float32)
    la = [f32(x) for x in jax.tree_util.tree_leaves(a)]
    lb = [f32(x) for x in jax.tree_util.tree_leaves(b)]
    if not la or not lb:
        return jnp.float32(0.0)
    num = sum(jnp.vdot(x, y) for x, y in zip(la, lb))
    na = jnp.sqrt(sum(jnp.vdot(x, x) for x in la))
    nb = jnp.sqrt(sum(jnp.vdot(x, x) for x in lb))
    return num / jnp.maximum(na * nb, 1e-12)


def grad_alignment(dfa_grads, bp_grads):
    """Per-subtree cosine(DFA, BP) — the 'alignment' diagnostic (the theory
    in the paper's ref [29] predicts this grows during the align phase).
    ``obs.introspect.AlignmentProbe`` samples this in-situ during fit."""
    return {name: tree_cosine(dfa_grads[name], bp_grads[name])
            for name in dfa_grads}


class DFAAlgorithm(base.Algorithm):
    """The paper's algorithm, Eq. 1."""

    name = "dfa"

    def init_extra_state(self, model, key, cfg: DFAConfig):
        return init_feedback(model, key, cfg)

    def value_and_grad(self, model, cfg: DFAConfig):
        return value_and_grad(model, cfg)


base.register(DFAAlgorithm())
