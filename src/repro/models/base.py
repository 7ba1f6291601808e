"""Model protocol consumed by the DFA engine (algos/dfa.py).

A DFA-trainable model decomposes into:

    embed  →  segments (stacks of homogeneous blocks, scanned)  →  head

with parameters laid out as ``{"embed": …, <segment name>: stacked…, "head": …}``.

The forward pass (``run_segments``) *saves each block's input* — the only
activation state DFA needs (backprop would need the full chain).  The head
is split into ``head_logits`` (parameterised) and ``loss_from_logits``
(pure) so the engine can tap the error either at the logits (paper-faithful
MLP: e = ∂L/∂logits, dim = n_classes) or below the unembedding
(``hidden`` tap: e = ∂L/∂x_final, dim = d_model — the at-scale choice).
Head parameters always receive *exact* gradients, matching the paper
("the output layer weight matrix W(l) is updated using the error vector e").
"""

from __future__ import annotations

import dataclasses
import typing

import jax
import jax.numpy as jnp

from repro.nn.module import Module


@dataclasses.dataclass(frozen=True)
class SegmentSpec:
    """Static description of one stack of homogeneous blocks."""

    name: str
    n_layers: int
    d_inject: int  # feature dim at the injection point (block output)
    # apply(params_slice, x, extras) -> (y, weighted_aux_loss_scalar)
    apply: typing.Callable = dataclasses.field(compare=False)
    # optional: transform the error before projection (e.g. pool decoder
    # positions for encoder segments in enc-dec models)
    adapt_error: typing.Callable | None = dataclasses.field(default=None, compare=False)
    # optional: expand the projected delta to the block-output shape
    # (default: reshape) — e.g. broadcast a pooled delta over positions
    expand_delta: typing.Callable | None = dataclasses.field(default=None, compare=False)


@dataclasses.dataclass(frozen=True)
class SavedSegment:
    """Per-segment forward tape: stacked block inputs + shared extras."""

    inputs: typing.Any  # (L, ...) leaves — input to each block
    extras: typing.Any = None  # shared across layers (positions, enc_out, …)
    # scalar counters of the segment's forward (MoE routing), merged into
    # the step's metrics
    stats: dict | None = None


class DFAModel(Module):
    """Interface — concrete models implement the five methods below."""

    # --- static info ---
    @property
    def error_tap(self) -> str:  # "hidden" | "logits"
        return "hidden"

    @property
    def d_tap(self) -> int:
        raise NotImplementedError

    def segment_specs(self) -> tuple[SegmentSpec, ...]:
        raise NotImplementedError

    def forward_gemm_specs(self) -> list:
        """(name, m, k) of every weight-stationary forward projection of one
        streamed token — the serving analogue of ``segment_specs``, consumed
        by ``sim.pipeline.forward_workload``.  LMs implement it; models that
        are not served (whisper, the MNIST MLP head aside) may not."""
        raise NotImplementedError(
            f"{type(self).__name__} declares no forward GEMM workload")

    def counters(self) -> dict:
        """{trace counter name: metric keys} of the counters the segments'
        forward adds to the step's metrics (``SavedSegment.stats``),
        charted by an observer at its drain."""
        return {}

    # --- forward parts ---
    def embed(self, params, batch):
        raise NotImplementedError

    def run_segments(self, params, x0):
        """-> (x_final, {name: SavedSegment}, {name: aux_loss_scalar})"""
        raise NotImplementedError

    def head_logits(self, params, x_final, batch):
        raise NotImplementedError

    def loss_from_logits(self, logits, batch):
        """-> (loss, metrics dict)"""
        raise NotImplementedError

    # --- composed API ---
    def loss(self, params, batch):
        """Plain forward loss — used by the backprop baseline and eval."""
        x0 = self.embed(params, batch)
        x_final, saved, auxes = self.run_segments(params, x0)
        logits = self.head_logits(params, x_final, batch)
        loss, metrics = self.loss_from_logits(logits, batch)
        aux_total = sum(auxes.values()) if auxes else 0.0
        metrics = {**metrics, **segment_stats(saved)}
        if auxes:
            metrics["aux_loss"] = aux_total
        return loss + aux_total, metrics

    # --- DFA hooks with defaults ---
    def embed_feedback(self, e_tap, fb_embed, x0, project_fn):
        """Cotangent injected at the embed output.  Default: single photonic
        projection of the (flattened-leading) error to x0's feature dim."""
        delta = project_fn(e_tap, fb_embed)
        return delta.astype(x0.dtype).reshape(x0.shape)


def segment_stats(saved: dict) -> dict:
    """The counters of every segment's forward, as one flat dict."""
    return {k: v for seg in saved.values() for k, v in (seg.stats or {}).items()}


def cross_entropy_loss(logits, labels, *, mask=None, label_smoothing=0.0):
    """Mean CE over valid positions. logits (..., V), labels (...) int."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - ll
    if label_smoothing > 0.0:
        v = logits.shape[-1]
        mean_ll = jnp.mean(logits, axis=-1)
        nll = (1 - label_smoothing) * nll + label_smoothing * (logz - mean_ll)
        del v
    if mask is not None:
        denom = jnp.maximum(mask.sum(), 1.0)
        loss = (nll * mask).sum() / denom
        acc = ((jnp.argmax(logits, -1) == labels) * mask).sum() / denom
    else:
        loss = nll.mean()
        acc = (jnp.argmax(logits, -1) == labels).mean()
    return loss, {"ce_loss": loss, "accuracy": acc}
