"""Mixture-of-Experts FFN (qwen2-moe, Moonlight/DeepSeek-V3, Kimi K2).

Routing is over all ``n_experts``; the layer computes only the experts it
holds, ``experts_held = (lo, hi)``: the chip's share under expert
parallelism (the whole layer where it is None).  What the experts held
elsewhere add is left out, and the partial result goes on to the next
layer, as it would ahead of the expert-parallel exchange.

* Router: float32 logits at ``highest`` precision, scored by ``softmax``
  (qwen2-moe) or ``sigmoid`` (DeepSeek-V3's ``scoring_func`` with its
  ``noaux_tc`` selection).  Sigmoid routing holds a per-expert bias
  (``e_score_correction_bias``) that is added to the scores for selection
  only: the weights are the selected scores without it, and its gradient
  is stopped, so an optimizer leaves it as it is.  The top-k weights are
  normalised (``norm_topk_prob``) and scaled by ``routed_scale``.
* Dispatch drops no token: the (token, slot) assignments are sorted by
  held expert (the others last), the rows are gathered into a static
  buffer of T·top_k rows, and ``kernels.moe_gmm.grouped_matmul`` runs the
  gate/up and down products over the held experts; its work follows the
  rows actually routed here, and it leaves the rows past them unwritten.
  The routed rows come back to (token, slot) order by the inverse
  permutation (zeros for assignments held elsewhere) and are summed with
  their weights.  Gathers both ways, in the forward and in the vjp, and
  none reads a row past the routed ones.
* Shared experts run densely on every token, once.

Returns ``(y, aux)``: ``lb_loss`` and ``z_loss`` (Switch load balancing
and router z-loss, for softmax routing; the block weights them), and
three routing counters, ``routed_here`` (share of assignments on held
experts), ``load_max_over_mean`` (over the held experts) and
``rows_routed``.

The expert products are digital under any photonic forward context: the
grouped kernel does not go through ``photonics.forward_matmul``.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.kernels.moe_gmm import grouped_matmul
from repro.nn import activations
from repro.nn.linear import GatedMLP, Linear
from repro.nn.module import Module, named_key

# routing counters every MoE call returns in its aux
COUNTERS = ("routed_here", "load_max_over_mean", "rows_routed")
# the selection bias's initial spread: the published values are learned, and
# random weights need a bias small against the scores' own spread
BIAS_INIT_STD = 0.05


def _rows_of(sorted_rows, inv, held):
    """Each assignment's row of a sorted buffer, zeros for assignments held
    elsewhere (whose rows the grouped kernel leaves unwritten)."""
    idx = jnp.where(held, inv, sorted_rows.shape[0])
    return sorted_rows.at[idx].get(mode="fill", fill_value=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _dispatch(x, order, inv, held, k):
    """Rows of x for the sorted assignments: x[order // k] (T·k, d).  Its
    vjp gathers the held assignments' rows back by ``inv`` and sums each
    token's k slots."""
    return x[order // k]


def _dispatch_fwd(x, order, inv, held, k):
    return _dispatch(x, order, inv, held, k), (inv, held)


def _dispatch_bwd(k, res, g):
    inv, held = res
    return _rows_of(g, inv, held).reshape(-1, k, g.shape[-1]).sum(1), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _collect(out, order, inv, held):
    """The sorted expert output back in (token, slot) order; its vjp
    sorts the cotangent again, zero past the routed rows."""
    return _rows_of(out, inv, held)


def _collect_fwd(out, order, inv, held):
    return _rows_of(out, inv, held), (order, held)


def _collect_bwd(res, g):
    order, held = res
    return jnp.where(held[order][:, None], g[order], 0), None, None, None


_collect.defvjp(_collect_fwd, _collect_bwd)


@dataclasses.dataclass(frozen=True)
class MoE(Module):
    d_model: int
    d_ff_expert: int
    n_experts: int
    top_k: int
    n_shared_experts: int = 0
    d_ff_shared: int | None = None  # defaults to d_ff_expert per shared expert
    experts_held: tuple[int, int] | None = None  # [lo, hi); None: all
    scoring: str = "softmax"  # softmax | sigmoid (with the selection bias)
    norm_topk_prob: bool = True
    routed_scale: float = 1.0
    activation: str = "silu"
    dtype: jnp.dtype = jnp.float32

    @property
    def held(self) -> tuple[int, int]:
        return self.experts_held or (0, self.n_experts)

    def _expert_init(self, key):
        return GatedMLP(self.d_model, self.d_ff_expert, self.activation,
                        self.dtype).init(key)

    def init(self, key):
        lo, hi = self.held
        # one key per expert of the whole layer: a share holds the same
        # weights as the uncut layer's experts lo..hi
        keys = jax.random.split(named_key(key, "experts"), self.n_experts)[lo:hi]
        p = {
            "router": Linear(self.d_model, self.n_experts,
                             dtype=self.dtype).init(named_key(key, "router")),
            "experts": jax.vmap(self._expert_init)(keys),
        }
        if self.scoring == "sigmoid":
            p["router"]["bias"] = (jax.random.normal(
                named_key(key, "select_bias"), (self.n_experts,))
                * BIAS_INIT_STD).astype(self.dtype)
        if self.n_shared_experts:
            p["shared"] = GatedMLP(self.d_model, self._d_shared, self.activation,
                                   self.dtype).init(named_key(key, "shared"))
        return p

    @property
    def _d_shared(self) -> int:
        return (self.d_ff_shared or self.d_ff_expert) * self.n_shared_experts

    def route(self, params, x_flat):
        """x_flat (T, d) -> (logits (T, E), scores (T, E), experts (T, K),
        weights (T, K))."""
        logits = jnp.dot(x_flat.astype(jnp.float32),
                         params["router"]["w"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        if self.scoring == "softmax":
            scores = choice = jax.nn.softmax(logits, axis=-1)
        elif self.scoring == "sigmoid":
            scores = jax.nn.sigmoid(logits)
            bias = jax.lax.stop_gradient(params["router"]["bias"]).astype(jnp.float32)
            choice = scores + bias
        else:
            raise ValueError(f"unknown scoring {self.scoring!r}")
        _, experts = jax.lax.top_k(choice, self.top_k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
        if self.norm_topk_prob:
            weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
        return logits, scores, experts, weights * self.routed_scale

    def _experts(self, params, x_flat, experts):
        """The held experts' outputs for every assignment, in (token, slot)
        order (T·K, d) — zeros for assignments held elsewhere — and the
        rows each held expert received (H,)."""
        lo, hi = self.held
        n_held = hi - lo
        k = self.top_k
        flat = experts.reshape(-1)
        held = (flat >= lo) & (flat < hi)
        local = jnp.where(held, flat - lo, n_held)
        order = jnp.argsort(local, stable=True).astype(jnp.int32)
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=jnp.int32))
        sizes = jnp.sum(local[:, None] == jnp.arange(n_held)[None, :], axis=0,
                        dtype=jnp.int32)
        xs = _dispatch(x_flat, order, inv, held, k)
        ew = params["experts"]
        gate_up = jnp.concatenate([ew["gate"]["w"], ew["up"]["w"]], axis=-1)
        h = grouped_matmul(xs, gate_up, sizes)
        g, _ = activations.get(self.activation)
        f = self.d_ff_expert
        out = grouped_matmul(g(h[:, :f]) * h[:, f:], ew["down"]["w"], sizes)
        return _collect(out, order, inv, held), sizes

    def __call__(self, params, x):
        """x: (B, S, d) -> (y, aux)."""
        b, s, d = x.shape
        t = b * s
        x_flat = x.reshape(t, d)
        with jax.named_scope("moe.route"):
            logits, scores, experts, weights = self.route(params, x_flat)
        with jax.named_scope("moe.experts"):
            per_slot, sizes = self._experts(params, x_flat, experts)
        with jax.named_scope("moe.combine"):
            per_slot = per_slot.reshape(t, self.top_k, d)
            y = jnp.einsum("tk,tkd->td", weights.astype(per_slot.dtype), per_slot)
            if self.n_shared_experts:
                y = y + GatedMLP(self.d_model, self._d_shared, self.activation,
                                 self.dtype)(params["shared"], x_flat)
            y = y.astype(x.dtype)
        rows = jnp.sum(sizes).astype(jnp.float32)
        aux = {
            "routed_here": rows / (t * self.top_k),
            "load_max_over_mean": jnp.max(sizes) / jnp.maximum(jnp.mean(sizes), 1e-9),
            "rows_routed": rows,
        }
        if self.scoring == "softmax":
            e = self.n_experts
            share = jnp.mean(jax.nn.one_hot(experts, e, dtype=jnp.float32).sum(1), 0)
            aux["lb_loss"] = e * jnp.sum(jnp.mean(scores, 0) * share)
            aux["z_loss"] = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
        return y.reshape(b, s, d), aux
