"""Model FLOPs of one DFA training step of Moonlight-16B-A3B on one chip's
share, from shapes, and the kernel calls of a step.

Counted per token (d = hidden, V = the vocabulary slice, S = sequence, h =
heads; a multiply-add is 2 operations):

* each layer's MLA projections, 2·d·(h·(nope + rope) + r + rope + h·v) +
  2·r·h·(nope + v) (q, kv_down, o; k_up and v_up from the latent r), and
  causal attention, 2·(S/2)·h·(nope + rope + v) (QKᵀ and PV over S/2 keys on
  average);
* the dense layer's SwiGLU, 2·3·d·f; each MoE layer's router, 2·d·E, its
  shared experts, 2·3·d·f_e·n_shared, and its routed experts at the share
  a token sends to the experts held here, top_k · held / E of them
  (6 · 8 / 64 = 0.75), 2·3·d·f_e each;
* the unembedding forward 2·d·V and its exact backward 4·d·V;
* the local vjp of each layer: twice its forward.  The forward that
  ``segment_grads`` recomputes inside ``jax.vjp`` is not counted;
* the DFA projections, one per layer and one for the embedding, 2·d·d each.
"""


def _routed_share(c: dict) -> float:
    lo, hi = c["experts_held"]
    return c["num_experts_per_tok"] * (hi - lo) / c["router_outputs"]


def per_token(c: dict, seq: int) -> float:
    d, v = c["hidden_size"], c["vocab_size"]
    h, nope, rope, vd = (c["num_attention_heads"], c["qk_nope_head_dim"],
                         c["qk_rope_head_dim"], c["v_head_dim"])
    r = c["kv_lora_rank"]
    n_layers, n_dense = c["num_hidden_layers"], c["first_k_dense_replace"]
    fe = c["moe_intermediate_size"]
    attn = (2 * d * (h * (nope + rope) + r + rope + h * vd) + 2 * r * h * (nope + vd)
            + seq * h * (nope + rope + vd))
    dense = 6 * d * c["intermediate_size"]
    moe = (2 * d * c["router_outputs"] + 6 * d * fe * c["n_shared_experts"]
           + 6 * d * fe * _routed_share(c))
    layers = n_dense * (attn + dense) + (n_layers - n_dense) * (attn + moe)
    projections = (n_layers + 1) * 2 * d * d
    return 3 * layers + 2 * d * v + 4 * d * v + projections


def step_flops(c: dict, traffic: dict) -> float:
    data = traffic["data"]
    tokens = data["batch"] * data["seq"]
    return float(tokens * per_token(c, data["seq"]))


def projections(c: dict, traffic: dict) -> list[dict]:
    """The DFA projections of one step: T error rows of width K onto M."""
    data = traffic["data"]
    t = data["batch"] * data["seq"]
    d = c["hidden_size"]
    return [{"t": t, "k": d, "m": d, "count": c["num_hidden_layers"] + 1}]


def expert_gemms(c: dict, traffic: dict) -> list[dict]:
    """The grouped-matmul calls of one step, with the rows a call routes
    here at the expected share (T · top_k · held / E).  Each MoE layer
    runs its two products (gate|up: C = d, N = 2·f_e; down: C = f_e, N = d)
    in the forward scan and again inside ``jax.vjp``; the vjp adds the
    gradient for the rows (``dlhs``, C and N swapped) and for the weights
    (``drhs``) of each.  A run routes more or fewer rows than this, as its
    router and selection bias fall with the seed; a roofline share built
    on these calls is off by the ratio of the two."""
    data = traffic["data"]
    t = data["batch"] * data["seq"]
    d, fe = c["hidden_size"], c["moe_intermediate_size"]
    lo, hi = c["experts_held"]
    rows = round(t * _routed_share(c))
    n_moe = c["num_hidden_layers"] - c["first_k_dense_replace"]
    out = []
    for cc, n in ((d, 2 * fe), (fe, d)):
        out.append({"kind": "gmm", "rows": rows, "c": cc, "n": n, "groups": hi - lo,
                    "count": 2 * n_moe})
        out.append({"kind": "dlhs", "rows": rows, "c": n, "n": cc, "groups": hi - lo,
                    "count": n_moe})
        out.append({"kind": "drhs", "rows": rows, "c": cc, "n": n, "groups": hi - lo,
                    "count": n_moe})
    return out
