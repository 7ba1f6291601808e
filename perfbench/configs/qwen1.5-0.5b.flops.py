"""Model FLOPs of one DFA training step of qwen1.5-0.5b, from shapes.

Counted per token (d = hidden, f = intermediate, V = vocab, S = sequence,
L = layers; a multiply-add is 2 operations):

* forward: the seven projections of each block, 2·(4d² + 3df), causal
  attention 2·S·d (QKᵀ and PV over S/2 keys on average), and the
  unembedding 2·d·V;
* the exact head backward: weight and input gradients of the
  unembedding, 4·d·V;
* the local vjp of each block: twice its forward (weight gradients plus
  the cotangents inside the block that reach them).  The forward that
  ``segment_grads`` recomputes inside ``jax.vjp`` is not counted;
* the 25 feedback projections (24 blocks and the embedding), 2·d·d each.
"""


def per_token(c: dict, seq: int) -> float:
    d = c["hidden_size"]
    f = c["intermediate_size"]
    v = c["vocab_size"]
    n_layers = c["num_hidden_layers"]
    hd = c["head_dim"]
    q = c["num_attention_heads"] * hd
    kv = c["num_key_value_heads"] * hd
    proj = 2 * (d * q + 2 * d * kv + q * d + 3 * d * f)
    attn = 2 * seq * q            # 2 matmuls x 2 ops x S/2 keys x width
    block_fwd = proj + attn
    head_fwd = 2 * d * v
    head_bwd = 4 * d * v
    vjp = 2 * block_fwd
    projections = (n_layers + 1) * 2 * d * d
    return n_layers * (block_fwd + vjp) + head_fwd + head_bwd + projections


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
