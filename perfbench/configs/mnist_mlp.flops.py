"""Model FLOPs of one DFA training step of the paper's MLP, from shapes.

Per example (dims 784-800-800-10; a multiply-add is 2 operations):

* forward: 2·(784·800 + 800·800 + 800·10);
* the exact head gradient: 2·800·10 (the error is tapped at the logits,
  so no input gradient is needed);
* the local vjp of each hidden layer: its weight gradient, 2·in·out (the
  input cotangent is discarded by DFA and not computed);
* the two feedback projections, 2·10·800 each.
"""


def _dims(c: dict) -> list[int]:
    return [c["input_dim"], *c["hidden_sizes"]]


def per_example(c: dict) -> float:
    dims = _dims(c)
    n_cls = c["num_classes"]
    hidden = sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))
    head = 2 * dims[-1] * n_cls
    forward = hidden + head
    vjp = hidden
    projections = sum(2 * n_cls * b for b in dims[1:])
    return forward + head + vjp + projections


def step_flops(c: dict, traffic: dict) -> float:
    return float(traffic["data"]["batch"] * per_example(c))


def projections(c: dict, traffic: dict) -> list[dict]:
    t = traffic["data"]["batch"]
    return [{"t": t, "k": c["num_classes"], "m": m, "count": 1}
            for m in c["hidden_sizes"]]
