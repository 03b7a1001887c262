"""Operations and bytes one of two expert-parallel chips' share of the
compressed-convolutional-attention MoE decoder requires (grouped-query
attention in a latent narrower than the model, a top-1 MLP router with a
choice that is no expert, wide experts, a tied head), from its shapes alone.

A multiply-add is two operations. Training requires the forward pass, the
gradient with respect to every weight and the gradient with respect to every
layer's input: three times the forward's matmul work. Recomputed work (each
block runs its forward twice; the backward kernels rebuild the scores) and
padded work are not counted. Causal attention needs half the score matrix.

**The mixing's own work** is what compressed convolutional attention adds
between its projections and its scores, whatever form the program computes
it in: the head-wise convolution is ``K1`` products of a head's ``e``
channels with an ``e x e`` matrix a position, ``K1 * (H + G) * e * e``
multiply-adds a token forward and twice that backward (the means, the
depthwise convolution, the normalisation and the value shift are
elementwise and carry no matmul); its bytes are ``q~``, ``k~`` and ``v`` in
and ``q``, ``k`` and ``v`` out at the module's precision, and their
cotangents once the other way. A program that splits a tap, pads a head or
keeps an intermediate in float32 does more; the share is of the required
work, so no implementation can push it past 100%.

The routed experts' rows depend on the routing. From shapes the expected
share is taken: ``top_k * held_count`` over the router's outputs (8 of 17
for one choice of 16 experts or none, 8 held); the grouped matmul's own
cost takes the rows a token brought from the program's counter where a run
has it.
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float32": 4}


def _m(config: dict) -> dict:
    return config["model"]


def routed_rows_per_token(config: dict) -> float:
    m = _m(config)
    return m["top_k"] * m["held_count"] / (m["n_routed"] + 1)


def attn_train_cost_per_sample(config: dict) -> tuple:
    """(FLOPs, bytes) of attention proper for one training sequence, every
    layer: forward 2 matmuls over the causal pairs (scores, values),
    backward 4 counted (the kernels' own score recomputation is not): three
    times the forward."""
    m, t = _m(config), int(config["data"]["seq_len"])
    size = _BYTES[config["precision"]["module"]]
    h, g, d, layers = m["heads"], m["kv_heads"], m["v_dim"], m["layers"]
    flops = 3.0 * 2.0 * (t * (t + 1) / 2) * h * 2 * d * layers
    # forward: q, o a query head; k, v a key-value head. Backward: q, do in
    # and dq out a query head; k, v in and dk, dv out a key-value head
    per_layer = t * d * ((2 * h + 2 * g) + (3 * h + 4 * g))
    return flops, float(size * per_layer * layers)


def cca_mix_train_cost_per_sample(config: dict) -> tuple:
    """(FLOPs, bytes) of the mixing between the projections and the scores
    for one training sequence, every layer (the module's note)."""
    m, t = _m(config), int(config["data"]["seq_len"])
    size = _BYTES[config["precision"]["module"]]
    h, g, e, layers = m["heads"], m["kv_heads"], m["v_dim"], m["layers"]
    taps = m["cca_conv"][1]
    flops = 3.0 * 2.0 * taps * (h + g) * e * e * t * layers
    one_way = t * size * 2 * ((h + g) * e + g * e)      # in and out
    return flops, float(2 * one_way * layers)


def expert_train_cost_per_sample(config: dict,
                                 rows_per_token: float = None) -> tuple:
    """(FLOPs, bytes) of the routed experts' grouped matmuls for one
    training sequence, all layers, at ``rows_per_token`` rows of held
    experts a token and layer (the expected rows when None)."""
    m, t = _m(config), int(config["data"]["seq_len"])
    size = _BYTES[config["precision"]["module"]]
    if rows_per_token is None:
        rows_per_token = routed_rows_per_token(config)
    rows = t * rows_per_token
    d, f = m["dim"], m["expert_width"]
    sparse = m["layers"] - m["first_dense"]
    flops = 3.0 * rows * 3 * 2 * d * f * sparse
    batch = int(config["recipe"]["batch_size"])
    weights = m["held_count"] * 3 * d * f / batch     # shared by a batch
    acts = rows * (d + 2 * f + f + d)                # x in; g, u out; h in; y out
    return flops, float(size * 3 * (acts + weights) * sparse)


def dense_fwd_flops_per_token(config: dict) -> float:
    """Every other matmul of the forward pass, per token: the mixers' four
    projections, the router's four layers, the tied head."""
    m = _m(config)
    d, h, g, e, rh = m["dim"], m["heads"], m["kv_heads"], m["v_dim"], m["router_hidden"]
    mixer = 2 * d * h * e + 2 * d * g * e
    router = d * rh + 2 * rh * rh + rh * (m["n_routed"] + 1)
    head = d * int(config["data"]["vocab"])
    return 2.0 * (m["layers"] * (mixer + router) + head)


def train_flops_per_sample(config: dict) -> float:
    """One sequence through forward and backward, the held experts at the
    expected rows a token."""
    t = int(config["data"]["seq_len"])
    return (3.0 * t * dense_fwd_flops_per_token(config)
            + attn_train_cost_per_sample(config)[0]
            + cca_mix_train_cost_per_sample(config)[0]
            + expert_train_cost_per_sample(config)[0])
