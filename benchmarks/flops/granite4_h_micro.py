"""Operations and bytes the first pipeline stage of the hybrid state-space
decoder requires (Mamba-2 mixers and one grouped-query attention layer over
dense MLPs, a tied head), from its shapes alone.

A multiply-add is two operations. Training requires the forward pass, the
gradient with respect to every weight and the gradient with respect to every
layer's input: three times the forward's matmul work. Recomputed work (each
block runs its forward twice) and the chunked form's own extra products are
not counted. Causal attention needs half the score matrix.

**The recurrence's own work** is the state's, whatever chunk size or
implementation the program computes it in: a position and head decays the
``P x N`` state, writes the rank-one ``dt x B^T`` into it and reads it with
``C``: 3 multiply-adds an entry of the state a position forward, and twice
that backward. Its bytes: ``x``, ``B``, ``C``, ``dt`` and ``y`` once each
way (their cotangents on the way back). A chunked program does more
operations than this (``C B^T``, the masked ``L``, the intra-chunk product)
and far fewer sequential steps; the share of the roofline is of the required
work, so it cannot pass 100% by the program's choice of chunk.
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float32": 4}


def _m(config: dict) -> dict:
    return config["model"]


def _count(config: dict, kind: str) -> int:
    return sum(1 for k in _m(config)["mixers"] if k == kind)


def ssd_train_cost_per_sample(config: dict) -> tuple:
    """(FLOPs, bytes) of the recurrence proper for one training sequence,
    all the state-space layers."""
    m, t = _m(config), int(config["data"]["seq_len"])
    h, p, n = m["ssd_heads"], m["ssd_head_dim"], m["ssd_state"]
    layers = _count(config, "ssd")
    flops = 2.0 * 3.0 * p * n * h * t * 3 * layers
    size = _BYTES[config["precision"]["module"]]
    # x and y a head and channel, B and C a layer, in the module's dtype;
    # dt a head in float32
    one_way = t * (2 * h * p * size + 2 * n * size + h * 4)
    return flops, float(2 * one_way * layers)


def attn_train_cost_per_sample(config: dict) -> tuple:
    """(FLOPs, bytes) of attention proper for one training sequence, the
    full layers: forward 2 matmuls over the causal pairs, backward 4 counted
    (the kernels' own score recomputation is not): three times the forward."""
    m, t = _m(config), int(config["data"]["seq_len"])
    size, layers = _BYTES[config["precision"]["module"]], _count(config, "full")
    h, g, d = m["heads"], m["kv_heads"], m["v_dim"]
    flops = 3.0 * 2.0 * (t * (t + 1) / 2) * h * 2 * d * layers
    # forward: q, o a query head; k, v a key-value head. Backward: q, do in
    # and dq out a query head; k, v in and dk, dv out a key-value head
    per_layer = t * d * ((2 * h + 2 * g) + (3 * h + 4 * g))
    return flops, float(size * per_layer * layers)


def dense_fwd_flops_per_token(config: dict) -> float:
    """Every other matmul of the forward pass, per token: the mixers'
    projections, the MLPs, the tied head."""
    m = _m(config)
    d, h, g, hd = m["dim"], m["heads"], m["kv_heads"], m["v_dim"]
    inner = m["ssd_heads"] * m["ssd_head_dim"]
    ssd = d * (2 * inner + 2 * m["ssd_state"] + m["ssd_heads"]) + inner * d
    full = 2 * d * h * hd + 2 * d * g * hd
    mlp = 3 * d * m["dense_width"]
    head = d * int(config["data"]["vocab"])
    return 2.0 * (_count(config, "ssd") * ssd + _count(config, "full") * full
                  + m["layers"] * mlp + head)


def train_flops_per_sample(config: dict) -> float:
    """One sequence through forward and backward."""
    t = int(config["data"]["seq_len"])
    return (3.0 * t * dense_fwd_flops_per_token(config)
            + attn_train_cost_per_sample(config)[0]
            + ssd_train_cost_per_sample(config)[0])
