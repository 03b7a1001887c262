"""Operations and bytes one chip's share of the hybrid decoder requires
(delta-rule linear attention and latent attention over sparse experts),
from its shapes alone.

A multiply-add is two operations. Training requires the forward pass, the
gradient with respect to every weight and the gradient with respect to every
layer's input: three times the forward's matmul work. Recomputed work (each
block runs its forward twice), padded work and the chunked form's own extra
products are not counted. Causal attention needs half the score matrix.

**The delta rule's own work** is the recurrence's, whatever chunk size the
program computes it in: a position and head decays the state, reads it with
the key (``S^T k``), writes the rank-one update and reads it with the query
(``S^T q``). Counted as the issue fixes it: 7 multiply-adds an entry of the
``dk x dv`` state a position forward (decay 1, the two reads 2 each, the
update 2), and twice that backward. Its bytes: q, k, v, the log-decay and
the output once each way, beta beside them. A chunked program does more
operations than this (the intra-chunk products and the solve) and fewer
sequential steps; the share of the roofline is of the required work, so it
cannot pass 100% by the program's choice of chunk.

The routed experts' rows depend on the routing. From shapes the expected
share is taken: ``top_k * held_count / n_routed`` rows a token and sparse
layer (0.125 for 8 of 512 with 8 held).
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float32": 4}


def _m(config: dict) -> dict:
    return config["model"]


def _count(config: dict, kind: str) -> int:
    return sum(1 for k in _m(config)["mixers"] if k == kind)


def routed_rows_per_token(config: dict) -> float:
    m = _m(config)
    return m["top_k"] * m["held_count"] / m["n_routed"]


def kda_train_cost_per_sample(config: dict) -> tuple:
    """(FLOPs, bytes) of the delta rule proper for one training sequence,
    all the linear-attention layers."""
    m, t = _m(config), int(config["data"]["seq_len"])
    d, layers = m["delta_head_dim"], _count(config, "delta")
    macs_fwd = 7.0 * d * d * m["heads"] * t
    flops = 2.0 * macs_fwd * 3 * layers
    size = _BYTES[config["precision"]["module"]]
    # q, k, v, o in the module's dtype, the log-decay in float32, beta a head
    one_way = t * m["heads"] * (4 * d * size + d * 4 + 4)
    return flops, float(2 * one_way * layers)


def attn_fwd_flops_per_sequence(config: dict) -> float:
    """Scores and values of one sequence in one latent layer, forward."""
    m, t = _m(config), int(config["data"]["seq_len"])
    pairs = t * (t + 1) / 2
    return 2.0 * pairs * m["heads"] * (m["nope"] + m["rope"] + m["v_dim"])


def attn_train_cost_per_sample(config: dict) -> tuple:
    """(FLOPs, bytes) of attention proper for one training sequence, the
    latent layers: forward 2 matmuls, backward 4 counted (the kernel's own
    score recomputation is not): three times the forward."""
    m, t = _m(config), int(config["data"]["seq_len"])
    size, layers = _BYTES[config["precision"]["module"]], _count(config, "latent")
    flops = 3.0 * attn_fwd_flops_per_sequence(config) * layers
    qk, v = m["nope"] + m["rope"], m["v_dim"]
    per_head = 3 * t * (2 * qk + 2 * v)       # fwd in/out; bwd in; grads out
    return flops, float(size * m["heads"] * per_head * layers)


def expert_train_cost_per_sample(config: dict,
                                 rows_per_token: float = None) -> tuple:
    """(FLOPs, bytes) of the routed experts' grouped matmuls for one
    training sequence, all sparse layers, at ``rows_per_token`` rows of held
    experts a token and sparse layer (the expected rows when None)."""
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
    """Every other matmul of the forward pass, per token: both mixers'
    projections and gates, dense MLP, shared expert, router, head."""
    m = _m(config)
    d, h = m["dim"], m["heads"]
    qk, v, r = m["nope"] + m["rope"], m["v_dim"], m["kv_rank"]
    latent = (d * h * qk + d * (r + m["rope"]) + r * h * (m["nope"] + v)
              + h * v * d + d * h)
    hd = m["delta_head_dim"]
    delta = 5 * d * h * hd + 2 * d * h
    dense = 3 * d * m["dense_width"]
    shared = 3 * d * m["n_shared"] * m["expert_width"]
    router = d * m["n_routed"]
    sparse = m["layers"] - m["first_dense"]
    head = d * int(config["data"]["vocab"])
    return 2.0 * (_count(config, "latent") * latent
                  + _count(config, "delta") * delta
                  + m["first_dense"] * dense + sparse * (shared + router) + head)


def train_flops_per_sample(config: dict) -> float:
    """One sequence through forward and backward."""
    t = int(config["data"]["seq_len"])
    return (3.0 * t * dense_fwd_flops_per_token(config)
            + attn_train_cost_per_sample(config)[0]
            + kda_train_cost_per_sample(config)[0]
            + expert_train_cost_per_sample(config)[0])
