"""Operations and bytes one of 64 chips' share of the first stage of the
hybrid state-space / latent-MoE decoder requires (each layer ONE sub-layer:
a Mamba-2 mixer's group, position-free attention's share of heads, or a
sparse MLP of squared-ReLU experts in a latent beside a shared MLP), from
its shapes alone.

A multiply-add is two operations. Training requires the forward pass, the
gradient with respect to every weight and the gradient with respect to every
layer's input: three times the forward's matmul work. Recomputed work (each
layer runs its forward twice) and padded work (rows of experts not held,
slots of a row capacity that no row fills) are not counted. Causal attention
needs half the score matrix.

**The recurrence's own work** is the state's, whatever chunk the program
computes it in: a position and head decays the ``P x N`` state, writes the
rank-one ``dt x B^T`` into it and reads it with ``C``: 3 multiply-adds an
entry of the state a position forward, twice that backward; its bytes ``x``,
``B``, ``C``, ``dt`` and ``y`` once each way.

**An expert is TWO matrices in the latent**: ``[latent, width]`` and
``[width, latent]``, two grouped matmuls a pass where a SwiGLU has three.
The rows depend on the routing: from shapes alone a token brings ``top_k *
held_count / n_routed`` rows to a sparse layer (22 x 8 / 512 = 0.34375). A
run's own count is the program's counter as
``benchmarks/metrics/sparse_rows_per_token.py`` reads it: rows over tokens x
the steps of the SPARSE layers, the layers that keep rows.

**The latent's two projections** (``W_1`` into the latent, ``W_2`` back) run
on every token of a sparse layer and are neither the experts' nor the dense
part's: a cost function of their own.

Bytes are the least a kernel has to move at the module's precision: each
operand read once and each result written once, in each of its passes.
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float32": 4}


def _m(config: dict) -> dict:
    return config["model"]


def _count(config: dict, kind: str) -> int:
    m = _m(config)
    return sum(1 for k in list(m["mixers"]) + list(m["mlps"]) if k == kind)


def routed_rows_per_token(config: dict) -> float:
    """Rows of held experts a token brings to ONE sparse layer, expected."""
    m = _m(config)
    return m["top_k"] * m["held_count"] / m["n_routed"]


def ssd_train_cost_per_sample(config: dict) -> tuple:
    """(FLOPs, bytes) of the recurrence proper for one training sequence,
    all the state-space layers."""
    m, t = _m(config), int(config["data"]["seq_len"])
    h, p, n = m["ssd_heads"], m["ssd_head_dim"], m["ssd_state"]
    layers = _count(config, "ssd")
    flops = 2.0 * 3.0 * p * n * h * t * 3 * layers
    size = _BYTES[config["precision"]["module"]]
    # x and y a head and channel, B and C of the one group held, in the
    # module's dtype; dt a head in float32
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


def expert_train_cost_per_sample(config: dict,
                                 rows_per_token: float = None) -> tuple:
    """(FLOPs, bytes) of the routed experts' grouped matmuls (two a pass, in
    the latent) for one training sequence, all sparse layers.
    ``rows_per_token``: rows of held experts a token and sparse layer, as
    the program's counter gives them; None: the rows expected from
    shapes."""
    m, t = _m(config), int(config["data"]["seq_len"])
    size = _BYTES[config["precision"]["module"]]
    sparse = _count(config, "sparse")
    if rows_per_token is None:
        rows_per_token = routed_rows_per_token(config)
    rows = t * rows_per_token * sparse
    latent, f = m["moe_latent"], m["expert_width"]
    flops = 3.0 * rows * 2 * 2 * latent * f
    batch = int(config["recipe"]["batch_size"])
    weights = sparse * m["held_count"] * 2 * latent * f / batch
    acts = rows * (latent + f + f + latent)       # x in; u out; h in; y out
    return flops, float(size * 3 * (acts + weights))


def latent_proj_train_cost_per_sample(config: dict) -> tuple:
    """(FLOPs, bytes) of the two projections around the routed experts,
    ``[dim, latent]`` in and ``[latent, dim]`` out on every token, for one
    training sequence, all sparse layers."""
    m, t = _m(config), int(config["data"]["seq_len"])
    size = _BYTES[config["precision"]["module"]]
    sparse, d, latent = _count(config, "sparse"), m["dim"], m["moe_latent"]
    flops = 3.0 * t * 2 * 2 * d * latent * sparse
    batch = int(config["recipe"]["batch_size"])
    weights = 2 * d * latent / batch
    acts = t * 2 * (d + latent)            # each: its input in, its output out
    return flops, float(size * 3 * (acts + weights) * sparse)


def dense_fwd_flops_per_token(config: dict) -> float:
    """Every other matmul of the forward pass, per token: the mixers'
    projections, the shared MLP, the router, the head."""
    m = _m(config)
    d, h, g, hd = m["dim"], m["heads"], m["kv_heads"], m["v_dim"]
    inner = m["ssd_heads"] * m["ssd_head_dim"]
    ssd = d * (2 * inner + 2 * m["ssd_state"] + m["ssd_heads"]) + inner * d
    full = 2 * d * h * hd + 2 * d * g * hd
    sparse = 2 * d * m["shared_width"] + d * m["n_routed"]
    head = d * int(config["data"]["vocab"])
    return 2.0 * (_count(config, "ssd") * ssd + _count(config, "full") * full
                  + _count(config, "sparse") * sparse + head)


def train_flops_per_sample(config: dict) -> float:
    """One sequence through forward and backward."""
    t = int(config["data"]["seq_len"])
    return (3.0 * t * dense_fwd_flops_per_token(config)
            + latent_proj_train_cost_per_sample(config)[0]
            + expert_train_cost_per_sample(config)[0]
            + attn_train_cost_per_sample(config)[0]
            + ssd_train_cost_per_sample(config)[0])
