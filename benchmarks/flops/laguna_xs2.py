"""Operations and bytes one chip's share of the window / full attention MoE
decoder requires (grouped-query attention, a band of ``window`` keys in the
window layers, softmax-routed experts), from its shapes alone.

A multiply-add is two operations. Training requires the forward pass, the
gradient with respect to every weight and the gradient with respect to every
layer's input: three times the forward's matmul work. Recomputed work (each
block runs its forward twice; the backward kernels rebuild the scores) and
padded work are not counted.

**Attention's own work** is the mask's, whatever tiles the kernels compute
it in. A full layer needs half the score matrix: position ``p`` meets ``p +
1`` keys. A window layer needs the BAND: position ``p`` meets ``min(p + 1,
window)`` keys, ``sum_p min(p + 1, 512)`` = 1,966,336 pairs a head at
T 4,096 (11.7% of the matrix; the kernels' sub-tiles execute 17.6%). Its
bytes: q, k, v and the output once forward, and q, k, v, the output's
cotangent in and the three gradients out backward, at the module's
precision, the keys and values of the ``kv_heads`` heads only: no tiling
can push a share of this roofline past 100%.

The routed experts' rows depend on the routing. From shapes the expected
share is taken: ``top_k * held_count / n_routed`` rows a token and sparse
layer (1.0 for 8 of 256 with 32 held); the grouped matmul's own cost takes
the rows a token brought from the program's counter where a run has it.
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float32": 4}


def _m(config: dict) -> dict:
    return config["model"]


def _count(config: dict, kind: str) -> int:
    return sum(1 for k in _m(config)["mixers"] if k == kind)


def _heads(config: dict, kind: str) -> int:
    m = _m(config)
    return m["heads"] if kind == "full" else m["window_heads"]


def routed_rows_per_token(config: dict) -> float:
    m = _m(config)
    return m["top_k"] * m["held_count"] / m["n_routed"]


def score_pairs(config: dict, kind: str) -> float:
    """(query, key) pairs a head's mask keeps in one sequence."""
    m, t = _m(config), int(config["data"]["seq_len"])
    if kind == "full":
        return t * (t + 1) / 2
    w = min(int(m["window"]), t)
    return w * (w + 1) / 2 + (t - w) * w


def _attn_train_cost(config: dict, kind: str) -> tuple:
    """(FLOPs, bytes) of attention proper for one training sequence in the
    layers of ``kind``: forward 2 matmuls over the kept pairs (scores,
    values), backward 4 counted (dv, dp, dq, dk; the kernels' own score
    recomputation is not): three times the forward."""
    m, t = _m(config), int(config["data"]["seq_len"])
    size, layers = _BYTES[config["precision"]["module"]], _count(config, kind)
    h, g, d = _heads(config, kind), m["kv_heads"], m["v_dim"]
    flops = 3.0 * 2.0 * score_pairs(config, kind) * h * 2 * d * layers
    # forward: q, o a query head; k, v a key-value head. Backward: q, do in
    # and dq out a query head; k, v in and dk, dv out a key-value head
    per_layer = t * d * ((2 * h + 2 * g) + (3 * h + 4 * g))
    return flops, float(size * per_layer * layers)


def attn_train_cost_per_sample(config: dict) -> tuple:
    """The full-attention layers' (what ``attn_roofline_pct`` reads)."""
    return _attn_train_cost(config, "full")


def attn_window_train_cost_per_sample(config: dict) -> tuple:
    """The window layers': the band's own work."""
    return _attn_train_cost(config, "window")


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
    """Every other matmul of the forward pass, per token: both kinds of
    mixer's projections and gates, dense MLP, shared expert, router, head."""
    m = _m(config)
    d, g, hd = m["dim"], m["kv_heads"], m["v_dim"]

    def mixer(kind):
        h = _heads(config, kind)
        return d * h * hd + 2 * d * g * hd + h * hd * d + d * h

    dense = 3 * d * m["dense_width"]
    shared = 3 * d * m["n_shared"] * m["expert_width"]
    router = d * m["n_routed"]
    sparse = m["layers"] - m["first_dense"]
    head = d * int(config["data"]["vocab"])
    return 2.0 * (_count(config, "full") * mixer("full")
                  + _count(config, "window") * mixer("window")
                  + m["first_dense"] * dense + sparse * (shared + router) + head)


def train_flops_per_sample(config: dict) -> float:
    """One sequence through forward and backward, the held experts at the
    expected rows a token."""
    t = int(config["data"]["seq_len"])
    return (3.0 * t * dense_fwd_flops_per_token(config)
            + attn_train_cost_per_sample(config)[0]
            + attn_window_train_cost_per_sample(config)[0]
            + expert_train_cost_per_sample(config)[0])
