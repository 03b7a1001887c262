"""Operations and bytes one chip's share of the latent-attention MoE decoder
requires, from its shapes alone.

A multiply-add is two operations. Training requires the forward pass, the
gradient with respect to every weight and the gradient with respect to
every layer's input (the embedding's lookup needs none): three times the
forward's matmul work. Recomputed work (each block runs its forward twice)
and padded work (the query / key size rounded up to the lanes, rows of
experts not held) are not counted. Causal attention needs half the score
matrix: position ``p`` meets ``p + 1`` keys.

The routed experts' rows depend on the routing. From shapes alone the
expected share is taken: every token chooses ``top_k`` of ``n_routed``
experts, of which ``held_count`` are here, so a token brings ``top_k *
held_count / n_routed`` rows on average (0.75 for 6 of 128 with 16 held);
``train_flops_per_sample`` counts the experts so (4% of a sequence's work).
A router sends the held experts more or fewer rows than that, so the grouped
matmul's own cost takes the rows a token brought from the program's counter
(``rows_per_token``) where a run has it.

Bytes are the least a kernel has to move at the module's precision: each
operand read once and each result written once, in each of its passes.
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float32": 4}


def _m(config: dict) -> dict:
    return config["model"]


def routed_rows_per_token(config: dict) -> float:
    m = _m(config)
    return m["top_k"] * m["held_count"] / m["n_routed"]


def attn_fwd_flops_per_sequence(config: dict) -> float:
    """Scores and values of one sequence in one layer, forward, causal."""
    m, t = _m(config), int(config["data"]["seq_len"])
    pairs = t * (t + 1) / 2
    return 2.0 * pairs * m["heads"] * (m["nope"] + m["rope"] + m["v_dim"])


def attn_train_cost_per_sample(config: dict) -> tuple:
    """(FLOPs, bytes) of attention proper for one training sequence, all
    layers: forward 2 matmuls, backward 5 (scores again, dv, dp, dq, dk) of
    which the score recomputation is the kernel's own choice and is not
    counted: 2 + 4 = three times the forward."""
    m, t = _m(config), int(config["data"]["seq_len"])
    size = _BYTES[config["precision"]["module"]]
    flops = 3.0 * attn_fwd_flops_per_sequence(config) * m["layers"]
    qk, v = m["nope"] + m["rope"], m["v_dim"]
    per_head_fwd = t * (2 * qk + 2 * v)              # q, k, v in; o out
    per_head_bwd = t * (2 * qk + 2 * v) + t * (2 * qk + 2 * v)  # in; grads out
    nbytes = size * m["heads"] * (per_head_fwd + per_head_bwd) * m["layers"]
    return flops, float(nbytes)


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
    nbytes = size * 3 * (acts + weights) * sparse
    return flops, float(nbytes)


def dense_fwd_flops_per_token(config: dict) -> float:
    """Every other matmul of the forward pass, per token: attention
    projections, dense MLP, shared experts, router, head."""
    m = _m(config)
    d, h = m["dim"], m["heads"]
    qk, v, r = m["nope"] + m["rope"], m["v_dim"], m["kv_rank"]
    attn = d * h * qk + d * (r + m["rope"]) + r * h * (m["nope"] + v) + h * v * d
    dense = 3 * d * m["dense_width"]
    shared = 3 * d * m["n_shared"] * m["expert_width"]
    router = d * m["n_routed"]
    sparse = m["layers"] - m["first_dense"]
    head = d * int(config["data"]["vocab"])
    return 2.0 * (m["layers"] * attn + m["first_dense"] * dense
                  + sparse * (shared + router) + head)


def train_flops_per_sample(config: dict) -> float:
    """One sequence through forward and backward."""
    t = int(config["data"]["seq_len"])
    attn, _ = attn_train_cost_per_sample(config)
    experts, _ = expert_train_cost_per_sample(config)
    return 3.0 * t * dense_fwd_flops_per_token(config) + attn + experts
