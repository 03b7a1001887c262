"""Kernels: the least time the chip could take for the executed steps'
grouped matmuls over ``expert_mm_ms``, at the rows the held experts
computed: the program's ``model`` counter group (``rows.<layer>.<expert>``
over ``steps.<layer>``: sums over every step the program ran since its
weights were seeded, set-up's rounds and the window's, which replay one
cycle) gives the rows a step, and the window's executed step slots take that
many each. XLA's own count, printed beside it, is of the kernels' static row
capacity (every (token, choice) pair)."""

from benchmarks.trace import lm_scopes


def rows_per_token(ctx):
    """Rows of held experts a token brought to a sparse layer, the mean over
    the layers and the program's steps; None without the counter."""
    try:
        from fedml_tpu.obs import model_counters
    except ImportError:          # a program without the counter
        return None
    rows = steps = 0.0
    for key, value in model_counters().items():
        kind = key.partition(".")[0]
        rows += value if kind == "rows" else 0.0
        steps += value if kind == "steps" else 0.0
    if not steps:
        return None
    config = ctx["config"]
    tokens = int(config["recipe"]["batch_size"]) * int(config["data"]["seq_len"])
    return rows / (steps * tokens)


def read(ctx):
    if lm_scopes.parts_s(ctx) is None:      # no trace, or not this model's
        return None
    per_token = rows_per_token(ctx)
    if per_token is None:
        return None
    print(f"expert_mm_roofline_pct: {per_token:.6g} rows of held experts a "
          "token and sparse layer by the program's counter (expected from "
          "shapes: top_k * held / n_routed)", flush=True)
    return lm_scopes.roofline_pct(ctx, "experts",
                                  "expert_train_cost_per_sample",
                                  "expert_mm_roofline_pct",
                                  rows_per_token=per_token)
