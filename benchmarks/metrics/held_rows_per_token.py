"""Local training program: rows of held experts a token brought to a sparse
layer, the mean over the layers and the program's steps, from the program's
``model`` counter group (``expert_mm_roofline_pct.rows_per_token``: rows over
steps x tokens); ``top_k * held / n_routed`` is expected (0.125 for 8 of 512
with 8 held). Printed beside it: the share of tokens among whose chosen
groups is a held expert's (``group_tokens.<layer>`` over the same steps)."""

from benchmarks.metrics.expert_mm_roofline_pct import rows_per_token


def read(ctx):
    per_token = rows_per_token(ctx)
    if per_token is None:        # a program without the counter, or no step
        return None
    from fedml_tpu.obs import model_counters

    sums = {"steps": 0.0, "group_tokens": 0.0}
    for key, value in model_counters().items():
        kind = key.partition(".")[0]
        if kind in sums:
            sums[kind] += value
    config = ctx["config"]
    tokens = int(config["recipe"]["batch_size"]) * int(config["data"]["seq_len"])
    print(f"held_rows_per_token: over {sums['steps']:.0f} layer-steps of "
          f"{tokens} tokens; a held expert's group stood for "
          f"{sums['group_tokens'] / (sums['steps'] * tokens):.4f} of the tokens",
          flush=True)
    return per_token
