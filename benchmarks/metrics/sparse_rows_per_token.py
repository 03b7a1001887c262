"""Local training program: rows of held experts a token brought to a SPARSE
layer, the mean over the sparse layers and their steps, from the program's
``model`` counter group: ``rows.<layer>.<expert>`` over tokens x the
``steps.<layer>`` of the layers that keep ``rows.*``, and of no other. In a
model whose layers are one sub-layer each the state-space layers count their
steps under the same name and bring no row: ``held_rows_per_token`` divides
by those too and is not reported in such a cell. ``top_k * held / n_routed``
is expected (22 x 8 / 512 = 0.34375). None where the program keeps no such
counter."""


def rows_per_token(ctx):
    """-> (rows a token and sparse layer, layer-steps, sparse layers), or
    None without the counter or before the first step."""
    config = ctx["config"]
    seq_len = config["data"].get("seq_len")
    if seq_len is None:          # not a language model's cell
        return None
    try:
        from fedml_tpu.obs import model_counters
    except ImportError:
        return None
    group = dict(model_counters().items())
    rows = {}
    for key, value in group.items():
        kind, _, rest = key.partition(".")
        if kind == "rows":
            layer = rest.rpartition(".")[0]
            rows[layer] = rows.get(layer, 0.0) + value
    steps = sum(group.get(f"steps.{layer}", 0.0) for layer in rows)
    if not steps:
        return None
    tokens = int(config["recipe"]["batch_size"]) * int(seq_len)
    return sum(rows.values()) / (steps * tokens), steps, len(rows)


def read(ctx):
    got = rows_per_token(ctx)
    if got is None:
        return None
    per_token, steps, layers = got
    print(f"sparse_rows_per_token: over {steps:.0f} layer-steps of {layers} "
          "sparse layers", flush=True)
    return per_token
