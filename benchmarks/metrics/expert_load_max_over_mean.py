"""Local training program: the busiest held expert's rows over the mean
held expert's, in the sparse layer where that ratio is largest; from the
program's ``model`` counter group (rows each held expert computed, summed
over every step of every client since the weights were seeded: the packed
round sums its clients' counts; written when the round driver closes)."""


def read(ctx):
    try:
        from fedml_tpu.obs import model_counters
    except ImportError:          # a program without the counter
        return None
    layers: dict = {}
    for key, value in model_counters().items():
        kind, _, rest = key.partition(".")
        if kind == "rows":
            layers.setdefault(rest.rpartition(".")[0], []).append(value)
    ratios = [max(rows) * len(rows) / sum(rows)
              for rows in layers.values() if sum(rows) > 0]
    if not ratios:
        return None
    print("expert_load_max_over_mean: rows per held expert  " + "  ".join(
        f"{layer} {[int(r) for r in rows]}" for layer, rows in sorted(layers.items())),
        flush=True)
    return max(ratios)
