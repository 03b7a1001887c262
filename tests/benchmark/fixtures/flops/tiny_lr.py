"""Operations the logistic regression requires, from its shapes alone: one
dense layer, forward and weight gradient (no input gradient: it is the
first layer). A multiply-add is two operations."""


def train_flops_per_sample(config: dict) -> float:
    d = config["data"]
    return 2 * 2.0 * d["input_dim"] * d["classes"]
