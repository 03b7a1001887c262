"""Operations and bytes the CIFAR ResNet requires, from its shapes alone.

A multiply-add is two operations. Training a sample requires the forward
pass, the gradient with respect to every weight, and the gradient with
respect to every layer's input but the first layer's (nothing upstream
needs it): recomputed or padded work is not counted. Bytes are the least a
convolution has to move at the module's precision: each operand read once
and each result written once, in each of its passes; weights are shared by
a batch, so their bytes per sample are divided by the batch size.
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float32": 4}


def conv_layers(config: dict) -> list:
    """(h_out, w_out, k, c_in, c_out, stride) of every convolution, the
    stem first."""
    m, d = config["model"], config["data"]
    h, w, cin = d["input_shape"]
    layers = [(h, w, 3, cin, m["widths"][0], 1)]
    prev = m["widths"][0]
    for stage, f in enumerate(m["widths"]):
        for b in range(int(m["blocks_per_stage"])):
            s = 2 if stage > 0 and b == 0 else 1
            h, w = h // s, w // s
            layers.append((h, w, 3, prev, f, s))
            layers.append((h, w, 3, f, f, 1))
            if s != 1 or prev != f:
                layers.append((h, w, 1, prev, f, s))
            prev = f
    return layers


def conv_fwd_flops(h: int, w: int, k: int, cin: int, cout: int) -> int:
    return 2 * h * w * k * k * cin * cout


def conv_train_cost_per_sample(config: dict) -> tuple:
    """(FLOPs, bytes) of all convolutions for one training sample."""
    size = _BYTES[config["precision"]["module"]]
    batch = int(config["recipe"]["batch_size"])
    flops = nbytes = 0.0
    for i, (h, w, k, cin, cout, s) in enumerate(conv_layers(config)):
        passes = 2 if i == 0 else 3          # no input gradient for the stem
        flops += passes * conv_fwd_flops(h, w, k, cin, cout)
        x, y = h * s * w * s * cin, h * w * cout
        wts = k * k * cin * cout / batch
        nbytes += size * passes * (x + y + wts)
    return flops, nbytes


def train_flops_per_sample(config: dict) -> float:
    """Convolutions as above plus the dense head's three passes."""
    flops, _ = conv_train_cost_per_sample(config)
    width, classes = config["model"]["widths"][-1], config["data"]["classes"]
    return flops + 3 * 2 * width * classes
