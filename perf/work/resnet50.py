"""What one forward of ResNet-50 (v1.5 bottleneck, He et al. Table 1, 50-layer
column) needs, from shapes alone: convolutions and the classifier as
multiply-adds x 2; normalisation, ReLU, pooling and the residual adds are not
counted (under 1%)."""

from __future__ import annotations

from readers import device

STAGES = (3, 4, 6, 3)
WIDTH = 64


def conv_flops(side_out: int, k: int, c_in: int, c_out: int) -> float:
    return 2.0 * side_out * side_out * k * k * c_in * c_out


def forward_cost(side: int, channels: int, classes: int) -> dict:
    """One image of side x side x channels.  XLA's SAME padding: a stride-2
    convolution or pool gives ceil(side / 2)."""
    half = lambda n: -(-n // 2)  # noqa: E731
    side = half(side)
    flops = conv_flops(side, 7, channels, WIDTH)
    weights = 7 * 7 * channels * WIDTH
    side = half(side)   # 3x3 max pool, stride 2
    c_in = WIDTH
    for i, blocks in enumerate(STAGES):
        f = WIDTH * 2 ** i
        for j in range(blocks):
            stride = 2 if i > 0 and j == 0 else 1
            out = half(side) if stride == 2 else side
            flops += conv_flops(side, 1, c_in, f)        # 1x1 at the input's side
            flops += conv_flops(out, 3, f, f)            # 3x3 carries the stride
            flops += conv_flops(out, 1, f, 4 * f)
            weights += c_in * f + 9 * f * f + 4 * f * f
            if j == 0:
                flops += conv_flops(out, 1, c_in, 4 * f)  # projection shortcut
                weights += c_in * 4 * f
            side, c_in = out, 4 * f
    flops += 2.0 * c_in * classes
    weights += c_in * classes
    return {"flops": flops, "bytes": 2.0 * weights, "weights": weights}


def forward(ctx) -> dict | None:
    """Per call of the traced program: the rows the clients got back per call
    (not the bucket's padded rows) times one image's need."""
    rows = device.work_per_call(ctx)
    if rows is None:
        return None
    side, _w, channels = ctx.config["server"]["input_shape"]
    one = forward_cost(side, channels, ctx.config["num_classes"])
    return {"flops": rows * one["flops"], "bytes": one["bytes"]}
