"""Plain reference: ResNet-50 v1.5 (He et al., arXiv:1512.03385, Table 1,
50-layer column; stride on the 3x3 convolution and XLA's SAME padding, which
at stride 2 pads (0, 1), as models/resnet.py builds it)
in straightforward float32 jax.numpy, inference mode, no bucketing or batching.
It reads the exported checkpoint's msgpack with flax's reader and shares no
code with the program.  Run on the CPU in a helper process:

    python resnet50.py <checkpoint_dir> <input.npy> <output.npy>
"""

import os
import sys

import flax.serialization
import jax
import jax.numpy as jnp
import numpy as np

STAGES = (3, 4, 6, 3)
EPS = 1e-5


def conv(x, kernel, stride, padding):
    return jax.lax.conv_general_dilated(
        x, jnp.asarray(kernel, jnp.float32), (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)


def norm(x, p, s):
    inv = jnp.asarray(p["scale"]) / jnp.sqrt(jnp.asarray(s["var"]) + EPS)
    return (x - jnp.asarray(s["mean"])) * inv + jnp.asarray(p["bias"])


def block(x, p, s, stride):
    y = jax.nn.relu(norm(conv(x, p["Conv_0"]["kernel"], 1, "SAME"),
                         p["BatchNorm_0"], s["BatchNorm_0"]))
    y = jax.nn.relu(norm(conv(y, p["Conv_1"]["kernel"], stride, "SAME"),
                         p["BatchNorm_1"], s["BatchNorm_1"]))
    y = norm(conv(y, p["Conv_2"]["kernel"], 1, "SAME"),
             p["BatchNorm_2"], s["BatchNorm_2"])
    if "conv_proj" in p:
        x = norm(conv(x, p["conv_proj"]["kernel"], stride, "SAME"),
                 p["norm_proj"], s["norm_proj"])
    return jax.nn.relu(x + y)


def forward(variables, x):
    p, s = variables["params"], variables["batch_stats"]
    x = jnp.asarray(x, jnp.float32)
    x = conv(x, p["conv_init"]["kernel"], 2, [(3, 3), (3, 3)])
    x = jax.nn.relu(norm(x, p["bn_init"], s["bn_init"]))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                              [(0, 0), (1, 1), (1, 1), (0, 0)])
    n = 0
    for i, count in enumerate(STAGES):
        for j in range(count):
            name = f"BottleneckBlock_{n}"
            x = block(x, p[name], s[name], 2 if i > 0 and j == 0 else 1)
            n += 1
    x = jnp.mean(x, axis=(1, 2))
    head = p["head"]
    return jnp.dot(x, jnp.asarray(head["kernel"], jnp.float32),
                   precision=jax.lax.Precision.HIGHEST) + jnp.asarray(head["bias"])


def main() -> None:
    ckpt, src, dst = sys.argv[1:4]
    with open(os.path.join(ckpt, "params.msgpack"), "rb") as f:
        variables = flax.serialization.msgpack_restore(f.read())
    np.save(dst, np.asarray(forward(variables, np.load(src)), np.float64))


if __name__ == "__main__":
    main()
