"""Helper child (JAX on the CPU): a seeded checkpoint in the layout JAXServer
loads, made with the program's own export tool.  The chip is the server's.

    python export_checkpoint.py <out_dir> <seed> '<server block of the config, JSON>'
"""

import json
import sys

import jax
import numpy as np

from seldon_core_tpu.models import get_model
from seldon_core_tpu.servers.jaxserver import export_checkpoint


def main() -> None:
    out_dir, seed, server = sys.argv[1], int(sys.argv[2]), json.loads(sys.argv[3])
    model = get_model(server["model"])
    shape = [1, *server["input_shape"]]
    variables = jax.jit(lambda k, x: model.init(k, x, **server["apply_kwargs"]))(
        jax.random.PRNGKey(seed), np.zeros(shape, np.float32))
    export_checkpoint(out_dir, server["model"], variables,
                      input_shape=server["input_shape"],
                      apply_kwargs=server["apply_kwargs"],
                      batch_buckets=server["batch_buckets"], use_orbax=False)


if __name__ == "__main__":
    main()
