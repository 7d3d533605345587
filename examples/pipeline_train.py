"""GPipe pipeline-parallel training in one differentiated program.

``make_pipeline_train`` writes the microbatch conveyor as a
``lax.scan`` inside ``shard_map``; reverse-mode AD through it IS the
backward conveyor (ppermute transposes to the inverted ring) with
microbatch gradient accumulation.  Loss and stage-sharded grads match
the unpipelined model exactly.

Run (8 virtual devices):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/pipeline_train.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main() -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from brpc_tpu.parallel.pipeline import make_pipeline_train

    n = jax.device_count()
    print(f"{n} devices on {jax.default_backend()}")
    mesh = Mesh(np.array(jax.devices()), ("pp",))
    width, n_micro, mb = 32, 8, 4

    def stage_fn(params, x):
        return jnp.tanh(x @ params["w"] + params["b"])

    def loss_fn(outputs, ys):
        return jnp.mean((outputs - ys) ** 2)

    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    params = {
        "w": jax.device_put(
            jax.random.normal(ks[0], (n, width, width)) * 0.3,
            NamedSharding(mesh, P("pp"))),
        "b": jax.device_put(
            jax.random.normal(ks[1], (n, width)) * 0.1,
            NamedSharding(mesh, P("pp"))),
    }
    xs = jax.random.normal(ks[2], (n_micro, mb, width))
    ys = jax.random.normal(ks[3], (n_micro, mb, width))

    step = make_pipeline_train(mesh, stage_fn, loss_fn, "pp")
    lr = 0.05
    for i in range(10):
        loss, grads = step(params, xs, ys)
        params = jax.tree_util.tree_map(lambda p, g: p - lr * g,
                                        params, grads)
        print(f"step {i}: loss {float(loss):.5f}  "
              f"(grads spread over "
              f"{len(grads['w'].sharding.device_set)} devices)")


if __name__ == "__main__":
    main()
