"""TransformerLM — train the long-context flagship on a device mesh.

Demonstrates the dense-compute model family end to end:

- dp×tp sharded SGD training (tensor-parallel projections, data-parallel
  batch; XLA inserts the collectives from the NamedSharding specs),
- sequence-parallel ring attention for long context (the same forward
  spread over an ``sp`` axis so context length scales with chips),
- remat on, bf16 matmuls on the MXU.

Run on the virtual CPU mesh (or real chips, if you have them):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/train_transformer_lm.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from brpc_tpu.models import (LMConfig, batch_specs, init_params,
                                 make_forward, make_train_step, param_specs)
    from brpc_tpu.utils.compile_cache import enable_compile_cache

    print("compile cache:", enable_compile_cache())
    n = len(jax.devices())
    tp = 2 if n % 2 == 0 else 1
    dp = n // tp
    mesh = Mesh(np.array(jax.devices()[:dp * tp]).reshape(dp, tp),
                ("dp", "tp"))
    print(f"mesh: dp={dp} tp={tp} on {jax.default_backend()}")

    cfg = LMConfig(vocab=256, dim=64, heads=4, depth=2,
                   max_seq=max(128, 16 * n), lr=0.3)
    params = init_params(jax.random.PRNGKey(0), cfg)
    params = jax.tree_util.tree_map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)),
        params, param_specs(cfg))

    # toy task: predict the next token of a repeating pattern
    ids = jnp.tile(jnp.arange(64, dtype=jnp.int32), (4 * dp, 2))
    labels = jnp.roll(ids, -1, axis=-1)
    ids_spec, lbl_spec = batch_specs()
    ids = jax.device_put(ids, NamedSharding(mesh, ids_spec))
    labels = jax.device_put(labels, NamedSharding(mesh, lbl_spec))

    step = jax.jit(make_train_step(cfg))
    with mesh:
        for i in range(20):
            params, loss = step(params, ids, labels)
            if i % 5 == 0 or i == 19:
                print(f"step {i:3d}  loss {float(loss):.4f}")

    # long context via sequence parallelism: same params, attention over
    # an sp axis — each chip holds 1/n of the sequence
    if n >= 2:
        sp_mesh = Mesh(np.array(jax.devices()), ("sp",))
        fwd = make_forward(cfg, mesh=sp_mesh, sp_axis="sp")
        long_ids = jnp.tile(jnp.arange(64, dtype=jnp.int32),
                            (2, (16 * n) // 64 + 1))[:, :16 * n]
        long_ids = jax.device_put(
            long_ids, NamedSharding(sp_mesh, P(None, "sp")))
        logits = fwd(params, long_ids)
        print(f"sequence-parallel forward over {n} chips: "
              f"logits {tuple(logits.shape)} finite="
              f"{bool(jnp.isfinite(logits).all())}")


if __name__ == "__main__":
    main()
