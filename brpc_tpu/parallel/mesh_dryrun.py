"""The multi-device programs of the dry run, over a GIVEN device list.

``run_mesh_programs(devices)`` jits and runs, on exactly those devices:
one dp×tp sharded EmbeddingPS train step, the ``MeshTransport``
collectives, one dp×tp sharded TransformerLM train step (plain and
gradient-accumulated), ring attention, the pipeline conveyor forward
and training, the dp×pp composition, and a ``PS.EchoTensor`` RPC whose
device attachment is sharded over all of them — each checked against
its host reference, and the sharded results checked to sit on
``len(devices)`` distinct devices.

The body knows nothing about WHICH devices: ``__graft_entry__
.dryrun_multichip`` passes N virtual CPU devices, ``chip_smoke.py``'s
mesh stage passes ``jax.devices()[:4]`` on a four-chip host.
"""

from __future__ import annotations


def run_mesh_programs(devices, lm_cfg=None, lm_seq: int = 16,
                      tp=None) -> None:
    """``lm_cfg`` is the TransformerLM the sharded train step runs
    (default: the toy MoE config, experts over tp — dp + tp + ep in one
    step) and ``lm_seq`` its sequence length; ``tp`` fixes the
    tensor-parallel width of the dp × tp meshes (default: the widest
    of 2, 4 that divides the device count)."""
    import jax

    # the sharded programs are compared with unsharded references at
    # float32 tolerances; on a TPU a float32 matmul runs as bf16
    # passes unless the precision is "highest" (first four-chip run:
    # ring attention off by 1.6e-3 against rtol 2e-4)
    with jax.default_matmul_precision("highest"):
        _mesh_programs(devices, lm_cfg, lm_seq, tp)


def _mesh_programs(devices, lm_cfg, lm_seq: int, tp) -> None:
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from brpc_tpu.models.embedding_ps import (PSConfig, batch_specs,
                                              init_params, param_specs,
                                              sgd_train_step)

    n_devices = len(devices)
    # dp × tp factorization: widest tp that divides n (≥2 when possible)
    if tp is None:
        tp = 1
        for cand in (2, 4):
            if n_devices % cand == 0:
                tp = cand
    dp = n_devices // tp
    devices = np.array(devices).reshape(dp, tp)
    mesh = Mesh(devices, ("dp", "tp"))

    cfg = PSConfig(vocab=64 * tp, dim=32, slots=4, hidden=16 * tp,
                   classes=8, lr=0.1)
    params = init_params(jax.random.PRNGKey(0), cfg)
    p_shard = {k: NamedSharding(mesh, s)
               for k, s in param_specs(cfg).items()}
    params = {k: jax.device_put(v, p_shard[k]) for k, v in params.items()}

    batch = 4 * dp
    ids = jax.random.randint(jax.random.PRNGKey(1), (batch, cfg.slots), 0,
                             cfg.vocab, dtype=jnp.int32)
    labels = jax.random.randint(jax.random.PRNGKey(2), (batch,), 0,
                                cfg.classes, dtype=jnp.int32)
    ids_spec, lbl_spec = batch_specs()
    ids = jax.device_put(ids, NamedSharding(mesh, ids_spec))
    labels = jax.device_put(labels, NamedSharding(mesh, lbl_spec))

    step = jax.jit(sgd_train_step, static_argnames=("lr",),
                   donate_argnums=(0,))
    with mesh:
        new_params, loss = step(params, ids, labels, lr=cfg.lr)
        jax.block_until_ready(loss)
    assert jnp.isfinite(loss), f"non-finite loss {loss}"
    # sharding really happened: the table shards its vocab dim over tp
    emb_shards = {d.id for d in new_params["emb"].sharding.device_set}
    assert len(emb_shards) == n_devices, (
        f"emb sharded over {len(emb_shards)} devices, want {n_devices}")
    print(f"dryrun_multichip ok: mesh dp={dp} tp={tp} "
          f"loss={float(loss):.4f} on devices {sorted(emb_shards)}")

    # mesh-transport collectives compile+run on the same mesh
    from brpc_tpu.parallel.mesh_transport import MeshTransport

    mt = MeshTransport(mesh=Mesh(devices.reshape(-1), ("ici",)),
                       axis="ici")
    x = jnp.arange(n_devices * 8, dtype=jnp.float32).reshape(n_devices, 8)
    xs = mt.scatter(x, axis=0)
    shifted = mt.ring_shift(xs, steps=1)
    total = mt.psum(xs)
    np.testing.assert_allclose(mt.gather(total)[0], x.sum(axis=0))
    np.testing.assert_allclose(mt.gather(shifted),
                               np.roll(np.asarray(x), 1, axis=0))
    np.testing.assert_allclose(mt.gather(mt.all_gather(xs)),
                               np.asarray(x))
    shift_devs = {d.id for d in shifted.sharding.device_set}
    assert len(shift_devs) == n_devices, (
        f"ring shift landed on {len(shift_devs)} devices, "
        f"want {n_devices}")
    print("mesh transport collectives ok")

    # TransformerLM flagship: one dp×tp-sharded train step (bf16 MXU
    # matmuls, RoPE, remat) with a Mixture-of-Experts FFN — experts
    # shard over the tp axis, so this one step exercises dp + tp + ep
    from brpc_tpu.models.transformer_lm import (LMConfig, batch_specs as
                                                lm_batch_specs, init_params
                                                as lm_init, make_train_step,
                                                param_specs as lm_specs)

    if lm_cfg is None:
        lm_cfg = LMConfig(vocab=64, dim=32, heads=4, depth=2, max_seq=64,
                          moe_experts=2 * tp)
    lm_params = lm_init(jax.random.PRNGKey(7), lm_cfg)
    lm_params = jax.tree_util.tree_map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)),
        lm_params, lm_specs(lm_cfg))
    ids_spec, lbl_spec = lm_batch_specs()
    lm_ids = jax.random.randint(jax.random.PRNGKey(8), (2 * dp, lm_seq),
                                0, lm_cfg.vocab, jnp.int32)
    lm_labels = jnp.roll(lm_ids, -1, axis=-1)
    lm_ids = jax.device_put(lm_ids, NamedSharding(mesh, ids_spec))
    lm_labels = jax.device_put(lm_labels, NamedSharding(mesh, lbl_spec))
    lm_step = jax.jit(make_train_step(lm_cfg))
    with mesh:
        lm_new, lm_loss = lm_step(lm_params, lm_ids, lm_labels)
        jax.block_until_ready(lm_loss)
    assert jnp.isfinite(lm_loss), f"non-finite LM loss {lm_loss}"
    lm_devs = {d.id for d in lm_new["embed"].sharding.device_set}
    assert len(lm_devs) == n_devices, (
        f"LM embed sharded over {len(lm_devs)} devices, want {n_devices}")
    del lm_new
    print(f"transformer LM dim={lm_cfg.dim} dp×tp"
          f"{'(+ep)' if lm_cfg.moe_experts else ''} train step ok: "
          f"loss={float(lm_loss):.4f} on devices {sorted(lm_devs)}")

    # the same step with in-jit gradient accumulation (the chip-filling
    # tokens/step shape): lax.scan over microbatches must compose with
    # the dp×tp(+ep) shardings
    lm_step_acc = jax.jit(make_train_step(lm_cfg, accum=2))
    with mesh:
        _, acc_loss = lm_step_acc(lm_params, lm_ids, lm_labels)
        jax.block_until_ready(acc_loss)
    assert jnp.isfinite(acc_loss), f"non-finite accum loss {acc_loss}"
    print(f"grad-accumulated (accum=2) sharded train step ok: "
          f"loss={float(acc_loss):.4f}")

    # sequence parallelism (sp): ring attention over all devices
    from jax.sharding import NamedSharding
    from brpc_tpu.parallel.ring_attention import (make_ring_attention,
                                                  reference_attention)

    sp_mesh = Mesh(devices.reshape(-1), ("sp",))
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (jax.random.normal(kk, (1, 8 * n_devices, 2, 8),
                                 jnp.float32) * 0.5 for kk in ks)
    sh = NamedSharding(sp_mesh, P(None, "sp", None, None))
    ring = make_ring_attention(sp_mesh, "sp", causal=True)
    got = ring(jax.device_put(q, sh), jax.device_put(k, sh),
               jax.device_put(v, sh))
    want = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    print("ring attention (sp) ok")

    # pipeline parallelism (pp): microbatch conveyor over all devices
    from brpc_tpu.parallel.pipeline import make_pipeline

    pp_mesh = Mesh(devices.reshape(-1), ("pp",))
    width = 8
    pw = jax.random.normal(jax.random.PRNGKey(4),
                           (n_devices, width, width)) * 0.3
    stage = lambda p, x: jnp.tanh(x @ p["w"])  # noqa: E731
    pipe = make_pipeline(pp_mesh, stage, "pp")
    xs_in = jax.random.normal(jax.random.PRNGKey(5), (3, 2, width))
    pparams = {"w": jax.device_put(
        pw, NamedSharding(pp_mesh, P("pp")))}
    out = pipe(pparams, xs_in)
    want = xs_in
    for i in range(n_devices):
        want = jnp.tanh(want @ pw[i])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    print("pipeline (pp) ok")

    # pipelined TRAINING (GPipe): the differentiated conveyor's loss and
    # stage-sharded grads must match the unpipelined model exactly —
    # backward conveyor + microbatch grad accumulation included
    from brpc_tpu.parallel.pipeline import make_pipeline_train

    def pp_loss(outs, ys):
        return jnp.mean((outs - ys) ** 2)

    pp_step = make_pipeline_train(pp_mesh, stage, pp_loss, "pp")
    ys_in = jax.random.normal(jax.random.PRNGKey(6), xs_in.shape)
    pp_l, pp_g = pp_step(pparams, xs_in, ys_in)

    def ref_pp_loss(p):
        h = xs_in
        for i in range(n_devices):
            h = jnp.tanh(h @ p["w"][i])
        return jnp.mean((h - ys_in) ** 2)

    want_l, want_g = jax.value_and_grad(ref_pp_loss)({"w": pw})
    np.testing.assert_allclose(float(pp_l), float(want_l),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(pp_g["w"]),
                               np.asarray(want_g["w"]),
                               rtol=1e-4, atol=1e-6)
    print(f"pipeline train step ok (loss matches: {float(pp_l):.6f})")

    # dp×pp composition: each dp group runs the conveyor on its batch
    # share, grads pmean'd across dp — still matching the oracle
    if n_devices % 2 == 0 and n_devices >= 4:
        mesh2 = Mesh(devices.reshape(2, n_devices // 2), ("dp", "pp"))
        n2 = n_devices // 2
        pw2 = jax.random.normal(jax.random.PRNGKey(14),
                                (n2, width, width)) * 0.3
        xs2 = jax.random.normal(jax.random.PRNGKey(15), (3, 4, width))
        ys2 = jax.random.normal(jax.random.PRNGKey(16), (3, 4, width))
        step2 = make_pipeline_train(mesh2, stage, pp_loss, "pp",
                                    dp_axis="dp")
        l2, g2 = step2(
            {"w": jax.device_put(pw2,
                                 NamedSharding(mesh2, P("pp")))},
            jax.device_put(xs2, NamedSharding(mesh2, P(None, "dp"))),
            jax.device_put(ys2, NamedSharding(mesh2, P(None, "dp"))))

        def ref2(p):
            h = xs2
            for i in range(n2):
                h = jnp.tanh(h @ p["w"][i])
            return jnp.mean((h - ys2) ** 2)

        wl2, wg2 = jax.value_and_grad(ref2)({"w": pw2})
        np.testing.assert_allclose(float(l2), float(wl2),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(g2["w"]),
                                   np.asarray(wg2["w"]),
                                   rtol=1e-4, atol=1e-6)
        print(f"dp×pp pipeline train step ok (loss {float(l2):.6f})")

    # device-resident PS RPC across the mesh: a mesh-sharded
    # tensor rides an RPC as a DEVICE attachment (descriptor on the
    # wire, payload through the fabric) and comes back sharded
    from brpc_tpu.client import Channel, Controller
    from brpc_tpu.models.ps_service import PSService
    from brpc_tpu.server import Server

    srv = Server()
    srv.add_service(PSService(), name="PS")
    assert srv.start("127.0.0.1:0") == 0
    try:
        ch = Channel()
        ch.init(str(srv.listen_endpoint))
        sharded = jax.device_put(
            jnp.arange(n_devices * 16, dtype=jnp.float32),
            NamedSharding(Mesh(devices.reshape(-1), ("ici",)), P("ici")))
        # call 1 exchanges fabric domains (host-staged); afterwards the
        # exchange rides the device fabric end to end.  Steady state =
        # two consecutive device-resident zero-copy rounds; a transient
        # reconnect restarts the domain exchange, so allow up to 4
        # rounds to get there before declaring the fabric broken.
        kinds, streak = [], 0
        for round_ in range(4):
            cntl = Controller()
            cntl.timeout_ms = 60_000
            cntl.request_device_attachment = sharded
            c = ch.call_method("PS.EchoTensor", b"", cntl=cntl)
            assert not c.failed, c.error_text
            att = c.response_device_attachment
            assert att is not None
            out = att.tensor()
            kinds.append(att.kind)
            streak = streak + 1 if (att.device_resident
                                    and out is sharded) else 0
            if streak >= 2:
                break
        assert streak >= 2, (
            "device fabric never reached steady state (zero-copy "
            f"device-resident echo); attachment kinds per round: {kinds}")
        echo_devs = {d.id for d in out.sharding.device_set}
        assert len(echo_devs) == n_devices, (
            f"echoed tensor on {len(echo_devs)} devices, "
            f"want {n_devices}")
        print("device-resident PS RPC over the mesh ok "
              f"(shards on devices {sorted(echo_devs)})")
    finally:
        srv.stop()
