"""Cross-PROCESS SPMD dry run: 2 ``jax.distributed`` processes × n/2
virtual CPU devices each, one global-mesh train step, one cross-process
ICI tensor transfer.  This is explicitly a CPU dry run: a chip belongs
to one process at a time, so the workers force ``JAX_PLATFORMS=cpu``
and never open an accelerator.

The single-process ``dryrun_multichip`` composes dp×tp×ep×sp×pp inside
one runtime; this module proves the same program model survives the
process boundary the way the reference's NCCL/MPI backend does
(SURVEY.md §5.8): the coordinator federates the per-process device sets
into one mesh, the train step's collectives cross the process boundary,
and an RPC carrying a device attachment moves a tensor between the two
interpreters (domains differ → the fabric's cross-process path, same
contract ``tests/test_ici_xfer.py`` pins).

Run as a module (one worker per process):

    python -m brpc_tpu.parallel.multiproc_dryrun <pid> <nproc> \
        <ndev_local> <coord_host:port> <rpc_port>

or drive both workers via :func:`run`, which ``__graft_entry__
.dryrun_multichip`` calls as its final stage (spawned with
``subprocess`` — ``multiprocessing`` spawn breaks under stdin-driven
parents, see bench.py's rationale).
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import time


def _worker(pid: int, nproc: int, ndev_local: int, coord: str,
            rpc_port: int) -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    # REPLACE any inherited device-count flag (the single-process dry
    # run's parent exports 8; each worker must expose exactly its local
    # share or the federated mesh doubles up)
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={ndev_local}")
    os.environ["XLA_FLAGS"] = " ".join(flags)
    import jax

    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=nproc, process_id=pid)
    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from brpc_tpu.models.embedding_ps import (PSConfig, batch_specs,
                                              init_params, param_specs,
                                              sgd_train_step)

    n_total = nproc * ndev_local
    devs = jax.devices()
    assert len(devs) == n_total, (len(devs), n_total)
    mesh = Mesh(np.array(devs).reshape(nproc, ndev_local), ("dp", "tp"))
    tp = ndev_local

    cfg = PSConfig(vocab=64 * tp, dim=32, slots=4, hidden=16 * tp,
                   classes=8, lr=0.1)
    # same PRNG on every process -> identical host values; each process
    # materializes only its addressable shards
    host_params = {k: np.asarray(v) for k, v in
                   init_params(jax.random.PRNGKey(0), cfg).items()}
    specs = param_specs(cfg)
    params = {
        k: jax.make_array_from_callback(
            host_params[k].shape, NamedSharding(mesh, specs[k]),
            lambda idx, a=host_params[k]: a[idx])
        for k in host_params}

    batch = 4 * nproc
    rng = np.random.default_rng(1)
    ids_h = rng.integers(0, cfg.vocab, (batch, cfg.slots), dtype=np.int32)
    lbl_h = rng.integers(0, cfg.classes, (batch,), dtype=np.int32)
    ids_spec, lbl_spec = batch_specs()
    ids = jax.make_array_from_callback(
        ids_h.shape, NamedSharding(mesh, ids_spec),
        lambda idx: ids_h[idx])
    labels = jax.make_array_from_callback(
        lbl_h.shape, NamedSharding(mesh, lbl_spec),
        lambda idx: lbl_h[idx])

    step = jax.jit(sgd_train_step, static_argnames=("lr",),
                   donate_argnums=(0,))
    with mesh:
        new_params, loss = step(params, ids, labels, lr=cfg.lr)
        jax.block_until_ready(loss)
    assert jnp.isfinite(loss), f"non-finite loss {loss}"
    emb_devs = {d.id for d in new_params["emb"].sharding.device_set}
    assert len(emb_devs) == n_total, (len(emb_devs), n_total)
    print(f"[p{pid}] cross-process SPMD train step ok: "
          f"loss={float(loss):.4f} over {n_total} devices "
          f"({nproc} processes)", flush=True)

    # barrier before the RPC stage so the server exists before the
    # client dials (a psum over the global mesh synchronizes processes)
    tok = jax.make_array_from_callback(
        (n_total,), NamedSharding(mesh, P(("dp", "tp"))),
        lambda idx: np.ones((n_total,), np.float32)[idx])
    sync = jax.jit(jnp.sum,
                   out_shardings=NamedSharding(mesh, P()))
    assert float(sync(tok)) == float(n_total)

    # cross-process ICI transfer: process 0 serves, process 1 sends a
    # device tensor as an RPC device attachment and checks the echo
    if pid == 0:
        from brpc_tpu.models.ps_service import PSService
        from brpc_tpu.server import Server

        srv = Server()
        srv.add_service(PSService(), name="PS")
        assert srv.start(f"127.0.0.1:{rpc_port}") == 0
        try:
            float(sync(tok))          # barrier: server is up, p1 may dial
            float(sync(tok))          # barrier: p1 finished its calls
        finally:
            srv.stop()
        print(f"[p{pid}] ici server stage done", flush=True)
    else:
        from brpc_tpu.client import Channel, Controller

        float(sync(tok))              # barrier: p0's server is up
        try:
            ch = Channel()
            assert ch.init(f"127.0.0.1:{rpc_port}") == 0, \
                "client channel init failed"
            x = jnp.arange(4096, dtype=jnp.float32)  # local device tensor
            got = None
            for attempt in range(10):
                cntl = Controller()
                cntl.timeout_ms = 30_000
                cntl.request_device_attachment = x
                c = ch.call_method("PS.EchoTensor", b"", cntl=cntl)
                if not c.failed \
                        and c.response_device_attachment is not None:
                    got = c.response_device_attachment.tensor()
                    break
                time.sleep(0.5)
            assert got is not None, \
                "cross-process tensor echo never succeeded"
            np.testing.assert_allclose(np.asarray(got), np.asarray(x))
            print(f"[p{pid}] cross-process ICI transfer ok "
                  f"({x.nbytes} bytes round-tripped)", flush=True)
        finally:
            # release p0's hold even on failure — a p1 error must
            # surface immediately, not after p0 burns the whole
            # parent timeout blocked in its barrier
            float(sync(tok))

    print(f"[p{pid}] 2-proc step ok", flush=True)


def run(n_devices: int = 8, processes: int = 2,
        timeout_s: float = 300.0) -> None:
    """Spawn the workers and raise unless every stage reports ok."""
    if n_devices % processes:
        raise ValueError(
            f"{n_devices} devices do not divide over {processes} "
            "processes")
    ndev_local = n_devices // processes
    # hold the probe sockets open until just before spawn: the port
    # must not be re-bindable by a stranger during the multi-second
    # worker startup window any longer than unavoidable
    probes = []
    for _ in range(2):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        probes.append(s)
    coord_port, rpc_port = (s.getsockname()[1] for s in probes)
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    import tempfile

    procs = []
    logs = []
    for s in probes:
        s.close()
    for pid in range(processes):
        # worker output goes to FILES: two workers coupled through
        # collectives + a parent draining pipes sequentially is a
        # deadlock (a chatty worker fills its 64KB pipe while the
        # parent blocks on its sibling)
        lf = tempfile.NamedTemporaryFile("w+", suffix=f".p{pid}.log",
                                         delete=False)
        logs.append(lf)
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "brpc_tpu.parallel.multiproc_dryrun",
             str(pid), str(processes), str(ndev_local),
             f"127.0.0.1:{coord_port}", str(rpc_port)],
            cwd=repo, env=env, stdout=lf, stderr=subprocess.STDOUT))
    deadline = time.time() + timeout_s
    for p in procs:
        left = max(1.0, deadline - time.time())
        try:
            p.wait(timeout=left)
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()
    outs = []
    for lf in logs:
        lf.flush()
        lf.seek(0)
        outs.append(lf.read())
        lf.close()
        os.unlink(lf.name)
    ok = all(p.returncode == 0 for p in procs) and all(
        "2-proc step ok" in o for o in outs)
    for i, o in enumerate(outs):
        for line in o.splitlines():
            if line.startswith("[p") or "Error" in line:
                print(line)
        if not ok and procs[i].returncode != 0:
            tail = "\n".join(o.splitlines()[-15:])
            print(f"--- worker {i} tail ---\n{tail}")
    if not ok:
        raise RuntimeError("multi-process dryrun failed")


if __name__ == "__main__":
    _worker(int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3]),
            sys.argv[4], int(sys.argv[5]))
