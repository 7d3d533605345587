"""Brings the system under test up the way a deployment does: a native-
engine ``Server`` around the configuration's service (``models/<model>.py
make_service``), reached over a loopback ``Channel`` (the way
``chip_smoke.serve`` starts it, copied; nothing of ``chip_smoke`` is
imported).  Nothing here depends on which block is served.
"""

from __future__ import annotations


class Served:
    """The server, its service and one client channel."""

    def __init__(self, svc):
        from brpc_tpu.client import Channel
        from brpc_tpu.server import Server, ServerOptions

        self.svc = svc
        opts = ServerOptions()
        opts.native = True
        opts.usercode_inline = True
        self.server = Server(opts)
        self.server.add_service(self.svc, name="LM")
        if self.server.start("127.0.0.1:0") != 0:
            raise RuntimeError("server failed to start")
        self.bridge = self.server._native_bridge
        if self.bridge is None:
            self.server.stop()
            raise RuntimeError("ServerOptions.native=True but the Python "
                               "transport is listening")
        self.channel = Channel()
        self.channel.init(str(self.server.listen_endpoint))

    def counters(self) -> dict:
        """The program's own counts, as they stand now."""
        return {"engine": self.bridge.engine.telemetry(),
                "kv": self.svc.batcher().kv_stats()}

    def steps_run(self) -> int:
        return self.svc.batcher().steps_run()

    def stop(self) -> None:
        """Stop the server and let go of everything of the program's."""
        self.server.stop()
        self.svc = self.server = self.channel = self.bridge = None


def free_program_state(keep) -> float:
    """After ``Served.stop``: let go of the device memory the stopped
    program still holds (its page pool, prefilled caches in flight), so
    that the reference has the chip to itself.  Dropping the service is
    not enough: the native engine's handler closures keep the ``Server``,
    and with it the service and its batcher, alive after ``stop()``
    (PERF.md, Open questions).  So, by JAX's own list and naming nothing
    of the program's: every live device array of a MiB or more that is
    not a leaf of ``keep`` (the benchmark's weights, which the reference
    goes on to use) is the program's, and is deleted.  Returns the bytes
    still in use on the device (0 where it keeps no count)."""
    import jax

    mine = {id(x) for x in jax.tree_util.tree_leaves(keep)}
    for a in jax.live_arrays():
        if id(a) not in mine and a.nbytes >= (1 << 20) \
                and not a.is_deleted():
            a.delete()
    stats = jax.local_devices()[0].memory_stats()
    return float(stats["bytes_in_use"]) if stats else 0.0
