"""Transfer fabrics — how a posted device tensor reaches its redeemer.

Role parity with the RDMA verbs layer the reference wraps in
RdmaEndpoint (/root/reference/src/brpc/rdma/rdma_endpoint.h:55-180): the
fabric owns the actual payload movement; the endpoint (endpoint.py) owns
per-connection descriptors and flow control, exactly as RdmaEndpoint
owns QP state while ibverbs moves bytes.

Two fabrics:

- :class:`InProcessFabric` — peers share one JAX runtime (every chip of
  a single-controller slice).  ``post`` parks the array in a registry;
  ``redeem`` lands it on the target device with ``jax.device_put`` —
  on hardware that is an HBM→HBM DMA over ICI, never touching the host.
- :class:`JaxTransferFabric` — peers in different processes with a
  runtime that implements the PJRT cross-host transfer API
  (``jax.experimental.transfer``): ``post`` schedules an await_pull,
  ``redeem`` pulls from the peer's transfer server over ICI/DCN.
  Probed at import; unsupported runtimes fall back to host-staged
  attachments (the ``FLAGS_use_rdma=false`` analogue).

A *domain id* names the reach of a fabric: peers exchange domain ids in
RpcMeta and go device-resident only when an installed fabric can bridge
the two domains.

Trust model: the domain exchange is cooperative, like the reference's
plaintext RDMA handshake (rdma_endpoint.cpp TCP bring-up) — it guards
against *misconfiguration* (random 16-byte tokens can't collide by
accident), not against a malicious peer.  The damage a forged domain or
descriptor can do is bounded: redemption requires the redeemer to sit
on the SAME connection the descriptor was posted for (the mirrored
address-pair key checked in :meth:`InProcessFabric.redeem`), acks from
other connections are rejected, all of a connection's descriptors are
reclaimed when it dies, the in-process path additionally requires a
loopback peer address, and the TTL sweep is the backstop.  Authenticate
peers with the regular auth layer if the network is hostile.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict, Optional, Tuple

from ..butil.flags import get_flag
from ..butil.logging_util import LOG

def _new_domain_token() -> bytes:
    """16 random bytes with no ``@`` in them: a domain id is
    ``token[@address]`` and :func:`domain_token` splits at the first
    ``@``, so a token that contained one (6% of raw draws) could never
    match itself and the whole process silently fell back to
    host-staged attachments."""
    while True:
        tok = os.urandom(16)
        if b"@" not in tok:
            return tok


# 16-byte process-unique token: same token on both ends of a connection
# ⇒ both ends share this process's JAX runtime (loopback / same host
# single-controller), so the in-process fabric can bridge them.
_LOCAL_DOMAIN = _new_domain_token()


_domain_cache: Optional[bytes] = None
_domain_cache_addr: Optional[bytes] = None


def local_domain_id() -> bytes:
    """Domain advertised in every RpcMeta: the process token, plus this
    process's transfer-server address when the cross-process fabric is
    up (``token@address``) — peers in OTHER processes use the address to
    pull device payloads directly (≈ the GID/QPN the reference sends in
    its RDMA handshake).  Cached: this runs on every RPC, so the common
    flag-off case is one dict lookup."""
    global _domain_cache, _domain_cache_addr
    if not get_flag("ici_transfer_enabled", False) and _xfer is None:
        addr = None
    else:
        # probing transfer_ready() here also lazily starts the transfer
        # server on the first RPC after the flag flips on
        addr = transfer_ready()
    if _domain_cache is None or addr != _domain_cache_addr:
        _domain_cache_addr = addr
        _domain_cache = _LOCAL_DOMAIN + b"@" + addr if addr \
            else _LOCAL_DOMAIN
    return _domain_cache


def domain_token(domain: bytes) -> bytes:
    return domain.split(b"@", 1)[0]


def peer_transfer_addr(domain: Optional[bytes]) -> Optional[bytes]:
    """The transfer-server address inside a peer's domain id (None when
    the peer has no cross-process fabric)."""
    if not domain or b"@" not in domain:
        return None
    return domain.split(b"@", 1)[1] or None


class PostedEntry:
    __slots__ = ("array", "nbytes", "posted_at", "on_release", "socket_id",
                 "conn_key")

    def __init__(self, array: Any, nbytes: int, on_release=None,
                 socket_id: int = 0, conn_key=None):
        self.array = array
        self.nbytes = nbytes
        self.posted_at = time.monotonic()
        self.on_release = on_release
        self.socket_id = socket_id      # poster-local: binds acks
        self.conn_key = conn_key        # connection pair: binds redemption


class InProcessFabric:
    """Descriptor registry for peers sharing this JAX runtime.

    post/redeem/release mirror the send-side MR lifecycle of
    rdma/block_pool.cpp: a posted tensor is 'registered' (kept alive,
    counted against the window) until the peer acks redemption or the
    TTL sweep reclaims it.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._posted: Dict[int, PostedEntry] = {}
        self._next_id = int.from_bytes(os.urandom(4), "little") | 1
        self.posted_bytes = 0          # live accounting (all connections)

    def can_reach(self, peer_domain: bytes) -> bool:
        return domain_token(peer_domain) == _LOCAL_DOMAIN

    def post(self, array: Any, nbytes: int, on_release=None,
             socket_id: int = 0, conn_key=None) -> int:
        with self._lock:
            desc_id = self._next_id
            self._next_id += 1
            self._posted[desc_id] = PostedEntry(array, nbytes, on_release,
                                                socket_id, conn_key)
            self.posted_bytes += nbytes
        return desc_id

    def redeem(self, desc_id: int, device: Any = None,
               conn_key=None) -> Optional[Any]:
        """Fetch a posted tensor, landing it on ``device`` (None = leave
        where posted).  Same-device redemption is zero-copy (device_put
        is an alias); cross-device rides ICI on hardware.

        When the entry was posted with a connection key, the redeemer
        must present the SAME key (both ends of one TCP connection see
        the mirrored address pair) — a peer forging descriptor ids from
        another connection gets None, never another client's tensor."""
        with self._lock:
            entry = self._posted.get(desc_id)
        if entry is None:
            return None
        if entry.conn_key is not None and conn_key != entry.conn_key:
            LOG.warning("ICI redeem rejected: descriptor %d bound to a "
                        "different connection", desc_id)
            return None
        arr = entry.array
        if device is not None:
            import jax
            arr = jax.device_put(arr, device)
        return arr

    def take(self, desc_id: int, conn_key=None) -> Optional[Any]:
        """Redeem AND consume in one step — the one-shot import the KV
        transfer plane rides: the caller owns the array from here on
        and the registration is gone, so a second take of the same
        descriptor (double import, or an import racing the exporter's
        release) returns None instead of silently aliasing memory two
        owners now believe they hold exclusively.  Same-device, so the
        hand-over is an alias: zero data motion."""
        with self._lock:
            entry = self._posted.get(desc_id)
            if entry is None:
                return None
            if entry.conn_key is not None and conn_key != entry.conn_key:
                LOG.warning("ICI take rejected: descriptor %d bound to "
                            "a different connection", desc_id)
                return None
            del self._posted[desc_id]
            self.posted_bytes -= entry.nbytes
        if entry.on_release is not None:
            try:
                entry.on_release(entry.nbytes)
            except Exception:
                LOG.exception("ici on_release callback raised")
        return entry.array

    def release(self, desc_id: int,
                only_socket: Optional[int] = None) -> bool:
        """Drop the posted ref (descriptor acked or expired).
        ``only_socket`` binds the release to the connection the
        descriptor was posted on — forged acks naming another
        connection's descriptors are rejected (the same spoof class the
        stream layer guards against)."""
        with self._lock:
            entry = self._posted.get(desc_id)
            if entry is None:
                return False
            if only_socket is not None and entry.socket_id != only_socket:
                return False
            del self._posted[desc_id]
            self.posted_bytes -= entry.nbytes
        if entry.on_release is not None:
            try:
                entry.on_release(entry.nbytes)
            except Exception:
                LOG.exception("ici on_release callback raised")
        return True

    def release_socket(self, socket_id: int) -> int:
        """Reclaim every descriptor posted on a dead connection (≈ QP
        teardown reclaiming posted WRs on disconnect)."""
        with self._lock:
            stale = [i for i, e in self._posted.items()
                     if e.socket_id == socket_id]
        n = 0
        for desc_id in stale:
            if self.release(desc_id):
                n += 1
        return n

    def sweep_expired(self, ttl_s: float) -> int:
        """Reclaim descriptors never redeemed (peer died before acking)
        — the reference's QP teardown reclaiming posted WRs."""
        now = time.monotonic()
        with self._lock:
            stale = [i for i, e in self._posted.items()
                     if now - e.posted_at > ttl_s]
        for desc_id in stale:
            self.release(desc_id)
        return len(stale)

    @property
    def live_descriptors(self) -> int:
        with self._lock:
            return len(self._posted)


class JaxTransferFabric:
    """Cross-host pull fabric over ``jax.experimental.transfer``.

    The PJRT transfer server is the runtime's RDMA engine: the sender
    schedules ``await_pull(uuid, arrays)`` and the receiver's
    ``TransferConnection.pull`` moves HBM→HBM over ICI/DCN.  Domain id =
    token + server address; redeem connects to the address inside the
    peer's descriptor.  Post/release mirror the in-process registry so
    window accounting and TICI acks work identically."""

    def __init__(self):
        self._server = None
        self._addr = b""
        self._conns: Dict[bytes, Any] = {}
        self._lock = threading.Lock()
        self._posted: Dict[int, PostedEntry] = {}
        self._next_id = int.from_bytes(os.urandom(4), "little") | 1

    @staticmethod
    def supported() -> bool:
        """One cached loopback probe — several installed runtimes ship
        the Python API but not the PJRT hooks underneath."""
        global _TRANSFER_SUPPORTED
        if _TRANSFER_SUPPORTED is None:
            _TRANSFER_SUPPORTED = _probe_transfer_runtime()
        return _TRANSFER_SUPPORTED

    def start(self) -> bool:
        if self._server is not None:
            return True
        try:
            import jax
            from jax.experimental import transfer
            self._server = transfer.start_transfer_server(
                jax.devices()[0].client)
            self._addr = self._server.address().encode()
            return True
        except Exception as e:
            LOG.warning("transfer server unavailable: %s", e)
            return False

    @property
    def address(self) -> bytes:
        return self._addr

    def post(self, array: Any, nbytes: int, on_release=None,
             socket_id: int = 0, conn_key=None) -> int:
        """Schedule an await_pull; returns the descriptor uuid the peer
        pulls with (same contract as InProcessFabric.post)."""
        with self._lock:
            uuid = self._next_id
            self._next_id += 1
            self._posted[uuid] = PostedEntry(array, nbytes, on_release,
                                             socket_id, conn_key)
        self._server.await_pull(uuid, [array])
        return uuid

    def redeem(self, peer_addr: bytes, uuid: int, specs):
        with self._lock:
            conn = self._conns.get(peer_addr)
            if conn is None:
                conn = self._server.connect(peer_addr.decode())
                self._conns[peer_addr] = conn
        return conn.pull(uuid, specs)

    def release(self, uuid: int, only_socket: Optional[int] = None) -> bool:
        """Ack arrived: drop the local ref + return window credit."""
        with self._lock:
            entry = self._posted.get(uuid)
            if entry is None:
                return False
            if only_socket is not None and entry.socket_id != only_socket:
                return False
            del self._posted[uuid]
        if entry.on_release is not None:
            try:
                entry.on_release(entry.nbytes)
            except Exception:
                LOG.exception("ici on_release callback raised")
        return True

    @property
    def live_descriptors(self) -> int:
        with self._lock:
            return len(self._posted)


_TRANSFER_SUPPORTED: Optional[bool] = None


def _probe_transfer_runtime() -> bool:
    try:
        import jax
        import jax.numpy as jnp
        from jax.experimental import transfer
        srv = transfer.start_transfer_server(jax.devices()[0].client)
        x = jnp.zeros((8,), jnp.float32)
        srv.await_pull(1, [x])
        conn = srv.connect(srv.address())
        out = conn.pull(1, [jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                 sharding=x.sharding)])
        return bool(out[0].shape == x.shape)
    except Exception:
        return False


_fabric_lock = threading.Lock()
_in_process: Optional[InProcessFabric] = None
_xfer: Optional[JaxTransferFabric] = None
_xfer_tried = False


def in_process_fabric() -> InProcessFabric:
    global _in_process
    with _fabric_lock:
        if _in_process is None:
            _in_process = InProcessFabric()
        return _in_process


def transfer_fabric() -> Optional[JaxTransferFabric]:
    """The process's cross-process fabric, started on first use; None
    when the runtime can't support it or the flag is off.  Tests may
    install a stand-in via set_transfer_fabric()."""
    global _xfer, _xfer_tried
    if not get_flag("ici_transfer_enabled", False):
        return _xfer            # explicit installs (tests) still count
    with _fabric_lock:
        if _xfer is not None or _xfer_tried:
            return _xfer
        _xfer_tried = True
    if not JaxTransferFabric.supported():
        LOG.warning("ici_transfer_enabled but the runtime lacks the "
                    "PJRT transfer hooks; device attachments fall back "
                    "to host staging across processes")
        return None
    f = JaxTransferFabric()
    if not f.start():
        return None
    with _fabric_lock:
        _xfer = f
    return _xfer


def set_transfer_fabric(f) -> None:
    """Install a transfer fabric explicitly (tests / custom runtimes)."""
    global _xfer, _xfer_tried
    with _fabric_lock:
        _xfer = f
        _xfer_tried = True


def transfer_ready() -> Optional[bytes]:
    """This process's transfer address, when the fabric is live."""
    f = transfer_fabric()
    return f.address if f is not None and f.address else None
