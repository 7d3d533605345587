"""Test harness config.

Forces JAX onto a virtual 8-device CPU mesh so multi-chip sharding paths are
exercised without TPU hardware (mirrors how the reference tests distributed
behavior in-process on loopback — /root/reference/test/brpc_server_unittest.cpp:185).

MUST run before any `import jax` anywhere in the test session.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


def require_native():
    """Skip the calling test when the native engine can't build."""
    from brpc_tpu.native import available
    if not available():
        pytest.skip("native engine unavailable (no toolchain)")


@pytest.fixture(scope="session", params=[False, True], ids=["py", "native"])
def native_mode(request):
    """Run server-backed suites over both transports: the pure-Python
    path and the native C++ IO engine (built on demand; the reference
    tests Socket/InputMessenger directly — brpc_socket_unittest.cpp)."""
    if request.param:
        require_native()
    return request.param


@pytest.fixture()
def server_options(native_mode):
    """ServerOptions pre-configured for the current transport param."""
    from brpc_tpu.server import ServerOptions
    opts = ServerOptions()
    opts.native = native_mode
    return opts


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "soak: long mixed-workload soak (duration via SOAK_SECONDS env)")
    config.addinivalue_line(
        "markers",
        "slow: long-running stress/soak tests excluded from tier-1 "
        "(-m 'not slow')")


# -- shared wire-format helpers for the native adversarial suites --------
# (one home for TRPC/TLV byte building: a framing change must not be
# mirrorable into only one of the raw/batch test files)

def wire_tlv(tag: int, data: bytes) -> bytes:
    import struct
    return bytes([tag]) + struct.pack("<I", len(data)) + data


def wire_resp_frame(cid: int, payload: bytes = b"ok",
                    extra_meta: bytes = b"") -> bytes:
    import struct
    meta = wire_tlv(1, struct.pack("<Q", cid)) + extra_meta
    return (b"TRPC" + struct.pack("<II", len(meta) + len(payload),
                                  len(meta)) + meta + payload)


WIRE_TAIL = wire_tlv(4, b"S") + wire_tlv(5, b"M")   # service/method TLVs


def load_native_or_skip(attr: str):
    """The loaded native module, skipping unless ``attr`` exists."""
    require_native()
    from brpc_tpu.native import load
    nat = load()
    if nat is None or not hasattr(nat, attr):
        pytest.skip(f"native {attr} unavailable")
    return nat
