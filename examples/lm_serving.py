"""LM serving — completions over the framework.

Starts an LMService (TransformerLM + KV-cache greedy decode), then a
client requests completions over plain RPC.  The first request pays the
XLA compile; the rest reuse the cached prefill/decode programs.

Run: python examples/lm_serving.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import numpy as np

    from brpc_tpu.client import Channel, Controller
    from brpc_tpu.models.lm_service import (LMService,
                                            pack_generate_request,
                                            unpack_generated)
    from brpc_tpu.server import Server
    from brpc_tpu.utils.compile_cache import enable_compile_cache

    print("compile cache:", enable_compile_cache())
    srv = Server()
    srv.add_service(LMService(), name="LM")
    assert srv.start("127.0.0.1:0") == 0
    ch = Channel()
    ch.init(str(srv.listen_endpoint))

    info = ch.call("LM.Info", b"")
    print("model:", info.decode())

    prompt = np.arange(12, dtype=np.int32).reshape(1, 12)
    for i in range(3):
        cntl = Controller()
        cntl.timeout_ms = 120_000
        t0 = time.perf_counter()
        c = ch.call_method("LM.Generate",
                           pack_generate_request(prompt, 16), cntl=cntl)
        dt = time.perf_counter() - t0
        assert not c.failed, c.error_text
        ids = unpack_generated(c.response)
        label = "compiles" if i == 0 else "cached"
        print(f"request {i} ({label}): {dt*1e3:7.1f} ms  "
              f"-> {ids[0][:8].tolist()}...")
    srv.stop()


if __name__ == "__main__":
    main()
