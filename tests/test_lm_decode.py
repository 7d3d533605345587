"""KV-cache decoding: teacher-forced equivalence with the full forward,
greedy generate shapes/determinism, MoE decode, and the LMService
serving generation over a real RPC server."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from brpc_tpu.models.transformer_lm import (LMConfig, generate,
                                            init_params, make_decode,
                                            make_forward)


def _setup(seed=0, **kw):
    cfg = LMConfig(vocab=64, dim=32, heads=4, depth=2, max_seq=32,
                   remat=False, **kw)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                cfg.vocab, jnp.int32)
    return cfg, params, prompt


def test_decode_matches_forward_teacher_forced():
    """decode_step logits at each position == full-forward last-position
    logits for the identical prefix (bf16 matmul tolerance)."""
    cfg, params, prompt = _setup()
    fwd = jax.jit(make_forward(cfg))
    prefill, decode_step = make_decode(cfg)
    cache, logits = jax.jit(prefill)(params, prompt)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(fwd(params, prompt)[:, -1]),
        rtol=2e-2, atol=2e-2)
    seq = prompt
    for i in range(5):
        tok = jax.random.randint(jax.random.PRNGKey(10 + i), (2,), 0,
                                 cfg.vocab, jnp.int32)
        cache, dl = decode_step(params, cache, tok)
        seq = jnp.concatenate([seq, tok[:, None]], axis=1)
        np.testing.assert_allclose(
            np.asarray(dl), np.asarray(fwd(params, seq)[:, -1]),
            rtol=2e-2, atol=2e-2)
    assert int(cache["len"]) == prompt.shape[1] + 5


def test_generate_shape_and_determinism():
    cfg, params, prompt = _setup()
    a = generate(params, cfg, prompt, 6)
    b = generate(params, cfg, prompt, 6)
    assert a.shape == (2, 6)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_temperature_sampling():
    """temperature>0 samples (reproducible under a fixed rng, generally
    different across rngs); temperature=0 stays greedy-deterministic."""
    from brpc_tpu.models.transformer_lm import make_generator

    cfg, params, prompt = _setup()
    gen = make_generator(cfg, params)
    a = gen(prompt, 8, temperature=1.0, rng=jax.random.PRNGKey(3))
    b = gen(prompt, 8, temperature=1.0, rng=jax.random.PRNGKey(3))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    diff_any = any(
        not np.array_equal(
            np.asarray(gen(prompt, 8, temperature=1.0,
                           rng=jax.random.PRNGKey(100 + i))),
            np.asarray(a))
        for i in range(3))
    assert diff_any, "three different rngs all sampled identically"


def test_moe_decode_generates():
    cfg, params, prompt = _setup(seed=2, moe_experts=2)
    out = generate(params, cfg, prompt, 4)
    assert out.shape == (2, 4)
    assert ((np.asarray(out) >= 0) & (np.asarray(out) < cfg.vocab)).all()


def test_lm_service_generates_over_rpc():
    from brpc_tpu.client import Channel, Controller
    from brpc_tpu.models.lm_service import (LMService,
                                            pack_generate_request,
                                            unpack_generated)
    from brpc_tpu.server import Server

    cfg, params, prompt = _setup()
    srv = Server()
    srv.add_service(LMService(cfg=cfg, params=params), name="LM")
    assert srv.start("127.0.0.1:0") == 0
    try:
        ch = Channel()
        ch.init(str(srv.listen_endpoint))
        cntl = Controller()
        cntl.timeout_ms = 120_000
        c = ch.call_method(
            "LM.Generate",
            pack_generate_request(np.asarray(prompt), 6), cntl=cntl)
        assert not c.failed, c.error_text
        got = unpack_generated(c.response)
        want = np.asarray(generate(params, cfg, prompt, 6))
        np.testing.assert_array_equal(got, want)

        # admission errors, not crashes
        bad = Controller(); bad.timeout_ms = 30_000
        c = ch.call_method("LM.Generate",
                           pack_generate_request(np.asarray(prompt), 999),
                           cntl=bad)
        assert c.failed and "max_new" in c.error_text
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# continuous batching (ISSUE 13): per-slot batch decode + the streaming
# Decode service — join-mid-batch, evict, TTFT under load
# ---------------------------------------------------------------------------

def test_batch_decode_matches_solo_decode():
    """A slot of the paged step produces the same tokens as a solo
    make_decode run (per-element math is independent)."""
    import functools as ft

    from brpc_tpu.models.transformer_lm import (empty_paged_cache,
                                                make_paged_batch_decode,
                                                make_paged_io)

    cfg, params, prompt = _setup()
    page, s = 8, prompt.shape[1]
    pps = cfg.max_seq // page
    prefill, step = make_paged_batch_decode(cfg, page)
    _gather, _scatter, insert = make_paged_io(cfg, page)
    cache = empty_paged_cache(cfg, 4 * pps + 1, 4, page)
    # insert session 0 (prompt row 0) into slot 2, nothing else active
    c1, logits = jax.jit(ft.partial(prefill, params))(prompt[:1],
                                                      jnp.int32(s))
    bt = np.zeros((4, pps), np.int32)
    bt[2] = 1 + np.arange(pps)
    cache = insert(cache, jnp.asarray(bt[2]), c1, jnp.int32(2))
    cache["len"] = cache["len"].at[2].set(s)
    active = jnp.zeros((4,), bool).at[2].set(True)
    toks = [int(jnp.argmax(logits[0]))]
    tokens = jnp.zeros((4,), jnp.int32).at[2].set(toks[0])
    step_j = jax.jit(ft.partial(step, params))
    for _ in range(5):
        cache, lg = step_j(cache, jnp.asarray(bt), tokens, active)
        t = int(jnp.argmax(lg[2]))
        toks.append(t)
        tokens = tokens.at[2].set(t)
    want = np.asarray(generate(params, cfg, prompt[:1], 6))[0].tolist()
    assert toks == want


def test_batch_decode_scan_layers_rejected():
    from brpc_tpu.models.transformer_lm import make_paged_batch_decode
    cfg = LMConfig(vocab=64, dim=32, heads=2, depth=2, max_seq=16,
                   scan_layers=True)
    with pytest.raises(NotImplementedError, match="unrolled"):
        make_paged_batch_decode(cfg, 16)


def _decode_server(cfg, params, slots=4):
    from brpc_tpu.models.lm_service import LMService
    from brpc_tpu.server import Server

    srv = Server()
    svc = LMService(cfg=cfg, params=params, decode_slots=slots)
    srv.add_service(svc, name="LM")
    assert srv.start("127.0.0.1:0") == 0
    return srv, svc


def _stream_decode(srv, prompt, max_new, timeout=120.0):
    """One streamed decode session: returns (tokens, close_reason,
    ttft_seconds)."""
    import time

    from brpc_tpu.client import Channel, Controller
    from brpc_tpu.models.lm_service import (pack_generate_request,
                                            unpack_token)
    from brpc_tpu.streaming import StreamOptions, stream_create

    toks, closed, first = [], [], []

    def on_received(st, msgs):
        if not first:
            first.append(time.monotonic())
        toks.extend(unpack_token(m) for m in msgs)

    ch = Channel()
    ch.init(str(srv.listen_endpoint))
    cntl = Controller()
    cntl.timeout_ms = int(timeout * 1000)
    stream = stream_create(cntl, StreamOptions(
        on_received=on_received,
        on_closed=lambda st: closed.append(st.close_reason)))
    t0 = time.monotonic()
    c = ch.call_method("LM.Decode",
                       pack_generate_request(prompt, max_new),
                       cntl=cntl)
    assert not c.failed, (c.error_code, c.error_text)
    deadline = time.monotonic() + timeout
    while not closed and time.monotonic() < deadline:
        time.sleep(0.005)
    assert closed, "decode stream never closed"
    return toks, closed[0], (first[0] - t0 if first else None)


def test_decode_streams_tokens_and_finishes():
    """Server-streaming decode: one token chunk per step, greedy-
    identical with Generate, stream closed with reason 'finished'."""
    cfg, params, prompt = _setup()
    srv, svc = _decode_server(cfg, params)
    try:
        toks, reason, ttft = _stream_decode(srv, np.asarray(prompt[:1]),
                                            6)
        want = np.asarray(generate(params, cfg, prompt[:1], 6))[0]
        assert toks == want.tolist()
        assert reason == "finished"
        assert ttft is not None
    finally:
        srv.stop()


def test_decode_join_mid_batch_and_evict():
    """Continuous batching: a second session joins while the first is
    mid-generation; both produce their solo-greedy tokens; finished
    sessions evict and free their slot for reuse."""
    import threading

    cfg, params, prompt = _setup()
    p2 = np.asarray(jax.random.randint(jax.random.PRNGKey(7), (1, 5),
                                       0, cfg.vocab, jnp.int32))
    srv, svc = _decode_server(cfg, params, slots=2)
    try:
        res = {}
        t1 = threading.Thread(target=lambda: res.__setitem__(
            "a", _stream_decode(srv, np.asarray(prompt[:1]), 10)))
        t1.start()
        time.sleep(0.3)          # a is mid-generation; b joins the batch
        res["b"] = _stream_decode(srv, p2, 4)
        t1.join(120)
        wa = np.asarray(generate(params, cfg, prompt[:1], 10))[0]
        wb = np.asarray(generate(params, cfg, p2, 4))[0]
        assert res["a"][0] == wa.tolist()
        assert res["b"][0] == wb.tolist()
        assert res["a"][1] == res["b"][1] == "finished"
        # both evicted: slots free again, and a THIRD session reuses one
        deadline = time.time() + 10
        while svc.batcher().live_slots() and time.time() < deadline:
            time.sleep(0.01)
        assert svc.batcher().live_slots() == 0
        toks, reason, _ = _stream_decode(srv, p2, 3)
        assert toks == wb.tolist()[:3]
        assert reason == "finished"
    finally:
        srv.stop()


def test_decode_ttft_under_load():
    """TTFT: with more sessions than slots, queued sessions still get
    their first token as soon as a slot frees (prefill-on-join emits
    immediately), and every session completes correctly."""
    import threading

    cfg, params, prompt = _setup()
    srv, svc = _decode_server(cfg, params, slots=2)
    try:
        results = {}

        def one(i):
            results[i] = _stream_decode(srv, np.asarray(prompt[:1]), 5)

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(5)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(180)
        want = np.asarray(generate(params, cfg, prompt[:1], 5))[0]
        for i, (toks, reason, ttft) in results.items():
            assert toks == want.tolist(), i
            assert reason == "finished"
            assert ttft is not None and ttft < 120
    finally:
        srv.stop()


def test_decode_stalled_client_evicted_not_hol_blocking():
    """A client that stops consuming (tiny window, handler wedged) is
    evicted with reason 'backpressure' after ONE bounded stall — it
    must not head-of-line-block the other live sessions' tokens."""
    import threading

    from brpc_tpu.client import Channel, Controller
    from brpc_tpu.models.lm_service import pack_generate_request
    from brpc_tpu.streaming import StreamOptions, stream_create

    cfg, params, prompt = _setup()
    srv, svc = _decode_server(cfg, params, slots=4)
    try:
        ch = Channel()
        ch.init(str(srv.listen_endpoint))
        stall_closed = []
        wedge = threading.Event()
        cntl = Controller()
        cntl.timeout_ms = 120_000
        stalled = stream_create(cntl, StreamOptions(
            on_received=lambda s, m: wedge.wait(60),
            on_closed=lambda s: stall_closed.append(s.close_reason),
            max_buf_size=16))           # 4 tokens of credit, no acks
        c = ch.call_method("LM.Decode",
                           pack_generate_request(
                               np.asarray(prompt[:1]), 20), cntl=cntl)
        assert not c.failed, c.error_text
        # a healthy session joins the same batch and must complete
        toks, reason, _ = _stream_decode(srv, np.asarray(prompt[:1]), 8)
        want = np.asarray(generate(params, cfg, prompt[:1], 8))[0]
        assert toks == want.tolist()
        assert reason == "finished"
        # server side evicts the stalled session (slot freed)...
        deadline = time.time() + 60
        while svc.batcher().live_slots() and time.time() < deadline:
            time.sleep(0.02)
        assert svc.batcher().live_slots() == 0
        # ...and once the wedged client handler releases, the queued
        # FIN delivers the NAMED reason
        wedge.set()
        deadline = time.time() + 10
        while not stall_closed and time.time() < deadline:
            time.sleep(0.02)
        assert stall_closed == ["backpressure"], stall_closed
    finally:
        wedge.set()
        srv.stop()


def test_decode_rejects_bad_shapes():
    cfg, params, prompt = _setup()
    srv, _ = _decode_server(cfg, params)
    try:
        from brpc_tpu.client import Channel, Controller
        from brpc_tpu.models.lm_service import pack_generate_request
        from brpc_tpu.streaming import StreamOptions, stream_create

        ch = Channel()
        ch.init(str(srv.listen_endpoint))
        # no stream attached
        c = ch.call_method("LM.Decode",
                           pack_generate_request(
                               np.asarray(prompt[:1]), 4),
                           cntl=Controller())
        assert c.failed and "stream" in c.error_text
        # batch != 1
        cntl = Controller()
        stream_create(cntl, StreamOptions())
        c = ch.call_method("LM.Decode",
                           pack_generate_request(np.asarray(prompt), 4),
                           cntl=cntl)
        assert c.failed and "one session" in c.error_text
        # over max_new cap
        cntl = Controller()
        stream_create(cntl, StreamOptions())
        c = ch.call_method("LM.Decode",
                           pack_generate_request(
                               np.asarray(prompt[:1]), 999),
                           cntl=cntl)
        assert c.failed and "max_new" in c.error_text
    finally:
        srv.stop()


def test_decode_scan_layers_moe_rejected():
    """Scanned decode supports dense blocks (see
    test_scanned_decode_matches_unrolled); the MoE combination is the
    one explicitly unsupported shape and must say so loudly."""
    from brpc_tpu.models.transformer_lm import LMConfig
    cfg = LMConfig(vocab=64, dim=32, heads=2, depth=2, max_seq=16,
                   scan_layers=True, moe_experts=2)
    with pytest.raises(NotImplementedError, match="MoE"):
        make_decode(cfg)


def test_scan_generator_matches_stepwise_greedy():
    """The whole-completion scan program must produce the same greedy
    tokens as the per-step generator (same model, same prompt)."""
    import numpy as np
    import jax

    from brpc_tpu.models.transformer_lm import (LMConfig, init_params,
                                                make_generator,
                                                make_scan_generator)
    cfg = LMConfig(vocab=64, dim=32, heads=4, depth=2, max_seq=48,
                   remat=False)
    params = init_params(jax.random.PRNGKey(3), cfg)
    prompt = np.arange(6, dtype=np.int32)[None, :] % cfg.vocab
    step_out = np.asarray(make_generator(cfg, params)(prompt, 10))
    scan_out = np.asarray(make_scan_generator(cfg, params)(prompt, 10))
    np.testing.assert_array_equal(step_out, scan_out)


def test_scan_generator_sampling_contract():
    import numpy as np
    import jax
    import pytest

    from brpc_tpu.models.transformer_lm import (LMConfig, init_params,
                                                make_scan_generator)
    cfg = LMConfig(vocab=64, dim=32, heads=4, depth=1, max_seq=32,
                   remat=False)
    params = init_params(jax.random.PRNGKey(0), cfg)
    gen = make_scan_generator(cfg, params)
    prompt = np.array([[1, 2, 3]], dtype=np.int32)
    with pytest.raises(ValueError, match="rng"):
        gen(prompt, 4, temperature=0.8)
    a = np.asarray(gen(prompt, 6, temperature=0.8,
                       rng=jax.random.PRNGKey(1)))
    b = np.asarray(gen(prompt, 6, temperature=0.8,
                       rng=jax.random.PRNGKey(1)))
    np.testing.assert_array_equal(a, b)      # same key -> same sample
    assert a.shape == (1, 6)
    with pytest.raises(ValueError, match="max_seq"):
        gen(prompt, 64)


def test_scanned_decode_matches_unrolled():
    """cfg.scan_layers decode (one compiled layer body, stacked caches)
    must produce the same logits/tokens as the unrolled path given the
    same weights — the compile-time answer for deep serving models."""
    import functools as ft

    import jax
    import jax.numpy as jnp
    import numpy as np

    from brpc_tpu.models.transformer_lm import (LMConfig, empty_cache,
                                                init_params, make_decode)

    kw = dict(vocab=64, dim=32, heads=2, depth=3, max_seq=16, mlp_mult=2,
              remat=False, attn_impl="dense")
    cfg_u = LMConfig(**kw)
    cfg_s = LMConfig(**kw, scan_layers=True)
    pu = init_params(jax.random.PRNGKey(0), cfg_u)
    # same weights, stacked layout
    ps = {k: v for k, v in pu.items() if not k.startswith("blk")}
    blks = [pu[f"blk{i}"] for i in range(cfg_u.depth)]
    ps["blocks"] = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                          *blks)

    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 5), 0,
                                cfg_u.vocab, jnp.int32)
    pre_u, step_u = make_decode(cfg_u)
    pre_s, step_s = make_decode(cfg_s)
    cu, lu = jax.jit(ft.partial(pre_u, pu))(prompt)
    cs, ls = jax.jit(ft.partial(pre_s, ps))(prompt)
    np.testing.assert_allclose(np.asarray(lu), np.asarray(ls),
                               atol=2e-2, rtol=2e-2)
    tok = jnp.argmax(lu, axis=-1).astype(jnp.int32)
    su = jax.jit(ft.partial(step_u, pu))
    ss = jax.jit(ft.partial(step_s, ps))
    for _ in range(4):
        cu, lu = su(cu, tok)
        cs, ls = ss(cs, tok)
        np.testing.assert_allclose(np.asarray(lu), np.asarray(ls),
                                   atol=2e-2, rtol=2e-2)
        tok = jnp.argmax(lu, axis=-1).astype(jnp.int32)
    # stacked empty_cache matches the scanned layout
    ec = empty_cache(cfg_s, 2)
    assert ec["k"].shape == (3, 2, 16, 2, 16)


def test_scanned_decode_int8():
    """Stacked scan_layers trees quantize (per-layer,out-channel
    scales) and the scanned decode streams them."""
    import functools as ft

    import jax
    import jax.numpy as jnp
    import numpy as np

    from brpc_tpu.models.transformer_lm import (LMConfig, init_params,
                                                make_decode)
    from brpc_tpu.ops.quant import QuantTensor, quantize_lm_params

    cfg = LMConfig(vocab=64, dim=32, heads=2, depth=2, max_seq=16,
                   mlp_mult=2, remat=False, attn_impl="dense",
                   scan_layers=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    qp = quantize_lm_params(params)
    assert isinstance(qp["blocks"]["wqkv"], QuantTensor)
    assert qp["blocks"]["wqkv"].s.shape == (2, 3 * 32)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 4), 0,
                                cfg.vocab, jnp.int32)
    pre, step = make_decode(cfg)
    cf, lf = jax.jit(ft.partial(pre, params))(prompt)
    cq, lq = jax.jit(ft.partial(pre, qp))(prompt)
    # int8 is an approximation: same argmax is the serving contract
    tok = jnp.argmax(lf, axis=-1).astype(jnp.int32)
    cq, lq2 = jax.jit(ft.partial(step, qp))(cq, tok)
    cf, lf2 = jax.jit(ft.partial(step, params))(cf, tok)
    corr = np.corrcoef(np.asarray(lf2).ravel(),
                       np.asarray(lq2).ravel())[0, 1]
    assert corr > 0.99, corr


def test_lm_service_scan_layers_quantized():
    """LMService over RPC with a scan_layers + int8 config: the serving
    stack (scan generator, quantized stacked tree) composes end-to-end."""
    import numpy as np

    from brpc_tpu.client import Channel, Controller
    from brpc_tpu.models.lm_service import (LMService,
                                            pack_generate_request,
                                            unpack_generated)
    from brpc_tpu.models.transformer_lm import LMConfig
    from brpc_tpu.server import Server

    cfg = LMConfig(vocab=128, dim=32, heads=2, depth=2, max_seq=64,
                   remat=False, scan_layers=True, attn_impl="dense")
    srv = Server()
    srv.add_service(LMService(cfg=cfg, quantize=True), name="LM")
    assert srv.start("127.0.0.1:0") == 0
    try:
        ch = Channel()
        ch.init(str(srv.listen_endpoint))
        cntl = Controller()
        cntl.timeout_ms = 120_000        # first compile pays its way
        prompt = np.array([[1, 2, 3]], dtype=np.int32)
        c = ch.call_method("LM.Generate",
                           pack_generate_request(prompt, 4), cntl=cntl)
        assert not c.failed, c.error_text
        out = unpack_generated(bytes(c.response))
        assert out.shape == (1, 4)
        assert (out >= 0).all() and (out < cfg.vocab).all()
    finally:
        srv.stop()
