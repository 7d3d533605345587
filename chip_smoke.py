"""chip_smoke.py — does the serving main path start and answer on the chip?

ONE process, ONE chip, the entry points a user calls, at the full width
of the repo's dense LM (``bench_device_mfu``'s config: vocab 8192, dim
2048, heads 16, depth 8, max_seq 2048 — 436M parameters, float32,
seeded random weights):

1. device header — exit non-zero unless JAX's platform is ``tpu``;
2. engine      — build ``brpc_tpu/native/src/engine.cpp`` in this run;
3. serving     — native ``Server`` + paged ``LMService``; over a real
                 ``Channel``: ``LM.Info``, one ``LM.Generate``, then 12
                 ``LM.Decode`` streams over 8 slots (short prompts, one
                 >1k-token prompt through the flash kernel, prefix-cache
                 full and partial hits); every token checked against
                 ``make_forward`` teacher-forced on the same chip;
4. kernels     — flash attention forward (serving + bench shapes) and
                 backward, two ``make_train_step`` steps through the
                 flash kernels, ``checksum_u32``, each against its dense
                 / numpy reference;
5. mesh        — the multi-device programs on ``jax.devices()[:4]`` when
                 there are four chips.

Any failed stage raises, so the exit code is non-zero and the result
line is never printed.  The last line of stdout on success is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

The stage functions take their sizes as arguments:
``tests/test_chip_smoke.py`` calls them at toy widths on the CPU.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# the repo's full-width dense config (bench.py bench_device_mfu)
FULL_LM = dict(vocab=8192, dim=2048, heads=16, depth=8, max_seq=2048,
               mlp_mult=4)
SLOTS, PAGE = 8, 16
# (prompt_len, max_new, index of the request whose prompt is re-sent).
# 12 streams over 8 slots: the last four join mid-batch as earlier ones
# evict.  Request 4's 1,099-token context buckets to 2048 and prefills
# through the flash kernel; 8 re-sends 2 (context = 2 full pages: a
# FULL prefix hit, pages aliased, no prefill); 9 re-sends 3 (2 full
# pages + 8 tokens: a PARTIAL hit, remainder through a chunk slice).
FULL_REQUESTS = ((6, 8, None), (12, 12, None), (33, 16, None),
                 (41, 10, None), (1100, 16, None), (20, 6, None),
                 (9, 24, None), (30, 4, None), (33, 12, 2), (41, 8, 3),
                 (17, 10, None), (7, 5, None))
FULL_GENERATE = (12, 8)                  # (prompt_len, max_new)

# SGD step size for the train-step check: LMConfig's default 0.05
# diverges at the full width (loss 11.9 -> 15.2 -> 23.0 over three steps
# in a CPU run with dense attention); 1e-3 descends (11.9 -> 11.0 -> 10.4)
TRAIN_LR = 1e-3

# A served token may differ from the reference argmax only on a bf16
# near-tie: by at most this fraction of the standard deviation of that
# position's logits.  (The argmax of 8192 random logits stands ~3.8 std
# above a random token, so a wrong computation misses by whole stds.)
LOGIT_MARGIN_STD = 0.05


def log(msg: str) -> None:
    print(msg, flush=True)


def device_header() -> dict:
    """Print what JAX found; return the result line's ``device``."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log(f"jax {jax.__version__}  platform={dev['platform']}  "
        f"device_kind={dev['kind']!r}  devices={dev['count']}")
    return dev


def make_prompts(requests, vocab: int):
    rng = np.random.default_rng(0)
    prompts = []
    for plen, _max_new, dup_of in requests:
        prompts.append(prompts[dup_of] if dup_of is not None
                       else rng.integers(0, vocab, (plen,), dtype=np.int32))
    return prompts


# -- serving ----------------------------------------------------------------

def serve(svc, requests, prompts, gen_prompt, gen_new: int,
          timeout_s: float):
    """Start a native-engine server around ``svc`` and drive it over a
    loopback Channel.  Returns ``(generated, streams, telemetry)``:
    the Generate tokens, per-request ``(tokens, close_reason)``, and
    the engine's telemetry snapshot."""
    from brpc_tpu.client import Channel, Controller
    from brpc_tpu.models.lm_service import (pack_generate_request,
                                            unpack_generated, unpack_token)
    from brpc_tpu.server import Server, ServerOptions
    from brpc_tpu.streaming import StreamOptions, stream_create

    opts = ServerOptions()
    opts.native = True
    opts.usercode_inline = True
    srv = Server(opts)
    srv.add_service(svc, name="LM")
    if srv.start("127.0.0.1:0") != 0:
        raise RuntimeError("server failed to start")
    try:
        bridge = srv._native_bridge
        if bridge is None:
            raise RuntimeError("ServerOptions.native=True but the Python "
                               "transport is listening")
        ch = Channel()
        ch.init(str(srv.listen_endpoint))

        info = json.loads(ch.call("LM.Info", b""))
        log(f"LM.Info: {info}")
        if info["param_bytes"] != svc._param_bytes:
            raise RuntimeError("LM.Info disagrees with the service")

        cntl = Controller()
        cntl.timeout_ms = int(timeout_s * 1000)
        c = ch.call_method(
            "LM.Generate", pack_generate_request(gen_prompt[None], gen_new),
            cntl=cntl)
        if c.failed:
            raise RuntimeError(f"LM.Generate failed: {c.error_text}")
        generated = unpack_generated(c.response)[0]

        n = len(requests)
        toks = [[] for _ in range(n)]
        reasons = [None] * n
        closed = [threading.Event() for _ in range(n)]

        def on_closed(st, i):
            reasons[i] = st.close_reason
            closed[i].set()

        for i, (_plen, max_new, _dup) in enumerate(requests):
            cntl = Controller()
            cntl.timeout_ms = 60_000
            stream_create(cntl, StreamOptions(
                on_received=lambda st, msgs, i=i: toks[i].extend(
                    unpack_token(m) for m in msgs),
                on_closed=lambda st, i=i: on_closed(st, i)))
            c = ch.call_method(
                "LM.Decode",
                pack_generate_request(prompts[i][None], max_new), cntl=cntl)
            if c.failed:
                raise RuntimeError(f"LM.Decode {i} failed: {c.error_text}")
        deadline = time.monotonic() + timeout_s
        for i, ev in enumerate(closed):
            if not ev.wait(max(0.0, deadline - time.monotonic())):
                raise RuntimeError(
                    f"stream {i} still open after {timeout_s:.0f}s "
                    f"({len(toks[i])} tokens)")
        return generated, list(zip(toks, reasons)), bridge.engine.telemetry()
    finally:
        srv.stop()


def check_against_reference(cfg, params, sessions) -> None:
    """Teacher-forced check of served tokens against ``make_forward``
    on the same device: ``sessions`` is ``[(prompt, served)]``; each
    served token must be the reference argmax or within
    LOGIT_MARGIN_STD of it.  The reference attends DENSE at every
    length (never the flash kernel the long prefill is served by)."""
    import jax

    from brpc_tpu.models.transformer_lm import LMConfig, make_forward

    ref_cfg = LMConfig(vocab=cfg.vocab, dim=cfg.dim, heads=cfg.heads,
                       depth=cfg.depth, mlp_mult=cfg.mlp_mult,
                       max_seq=cfg.max_seq, remat=False, attn_impl="dense")
    fwd = jax.jit(make_forward(ref_cfg))
    # causal: right-padding changes nothing to its left, so sessions
    # share one compiled program per power-of-two bucket
    groups: dict = {}
    for prompt, served in sessions:
        n = len(prompt) + len(served) - 1
        bucket = 64
        while bucket < n:
            bucket <<= 1
        groups.setdefault(min(bucket, cfg.max_seq), []).append(
            (prompt, served))
    worst, mismatched, total = 0.0, 0, 0
    for bucket, group in sorted(groups.items()):
        ids = np.zeros((len(group), bucket), np.int32)
        for r, (prompt, served) in enumerate(group):
            seq = np.concatenate([prompt, served])[:-1]
            ids[r, :len(seq)] = seq
        logits = np.asarray(fwd(params, ids))
        if not np.isfinite(logits).all():
            raise RuntimeError("reference logits are not finite")
        for r, (prompt, served) in enumerate(group):
            for j, tok in enumerate(served):
                row = logits[r, len(prompt) - 1 + j]
                total += 1
                best = int(row.argmax())
                if tok == best:
                    continue
                mismatched += 1
                margin = float((row[best] - row[tok]) / row.std())
                worst = max(worst, margin)
                if margin > LOGIT_MARGIN_STD:
                    raise RuntimeError(
                        f"served token {tok} at position "
                        f"{len(prompt) + j} trails the reference argmax "
                        f"{best} by {margin:.3f} logit std "
                        f"(limit {LOGIT_MARGIN_STD})")
    log(f"reference check: {total} served tokens, {total - mismatched} "
        f"are the reference argmax, {mismatched} near-ties; worst margin "
        f"{worst:.4f} logit std (limit {LOGIT_MARGIN_STD})")


def stage_serving(lm_kw: dict, requests, gen_req, slots: int, page: int,
                  meter, expect_flash: bool,
                  timeout_s: float = 900.0) -> None:
    import jax

    from brpc_tpu.models.lm_service import LMService
    from brpc_tpu.models.transformer_lm import LMConfig, init_params

    t_stage, c_stage = time.monotonic(), meter.seconds
    cfg = LMConfig(remat=False, **lm_kw)
    params = init_params(jax.random.PRNGKey(0), cfg)
    svc = LMService(cfg=cfg, params=params, decode_slots=slots,
                    page=page)
    log(f"LMService: {lm_kw}  params={svc._param_bytes / 1e9:.3f} GB "
        f"float32  paged page={page} slots={slots}")
    prompts = make_prompts(requests, cfg.vocab)
    gen_prompt = np.random.default_rng(1).integers(
        0, cfg.vocab, (gen_req[0],), dtype=np.int32)

    generated, streams, tel = serve(svc, requests, prompts, gen_prompt,
                                    gen_req[1], timeout_s)
    if len(generated) != gen_req[1]:
        raise RuntimeError(f"LM.Generate returned {len(generated)} tokens, "
                           f"want {gen_req[1]}")
    for i, ((toks, reason), (_p, max_new, _d)) in enumerate(
            zip(streams, requests)):
        if reason != "finished" or len(toks) != max_new:
            raise RuntimeError(
                f"stream {i}: closed {reason!r} with {len(toks)} tokens, "
                f"want 'finished' with {max_new}")
    log(f"LM.Decode: {len(streams)} of {len(streams)} streams closed "
        f"'finished' with max_new tokens (over {slots} slots)")

    # the path taken, from the program's own counters
    batcher = svc.batcher()
    kv = batcher.kv_stats()
    decode = tel["methods"].get("LM.Decode", {})
    log(f"engine: LM.Decode stream_opens={decode.get('stream_opens')} "
        f"fb_stream_open={decode.get('fb_stream_open')}  "
        f"streams={tel['streams']}")
    n_tokens = sum(r[1] for r in requests)
    if decode.get("stream_opens") != len(requests) \
            or decode.get("fb_stream_open") \
            or any(tel["streams"]["fallbacks"].values()) \
            or tel["streams"]["chunks_out"] != n_tokens:
        raise RuntimeError("the kind-5 lane did not carry every stream")
    log(f"kv_stats: steps={kv['steps']} prefills_run={kv['prefills_run']} "
        f"alloc={kv['alloc']} prefix={kv['prefix']} sched={kv['sched']}")
    n_dup = sum(1 for r in requests if r[2] is not None)
    if kv["prefills_run"] != len(requests) - n_dup:
        raise RuntimeError("re-sent prompts were prefilled again")
    if kv["prefix"]["hits"] + kv["prefix"]["partial_hits"] != n_dup:
        raise RuntimeError("re-sent prompts did not hit the prefix cache")
    if not max(r[1] for r in requests) <= kv["steps"] < n_tokens:
        raise RuntimeError(f"{kv['steps']} steps for {n_tokens} tokens: "
                           "the streams were not batched")
    for i, (_p, _n, dup_of) in enumerate(requests):
        if dup_of is not None:
            n = min(len(streams[i][0]), len(streams[dup_of][0]))
            log(f"stream {i} (re-sent prompt of {dup_of}): first {n} tokens "
                f"identical: {streams[i][0][:n] == streams[dup_of][0][:n]}")

    # weights are arguments: the step's module is kilobytes, and (where
    # the backend reports it) peak memory holds ONE copy of them
    step_txt = batcher._step.func.lower(
        *batcher._step.args, batcher._cache, batcher._bt,
        batcher._tokens, batcher._active).as_text()
    log(f"batch step lowers to {len(step_txt) / 1024:.0f} KiB of module "
        f"text for {svc._param_bytes / 1e9:.3f} GB of weights")
    if len(step_txt) > svc._param_bytes / 4:
        raise RuntimeError("the step's module embeds the weights")
    if expect_flash:
        long_ctx = max(len(p) for p in prompts) - 1
        bucket = np.zeros((1, cfg.max_seq), np.int32)
        n_kernels = batcher._prefill.func.lower(
            *batcher._prefill.args, bucket,
            np.int32(long_ctx)).as_text().count(
                "tpu_custom_call")
        log(f"prefill bucket {cfg.max_seq} (served the {long_ctx}-token "
            f"context) holds {n_kernels} compiled Mosaic kernel calls")
        if n_kernels != cfg.depth:
            raise RuntimeError("the long prefill did not go through the "
                               "compiled flash kernel")
    pool_bytes = batcher._alloc.stats()["pages"] * batcher._alloc.page_bytes
    mem = jax.devices()[0].memory_stats()
    if mem is None:
        log("peak device memory: not reported by this backend")
    else:
        peak = mem["peak_bytes_in_use"]
        log(f"peak_bytes_in_use={peak / 1e9:.3f} GB  (weights "
            f"{svc._param_bytes / 1e9:.3f} GB + KV pool "
            f"{pool_bytes / 1e9:.3f} GB resident)")
        if peak >= 2 * svc._param_bytes + pool_bytes:
            raise RuntimeError("peak memory holds more than one copy of "
                               "the weights")
    serve_s = time.monotonic() - t_stage
    compile_s = meter.seconds - c_stage
    log(f"serving stage: {serve_s:.1f}s wall, of which {compile_s:.1f}s "
        "tracing+compiling")

    sessions = [(gen_prompt, np.asarray(generated, np.int32))] + [
        (prompts[i], np.asarray(toks, np.int32))
        for i, (toks, _reason) in enumerate(streams)]
    check_against_reference(cfg, params, sessions)


# -- kernels ----------------------------------------------------------------

def _dense_rows(q, k, v, r0: int, r1: int):
    """Causal dense attention for query rows [r0, r1) against the whole
    context, at full float32 matmul precision — the blockwise reference
    for sequences whose (s, s) scores do not fit."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        qf, kf, vf = (t.astype(jnp.float32) for t in (q[:, r0:r1], k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) / (q.shape[-1] ** 0.5)
        mask = (jnp.arange(r0, r1)[:, None]
                >= jnp.arange(k.shape[1])[None, :])
        p = jax.nn.softmax(jnp.where(mask[None, None], s, -1e30), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, vf)


def _max_err(name: str, got, want, tol: float) -> None:
    """Largest error as a fraction of the reference's largest value."""
    want = np.asarray(want, np.float32)
    err = float(np.max(np.abs(np.asarray(got, np.float32) - want))
                / np.max(np.abs(want)))
    log(f"{name}: max error {err:.2e} of the reference's range "
        f"(tolerance {tol:.0e})")
    if not err <= tol:          # also catches NaN
        raise RuntimeError(f"{name}: error {err} exceeds {tol}")


def flash_forward_check(shape, dtype, tol: float) -> None:
    """Causal flash forward at ``shape`` (b, s, h, d) against dense
    rows at the head, the middle and the tail of the sequence."""
    import jax
    import jax.numpy as jnp

    from brpc_tpu.ops.flash_attention import flash_attention

    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k, v = (jax.random.normal(kk, shape, dtype) * 0.5 for kk in ks)
    out = jax.jit(lambda q, k, v: flash_attention(q, k, v, True))(q, k, v)
    s, blk = shape[1], min(256, shape[1])
    for r0 in sorted({0, (s // 2 // blk) * blk, s - blk}):
        _max_err(f"flash fwd {jnp.dtype(dtype).name} {shape} "
                 f"rows {r0}:{r0 + blk}",
                 out[:, r0:r0 + blk], _dense_rows(q, k, v, r0, r0 + blk),
                 tol)


def flash_backward_check(shape, tol: float) -> None:
    """dq/dk/dv of the causal flash kernels (float32, the dtype the
    train step feeds them) against autodiff through dense attention."""
    import jax
    import jax.numpy as jnp

    from brpc_tpu.ops.flash_attention import flash_attention

    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q, k, v, w = (jax.random.normal(kk, shape, jnp.float32) * 0.5
                  for kk in ks)
    got = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v, True) * w),
        argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(_dense_rows(q, k, v, 0, shape[1]) * w),
        argnums=(0, 1, 2)))(q, k, v)
    for name, g, r in zip(("dq", "dk", "dv"), got, want):
        _max_err(f"flash bwd {name} float32 {shape}", g, r, tol)


def train_steps_check(lm_kw: dict, batch: int, accum: int, seq: int,
                      tol: float) -> None:
    """Two ``make_train_step(use_flash=True, remat=True)`` steps: the
    first loss against the dense reference forward on the same batch,
    the second finite and lower (the backward kernels' gradients
    descend)."""
    import jax
    import jax.numpy as jnp

    from brpc_tpu.models.transformer_lm import (LMConfig, init_params,
                                                make_forward,
                                                make_train_step)

    cfg = LMConfig(use_flash=True, remat=True, lr=TRAIN_LR, **lm_kw)
    params = init_params(jax.random.PRNGKey(0), cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (batch * accum, seq),
                             0, cfg.vocab, jnp.int32)
    labels = jnp.roll(ids, -1, axis=-1)
    log(f"train step: {lm_kw} use_flash remat lr={TRAIN_LR}  "
        f"batch={batch} accum={accum} seq={seq}")

    ref_cfg = LMConfig(remat=False, attn_impl="dense", **lm_kw)
    fwd = jax.jit(make_forward(ref_cfg))
    nll = []
    for row_ids, row_lbl in zip(ids, labels):
        logp = jax.nn.log_softmax(fwd(params, row_ids[None])[0], axis=-1)
        nll.append(-jnp.take_along_axis(logp, row_lbl[:, None], axis=-1))
    want = float(jnp.mean(jnp.stack(nll)))

    step = jax.jit(make_train_step(cfg, accum=accum), donate_argnums=(0,))
    params, loss1 = step(params, ids, labels)
    params, loss2 = step(params, ids, labels)
    loss1, loss2 = float(loss1), float(loss2)
    log(f"train step: loss {loss1:.4f} (dense reference {want:.4f}, "
        f"tolerance {tol:.0e}) -> {loss2:.4f}")
    if not abs(loss1 - want) <= tol:
        raise RuntimeError("flash train step's loss disagrees with the "
                           "dense reference")
    if not loss2 < loss1:
        raise RuntimeError("the second step's loss did not fall")


def checksum_check(nbytes: int) -> None:
    import jax.numpy as jnp

    from brpc_tpu.ops.device_ops import checksum_u32

    host = np.random.default_rng(4).integers(
        0, 2 ** 32, (nbytes // 4,), dtype=np.uint32)
    want = int(host.sum(dtype=np.uint64) & 0xFFFFFFFF)
    got = checksum_u32(jnp.asarray(host.view(np.int32)))
    log(f"checksum_u32 over {nbytes} bytes: {got:#010x} "
        f"(numpy {want:#010x})")
    if got != want:
        raise RuntimeError("checksum_u32 disagrees with numpy")


# The kernels feed the MXU bf16 products (float32 inputs included: the
# default matmul precision) and accumulate in float32, against a
# reference at full float32 precision: errors sit at a few 2^-8 of the
# result's range (v5e, PR 21: forward 2.2e-3..3.4e-3, backward
# 3.0e-3..4.5e-3; the train step's loss differed by 1e-4).
KERNEL_TOL = 1e-2
LOSS_TOL = 5e-3


def stage_kernels(flash_fwd, flash_bwd_shape, train_kw: dict,
                  train_batch: int, train_accum: int, train_seq: int,
                  checksum_bytes: int) -> None:
    """``flash_fwd`` is ``[(shape, dtype)]``."""
    for shape, dtype in flash_fwd:
        flash_forward_check(shape, dtype, KERNEL_TOL)
    flash_backward_check(flash_bwd_shape, KERNEL_TOL)
    train_steps_check(train_kw, train_batch, train_accum, train_seq,
                      LOSS_TOL)
    checksum_check(checksum_bytes)


# -- mesh -------------------------------------------------------------------

def stage_mesh(devices, lm_kw: dict, lm_seq: int) -> None:
    """The multi-device programs on real chips, the sharded train step
    at the full LM width (sequence below the flash crossover: a Pallas
    call is not partitioned by the sharding propagation)."""
    from brpc_tpu.models.lm_service import LMService
    from brpc_tpu.models.transformer_lm import LMConfig
    from brpc_tpu.parallel.mesh_dryrun import run_mesh_programs

    log(f"mesh stage on devices {[d.id for d in devices]}")
    run_mesh_programs(devices, lm_cfg=LMConfig(remat=True, **lm_kw),
                      lm_seq=lm_seq, tp=2)
    # a finding for the replica work (ROADMAP R5), not a check: where
    # does a second service in this process put its weights and cache?
    svc = LMService()
    batcher = svc.batcher()
    batcher._ensure_engine()
    log("second LMService in this process: weights on devices "
        f"{sorted(d.id for d in svc.params['embed'].devices())}, KV pool "
        f"on {sorted(d.id for d in batcher._cache['pk0'].devices())}")


def report_cache_effect(cache_dir: str, meter) -> None:
    """Print this run's compile seconds beside those of the previous
    run against the same cache directory (whose record sits among the
    cache entries), then leave this run's record."""
    record = os.path.join(cache_dir, "chip_smoke_last_run.json")
    log(f"total {meter.seconds:.1f}s tracing+compiling (persistent "
        f"cache: {meter.hits} hits, {meter.misses} misses)")
    if os.path.exists(record):
        with open(record) as f:
            prev = json.load(f)["compile_s"]
        log(f"previous run against this cache directory: {prev:.1f}s "
            f"tracing+compiling — a drop of {prev - meter.seconds:.1f}s")
    os.makedirs(cache_dir, exist_ok=True)
    with open(record, "w") as f:
        json.dump({"compile_s": round(meter.seconds, 1)}, f)


def main() -> int:
    dev = device_header()
    if dev["platform"] != "tpu":
        log(f"chip_smoke: no accelerator — JAX platform is "
            f"{dev['platform']!r}; nothing was run")
        return 1
    import jax
    import jax.numpy as jnp

    from brpc_tpu import native
    from brpc_tpu.utils.compile_cache import (CompileMeter,
                                              enable_compile_cache)

    t0 = time.monotonic()
    cache_dir = enable_compile_cache()
    meter = CompileMeter()
    log(f"compile cache: {cache_dir} "
        f"({len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0} "
        "entries at start)")

    so = native.build(force=True)
    if native.load() is None:
        raise RuntimeError("the native engine built but did not load")
    log(f"engine: built {os.path.relpath(so)} from src/engine.cpp")

    stage_serving(FULL_LM, FULL_REQUESTS, FULL_GENERATE, SLOTS, PAGE,
                  meter, expect_flash=True)
    stage_kernels(
        flash_fwd=[((1, 2048, 16, 128), jnp.float32),      # serving
                   ((1, 16384, 8, 128), jnp.bfloat16)],    # bench
        flash_bwd_shape=(1, 2048, 16, 128),
        train_kw=FULL_LM, train_batch=2, train_accum=2, train_seq=2048,
        checksum_bytes=1 << 20)
    if dev["count"] >= 4:
        stage_mesh(jax.devices()[:4], FULL_LM, lm_seq=512)
    else:
        log(f"mesh stage: not run ({dev['count']} device)")

    log(f"total {time.monotonic() - t0:.1f}s wall")
    report_cache_effect(cache_dir, meter)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
