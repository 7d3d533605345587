"""On the chip, at ``kimi-k2.7-code``'s published widths: the latent
decode kernel against its plain form, the routed experts' grouped
product against the cost rule (time follows the rows present and the
experts touched, not the buffer), and a two-layer cut (the dense layer
and one expert layer) through prefill, insert and paged steps against
the plain reference and its int8 control.

    chiprun -- python3 benchmarks/tests/chip_kimi.py [seed]

Prints one JSON line a check and appends them to
``chiprun_out/kimi_numerics.jsonl``.
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "tools"))
from procs import ROOT, record  # noqa: E402

sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.harness import spec  # noqa: E402
from brpc_tpu.models import moe  # noqa: E402
from brpc_tpu.models import transformer_lm as T  # noqa: E402
from brpc_tpu.ops import paged_attention  # noqa: E402


def timed(fn, *args, n=20):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def latent_kernel(seed: int) -> None:
    """64 slots x 64 heads against up to 1,800 live rows each."""
    r = np.random.default_rng(seed)
    slots, heads, kl, rope, page, pps = 64, 64, 512, 64, 16, 128
    pages = 7169
    ql = jnp.asarray(r.normal(size=(slots, heads, kl)).astype(np.float32))
    qr = jnp.asarray(r.normal(size=(slots, heads, rope)).astype(np.float32))
    pc = np.zeros((pages, page, 640), np.float32)
    pc[..., :576] = r.normal(size=(pages, page, 576))
    pc = jnp.asarray(pc)
    bt = jnp.asarray((1 + r.permutation(pages - 1)[:slots * 112])
                     .reshape(slots, 112).astype(np.int32))
    bt = jnp.pad(bt, ((0, 0), (0, pps - 112)))
    pos = jnp.asarray(r.integers(0, 1792, (slots,)).astype(np.int32))
    scale = 192 ** -0.5 * 1.4159 ** 2
    got = paged_attention.mla_decode_attention(ql, qr, pc, bt, pos, scale)
    with jax.default_matmul_precision("highest"):
        want = paged_attention.mla_reference(ql, qr, pc, bt, pos, scale)
    live = int(np.asarray(pos).sum()) + slots
    secs = timed(lambda: paged_attention.mla_decode_attention(
        ql, qr, pc, bt, pos, scale))
    record("kimi_numerics", {
        "check": "mla_decode_attention", "seed": seed,
        "max_abs_err": float(jnp.abs(got - want).max()),
        "out_std": float(want.std()), "live_rows": live,
        "seconds": secs, "gb_s_over_live_rows": live * 2304 / secs / 1e9})


def grouped_product(seed: int) -> None:
    """The expert layer at the published widths, 12 of 384 held: the
    time of a call as the rows' choices vary."""
    ecfg = moe.ExpertConfig(dim=7168, hidden=2048, routed=384, held=(0, 12),
                            top_k=8, route_scale=2.827, shared=1)
    # matrices in bfloat16 as served, the correction bias float32
    p = jax.jit(lambda k: {
        n: w if n == "bias" else w.astype(jnp.bfloat16)
        for n, w in moe.init_served(k, ecfg).items()})(
        jax.random.key(seed, impl="rbg"))
    r = np.random.default_rng(seed)
    serve = jax.jit(lambda p, t, live: moe.serve(p, t, ecfg, live))
    for rows in (64, 1024):
        t = jnp.asarray(r.normal(size=(rows, 7168)).astype(np.float32))
        for name, bias in (("as_routed", p["bias"]),
                           ("none_held", p["bias"].at[:12].set(-9.0)),
                           ("all_on_8_held", p["bias"].at[:8].set(9.0))):
            q = {**p, "bias": bias}
            live = jnp.ones((rows,), bool)
            _out, counts = serve(q, t, live)
            record("kimi_numerics", {
                "check": "expert_layer", "rows": rows, "routing": name,
                "counts": [int(c) for c in counts],
                "seconds": timed(serve, q, t, live, n=10)})


def two_layers(seed: int) -> None:
    cfg = dict(spec.load_json(os.path.join(
        spec.BENCH_DIR, "configs", "kimi-k2.7-code.json")))
    m = spec.load_module("models", cfg["model"])
    cfg["num_hidden_layers"] = 2
    params = m.make_params(cfg, seed)
    lm = T.LMConfig(remat=False, **m.lm_kwargs(cfg))
    page, slots = 16, 8
    prefill, step = T.make_paged_batch_decode(lm, page)
    insert = T.make_paged_io(lm, page)[2]
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, cfg["vocab_size"], (301,), dtype=np.int32)
    served = rng.integers(0, cfg["vocab_size"], (24,), dtype=np.int32)
    ctx = prompt[:-1]
    ids = np.zeros((512,), np.int32)
    ids[:len(ctx)] = ctx
    cache1, _ = jax.jit(prefill)(params, ids[None], jnp.int32(len(ctx)))
    cache = T.empty_paged_cache(lm, 257, slots, page)
    bt = np.zeros((slots, lm.max_seq // page), np.int32)
    bt[3, :64] = 1 + np.arange(64)
    cache = jax.jit(insert)(cache, jnp.asarray(bt[3]), cache1, jnp.int32(3))
    cache["len"] = cache["len"].at[3].set(len(ctx))
    active = np.zeros((slots,), bool)
    active[3] = True
    stepj = jax.jit(step, donate_argnums=(1,))
    got = []
    for tok in np.concatenate([prompt[-1:], served[:-1]]):
        toks = np.zeros((slots,), np.int32)
        toks[3] = tok
        cache, logits, _counts = stepj(params, cache, jnp.asarray(bt),
                                       jnp.asarray(toks), jnp.asarray(active))
        got.append(np.asarray(logits[3]))
    got = np.stack(got)
    del cache
    want = m.Reference(cfg, params).served_logits(prompt, served)
    ctl = m.Reference(cfg, params, int8=True).served_logits(prompt, served)
    std = want.std(axis=-1)
    record("kimi_numerics", {
        "check": "two_layers", "seed": seed,
        "served_gap_std_max": float((np.abs(got - want).max(axis=-1)
                                     / std).max()),
        "served_gap_std_mean": float((np.abs(got - want).max(axis=-1)
                                      / std).mean()),
        "int8_gap_std_max": float((np.abs(ctl - want).max(axis=-1)
                                   / std).max()),
        "int8_gap_std_mean": float((np.abs(ctl - want).max(axis=-1)
                                    / std).mean())})


def main(argv) -> int:
    seed = int(argv[0]) if argv else 1
    if jax.default_backend() != "tpu":
        raise SystemExit("this needs the chip")
    latent_kernel(seed)
    grouped_product(seed)
    two_layers(seed)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
