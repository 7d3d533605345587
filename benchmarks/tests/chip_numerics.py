"""The new kernels and the hybrid block against their plain forms, on
the chip, at the published widths.

    chiprun -- python3 benchmarks/tests/chip_numerics.py [<layers>]

1. ``ssm_scan``, ``ssm_step`` and the grouped paged-attention kernel
   against ``sequential`` / ``reference`` on random inputs;
2. the program (prefill, insert, paged steps through slot state) at
   ``layers`` layers of ``configs/jamba2-3b.json`` (attention at layer
   1 of every 4) against ``models/jamba.py Reference``: the worst and
   the mean difference of the logits in units of a position's logit
   standard deviation, as served (bf16 matmuls) and with the program's
   matmuls in float32 at ``highest`` (what is left is then the
   program's arithmetic, not its precision).
Appends to ``chiprun_out/numerics.jsonl``.
"""
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def kernels():
    import jax
    import jax.numpy as jnp

    from brpc_tpu.ops import paged_attention as pa
    from brpc_tpu.ops import selective_scan as ss
    out = {}
    r = np.random.default_rng(0)
    f = np.float32
    for n_pos, lens in ((1024, 1000), (512, 300), (32, 17)):
        di, n = 5120, 16
        u = r.normal(size=(1, n_pos, di)).astype(f)
        dt = np.log1p(np.exp(r.normal(size=(1, n_pos, di)) - 4)).astype(f)
        a = -np.broadcast_to(np.arange(1, n + 1, dtype=f)[:, None], (n, di))
        b, c = (r.normal(size=(1, n_pos, n)).astype(f) for _ in range(2))
        h0 = r.normal(size=(1, n, di)).astype(f)
        ln = jnp.asarray([lens], jnp.int32)
        with jax.default_matmul_precision("highest"):
            y0, hw = jax.jit(ss.sequential)(u, dt, a, b, c, h0, ln)
        g = lambda x: jnp.asarray(x).reshape(*x.shape[:-1], di // 128, 128)
        y1, hg = ss.ssm_scan(g(u), g(dt), g(a), b, c, g(h0), ln)
        out[f"scan{n_pos}"] = [
            float(np.abs(np.asarray(y1).reshape(y0.shape)[:, :lens]
                         - np.asarray(y0)[:, :lens]).max()),
            float(np.abs(np.asarray(hg).reshape(hw.shape) - hw).max())]
    u = r.normal(size=(8, 1, 5120)).astype(f)
    dt = np.log1p(np.exp(r.normal(size=(8, 1, 5120)) - 4)).astype(f)
    a = -np.broadcast_to(np.arange(1, 17, dtype=f)[:, None], (16, 5120))
    b, c = (r.normal(size=(8, 1, 16)).astype(f) for _ in range(2))
    h0 = r.normal(size=(8, 16, 5120)).astype(f)
    act = np.asarray([1, 0, 1, 1, 0, 0, 1, 1], bool)
    y0, hw = jax.jit(ss.sequential)(u, dt, a, b, c, h0,
                                    jnp.asarray(act, jnp.int32))
    g = lambda x: jnp.asarray(x).reshape(*x.shape[:-1], 40, 128)
    y1, hg = ss.ssm_step(g(u[:, 0]), g(dt[:, 0]), g(a), b[:, 0], c[:, 0],
                         g(h0), jnp.asarray(act))
    out["step"] = [float(np.abs(np.asarray(y1).reshape(8, -1)[act]
                                - np.asarray(y0)[act, 0]).max()),
                   float(np.abs(np.asarray(hg).reshape(hw.shape) - hw).max())]
    q = r.normal(size=(8, 20, 128)).astype(f)
    pk, pv = (r.normal(size=(1025, 16, 128)).astype(f) for _ in range(2))
    bt = r.integers(1, 1025, size=(8, 128)).astype(np.int32)
    pos = np.asarray([0, 2047, 37, 16, 500, 1151, 15, 255], np.int32)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(pa.reference, static_argnums=5)(
            q, pk, pv, bt, pos, 16)
    got = pa.paged_decode_attention_grouped(q, pk, pv, bt, pos, 16)
    out["grouped_attention"] = float(np.abs(np.asarray(got)
                                            - np.asarray(want)).max())
    return out


def model(layers: int, seed: int = 3):
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import spec
    from brpc_tpu.models import transformer_lm as T
    from brpc_tpu.ops import quant

    cfg = dict(spec.load_json(os.path.join(spec.BENCH_DIR, "configs",
                                           "jamba2-3b.json")))
    cfg.update(num_hidden_layers=layers, attn_layer_period=4,
               attn_layer_offset=1)
    m = spec.load_module("models", cfg["model"])
    params = m.make_params(cfg, seed)
    lm = T.LMConfig(remat=False, **m.lm_kwargs(cfg))
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, cfg["vocab_size"], (301,), dtype=np.int32)
    served = rng.integers(0, cfg["vocab_size"], (48,), dtype=np.int32)
    want = m.Reference(cfg, params).served_logits(prompt, served)
    ctl = m.Reference(cfg, params, int8=True).served_logits(prompt, served)

    def gaps(got):
        d = np.abs(got - want) / want.std(axis=-1, keepdims=True)
        return [float(d.max()), float(d.mean())]

    def program():
        prefill, step = T.make_paged_batch_decode(lm, 16)
        insert = T.make_paged_io(lm, 16)[2]
        ctx = prompt[:-1]
        ids = np.zeros((512,), np.int32)
        ids[:len(ctx)] = ctx
        cache1, _ = jax.jit(prefill)(params, ids[None], jnp.int32(len(ctx)))
        cache = T.empty_paged_cache(lm, 129, 8, 16)
        bt = np.zeros((8, lm.max_seq // 16), np.int32)
        bt[5] = 1 + np.arange(bt.shape[1])
        cache = jax.jit(insert)(cache, jnp.asarray(bt[5]), cache1,
                                jnp.int32(5))
        cache["len"] = cache["len"].at[5].set(len(ctx))
        stepj = jax.jit(step)
        got = []
        tok = np.zeros((8,), np.int32)
        act = np.zeros((8,), bool)
        act[5] = True
        for t in np.concatenate([prompt[-1:], served[:-1]]):
            tok[5] = t
            cache, logits = stepj(params, cache, jnp.asarray(bt),
                                  jnp.asarray(tok), jnp.asarray(act))
            got.append(np.asarray(logits[5]))
        return np.stack(got)

    out = {"layers": layers, "served_bf16": gaps(program()),
           "int8_control": gaps(ctl)}
    real = quant.qmatmul
    quant.qmatmul = lambda x, w: jnp.matmul(x, w, precision="highest")
    try:
        out["program_float32"] = gaps(program())
    finally:
        quant.qmatmul = real
    return out


def main(argv) -> int:
    layers = int(argv[0]) if argv else 4
    res = {"kernels": kernels(), "model": model(layers)}
    print(json.dumps(res, indent=1), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "numerics.jsonl"), "a") as f:
        f.write(json.dumps(res) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
