"""On the chip, at ``smallthinker-21b``'s published widths.

    chiprun -- python3 benchmarks/tests/chip_smallthinker.py numerics [seed] [layers]
    chiprun -- python3 benchmarks/tests/chip_smallthinker.py metrics <seconds> <seed>

``numerics``: the two attention kernels at a group of SEVEN (28 query
heads on 4 key/value heads of 128) against their plain forms: the
decode kernel over 32 slots (a window layer's walk from the window's
first page, a global layer's whole walk), a span's flash attention (a
window layer's span from deep in a context, a global layer's first and
a deep one), each with its device time from the host's clock and what
that is of the pages' bytes; then a cut of ``layers`` layers (4 where
none is given: one period) at the cell's widths through the two page
classes: a prompt of 4,301 tokens (past the window) filled in spans,
then 24 paged steps, the logits against the plain reference and its
int8 control.  One JSON line a check, appended to
``chiprun_out/smallthinker_numerics.jsonl``; exits non-zero where a
kernel lies further from its plain form than bfloat16 operands explain.

``metrics``: one traced run of ``smallthinker-21b.longdoc`` through the
runner's own ``run_window`` with the trace reduced whole, judged as the
runner judges it; prints the result's line, the step and the fill by
operation, and what the scope ``moe_route`` (the router, the top-k, the
sort, the visit lists) took of the step: the compiled step's
instructions whose ``op_name`` lies under that scope, by their device
time in the trace.  Writes ``chiprun_out/smallthinker_metrics.json``.
"""
import functools
import json
import os
import re
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "tools"))
from procs import ROOT, record  # noqa: E402

sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.harness import spec  # noqa: E402

CELL = "smallthinker-21b.longdoc"
CONFIG = os.path.join(spec.BENCH_DIR, "configs", "smallthinker-21b.json")
TOL = 0.03          # of a unit-variance value; bf16 operands read ~5e-3


def _config(toy: bool = False):
    path = os.path.join(HERE, "toy_smallthinker", "config.json") if toy \
        else CONFIG
    cfg = dict(spec.load_json(path))
    return cfg, spec.load_module("models", cfg["model"])


def timed(fn, *args, n=20):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


class Shapes:
    """The cell's widths, from its file."""

    def __init__(self, cfg):
        svc = cfg["service"]
        self.heads, self.kvh = cfg["num_attention_heads"], \
            cfg["num_key_value_heads"]
        self.hd, self.page = cfg["head_dim"], svc["page"]
        self.window, self.span = cfg["sliding_window_size"], svc["fill_span"]
        self.pps, self.slots = svc["max_seq"] // svc["page"], \
            svc["decode_slots"]
        self.pool = {True: svc["window_pages"], False: svc["kv_pages"]}

    def pools(self, r, window: bool):
        shape = (self.pool[window], self.page * self.kvh, self.hd)
        return (jnp.asarray(r.normal(size=shape).astype(np.float32)),
                jnp.asarray(r.normal(size=shape).astype(np.float32)))


def plain_decode(s: Shapes, q, pk, pv, bt, pos, window: int):
    """``paged_attention.reference`` without its copy of every key/value
    head for each query head of the group."""
    b, n = q.shape[0], bt.shape[1] * s.page
    k, v = (p[bt].reshape(b, n, s.kvh, s.hd) for p in (pk, pv))
    sc = jnp.einsum("bhgd,bkhd->bhgk",
                    q.reshape(b, s.kvh, s.heads // s.kvh, s.hd),
                    k) / s.hd ** 0.5
    j = jnp.arange(n)[None, :]
    live = j <= pos[:, None]
    if window:
        live = live & (j > pos[:, None] - window)
    p = jax.nn.softmax(jnp.where(live[:, None, None], sc, -1e30), axis=-1)
    return jnp.einsum("bhgk,bkhd->bhgd", p, v).reshape(b, s.heads, s.hd)


def decode_kernel(s: Shapes, seed: int, window: int) -> bool:
    """32 slots at the traffic's contexts (and the edges)."""
    from brpc_tpu.ops import paged_attention

    r = np.random.default_rng(seed)
    pk, pv = s.pools(r, bool(window))
    pages, top = pk.shape[0], s.pps * s.page
    edges = [0, s.page - 1, s.page, min(s.window, top) - 1,
             min(s.window, top - 1), top - 1]
    slots = max(s.slots, len(edges))
    bt = jnp.asarray((1 + r.integers(0, pages - 1, (slots * s.pps,)))
                     .reshape(slots, s.pps).astype(np.int32))
    q = jnp.asarray(r.normal(size=(slots, s.heads, s.hd))
                    .astype(np.float32))
    pos = jnp.asarray(np.concatenate(
        [edges, r.integers(top // 5, top, (slots - len(edges),))])
        .astype(np.int32))
    got = paged_attention.window_decode_attention(q, pk, pv, bt, pos,
                                                  s.page, window)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(functools.partial(plain_decode, s),
                       static_argnums=(5,))(q, pk, pv, bt, pos, window)
    err = float(jnp.abs(got - want).max())
    secs = timed(lambda: paged_attention.window_decode_attention(
        q, pk, pv, bt, pos, s.page, window))
    live = np.asarray(pos) + 1
    rows = int((np.minimum(live, window) if window else live).sum())
    nbytes = rows * 2 * s.kvh * s.hd * 4
    record("smallthinker_numerics", {
        "check": "window_decode_attention" if window
        else "paged_decode_attention (grouped, bf16)", "group": s.heads
        // s.kvh, "window": window, "max_abs_err": err, "ms": secs * 1e3,
        "rows_read": rows, "gb_s": nbytes / secs / 1e9,
        "finite": bool(jnp.isfinite(got).all())})
    return err < TOL


def group_of_eight(s: Shapes, seed: int) -> None:
    """The window kernel over the SAME pools, table and positions with
    8 query heads a key/value head where the cell has 7: what the 7-row
    slices cost beside whole sublane tiles (a row more of products a
    head, nothing more of pages)."""
    from brpc_tpu.ops import paged_attention

    r = np.random.default_rng(seed)
    pk, pv = s.pools(r, True)
    bt = jnp.asarray((1 + r.integers(0, pk.shape[0] - 1,
                                     (s.slots * s.pps,)))
                     .reshape(s.slots, s.pps).astype(np.int32))
    pos = jnp.asarray(r.integers(s.pps * s.page // 5, s.pps * s.page,
                                 (s.slots,)).astype(np.int32))
    ms = {}
    for g in (s.heads // s.kvh, 8):
        q = jnp.asarray(r.normal(size=(s.slots, g * s.kvh, s.hd))
                        .astype(np.float32))
        ms[g] = 1e3 * timed(lambda: paged_attention.window_decode_attention(
            q, pk, pv, bt, pos, s.page, s.window), n=50)
    record("smallthinker_numerics", {
        "check": "window kernel, group of 7 against 8", "ms": ms})


def span_kernel(s: Shapes, seed: int, window: int, start: int) -> bool:
    """One span of the cell's width at ``start``."""
    from brpc_tpu.ops import span_attention

    r = np.random.default_rng(seed + 1)
    w = s.span
    q = jnp.asarray(r.normal(size=(w, s.heads, s.hd)).astype(np.float32))
    pk, pv = s.pools(r, bool(window))
    row = np.zeros((s.pps,), np.int32)
    n_live = (start + w) // s.page
    row[:n_live] = 1 + r.integers(0, pk.shape[0] - 1, (n_live,))
    if window:
        reach = span_attention.table_reach(s.pps, w, s.page, window)
        p0 = min(max((start - window + 1) // s.page, 0), s.pps - reach)
        ids = jnp.asarray(row[p0:p0 + reach])
    else:
        p0, ids = 0, jnp.asarray(row)
    args = (q, pk, pv, ids, jnp.int32(start), jnp.int32(p0 * s.page))
    fn = jax.jit(lambda *a: span_attention.span_flash_attention(
        *a, s.page, window))
    got = fn(*args)
    # the plain form a block of rows at a time: the first and the last
    plain = jax.jit(lambda q, pk, pv, ids, q0, k0: span_attention.reference(
        q, pk, pv, ids, q0, k0, s.page, window))
    err, blk = 0.0, min(128, w)
    with jax.default_matmul_precision("highest"):
        for lo in (0, w - blk):
            want = plain(q[lo:lo + blk], pk, pv, ids,
                         jnp.int32(start + lo), args[5])
            err = max(err, float(jnp.abs(got[lo:lo + blk] - want).max()))
    secs = timed(fn, *args, n=5)
    keys = (np.minimum(start + np.arange(w) + 1, window) if window
            else start + np.arange(w) + 1).sum()
    record("smallthinker_numerics", {
        "check": "span_flash_attention", "group": s.heads // s.kvh,
        "in_place": span_attention.in_place(w, s.heads // s.kvh),
        "window": window, "start": start, "max_abs_err": err,
        "ms": secs * 1e3,
        "tflops": 4.0 * s.heads * s.hd * float(keys) / secs / 1e12,
        "finite": bool(jnp.isfinite(got).all())})
    return err < TOL


def cut_of_layers(cfg, m, seed: int, layers: int, n_ctx: int,
                  n_new: int) -> bool:
    """``layers`` layers through the two page classes (the tier-1
    test's own driver: spans, then steps, the window class's row moved
    and checked before every program) against the reference and the
    control."""
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    from test_window_experts import _Paged

    from brpc_tpu.models import transformer_lm as T

    cfg = dict(cfg, num_hidden_layers=layers)
    params = m.make_params(cfg, seed)
    lm = T.LMConfig(remat=False, **m.lm_kwargs(cfg))
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, cfg["vocab_size"], (n_ctx + 1,), dtype=np.int32)
    served = rng.integers(0, cfg["vocab_size"], (n_new,), dtype=np.int32)
    t0 = time.perf_counter()
    run = _Paged(lm, params, prompt[:-1])
    got, touched = [], []
    for tok in np.concatenate([prompt[-1:], served[:-1]]):
        logits, counts = run.feed(tok)
        got.append(logits)
        touched.append(int(counts[1]))
    secs = time.perf_counter() - t0
    got = np.stack(got)
    del run
    want = m.Reference(cfg, params).served_logits(prompt, served)
    ctl = m.Reference(cfg, params, int8=True).served_logits(prompt, served)
    std = want.std(axis=-1)

    def gaps(x):
        g = np.abs(x - want).max(axis=-1) / std
        return float(g.max()), float(g.mean())

    def below_best(tokens):
        rows = np.arange(len(tokens))
        return float(((want.max(axis=-1) - want[rows, tokens]) / std).mean())

    line = {
        "check": "cut_of_layers", "seed": seed, "layers": layers,
        "context": n_ctx, "steps": n_new,
        "served_gap_std_max": gaps(got)[0],
        "served_gap_std_mean": gaps(got)[1],
        "served_below_best_mean": below_best(got.argmax(axis=-1)),
        "int8_gap_std_max": gaps(ctl)[0], "int8_gap_std_mean": gaps(ctl)[1],
        "int8_below_best_mean": below_best(ctl.argmax(axis=-1)),
        "experts_touched_a_step": touched[:4],
        "seconds_with_compiles": secs,
        "finite": bool(np.isfinite(got).all())}
    record("smallthinker_numerics", line)
    # one row a step through `layers` expert layers: 6 experts each
    return line["finite"] and touched[0] == layers * cfg[
        "moe_num_active_primary_experts"] \
        and line["served_gap_std_mean"] < line["int8_gap_std_mean"]


def numerics(seed: int, layers: int, toy: bool) -> int:
    cfg, m = _config(toy)
    s = Shapes(cfg)
    deep = (s.pps * s.page - s.span) // s.span * s.span
    group_of_eight(s, seed)
    ok = [decode_kernel(s, seed, s.window), decode_kernel(s, seed, 0),
          span_kernel(s, seed, s.window, deep),
          span_kernel(s, seed, s.window, 0), span_kernel(s, seed, 0, 0),
          span_kernel(s, seed, 0, deep),
          cut_of_layers(cfg, m, seed, layers,
                        s.window + 205 if not toy else 69, 24)]
    return 0 if all(ok) else 1


# -- metrics ------------------------------------------------------------------


def _by_operation(red, program: str, top: int = 30):
    ops = []
    for key, secs in red["device_ops"]:
        m = re.match(r"(\S+): (.*) x(\d+)$", key)
        if m.group(1) == program:
            ops.append([m.group(2), int(m.group(3)), secs])
    total = sum(o[2] for o in ops)
    execs = len(red["programs"].get(program, []))
    print(f"{program}: {execs} executions, {total:.4f} s of "
          f"{red['busy_s']:.4f} s busy in {red['window_s']:.4f} s")
    for op, calls, secs in ops[:top]:
        print(f"  {secs:9.5f} s {100 * secs / total:5.1f}%  x{calls:<6d} "
              f"{1e6 * secs / calls:8.1f} us  {op}")
    return ops, execs, total


def instruction_seconds(path: str, program: str) -> dict:
    """Device seconds of every instruction (``%name``) that ran inside
    an execution of ``program``, over the device planes of a trace."""
    from jax.profiler import ProfileData

    from benchmarks.harness import xplane

    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if not xplane.DEVICE_PLANE.match(plane.name):
            continue
        mods, ops = xplane._plane_events(plane)
        spans = [(a, b) for a, b, name in mods if name == program]
        i = 0
        for a, b, name in ops:
            while i < len(spans) and spans[i][1] < a:
                i += 1
            if i < len(spans) and spans[i][0] <= a:
                m = re.match(r"%?([\w.\-]+)\s*=", name)
                if m:
                    out[m.group(1)] = out.get(m.group(1), 0.0) + (b - a) / 1e9
    return out


def scoped_instructions(text: str, scope: str) -> set:
    """Names of a compiled module's instructions whose ``op_name``
    lies under the named scope."""
    return {m.group(1) for m in re.finditer(
        r"^\s*(?:ROOT )?%?([\w.\-]+) = [^\n]*op_name=\"([^\"]*)\"", text,
        flags=re.M) if f"/{scope}/" in m.group(2) + "/"}


def compiled_step_text(cfg, m) -> str:
    """The step as the batcher jits it, compiled here for this chip at
    the cell's shapes (nothing runs)."""
    from brpc_tpu.models import transformer_lm as T

    lm = T.LMConfig(remat=False, **m.lm_kwargs(cfg))
    svc = cfg["service"]
    bf = lambda t: jax.tree_util.tree_map(                # noqa: E731
        lambda a: jax.ShapeDtypeStruct(
            a.shape, jnp.bfloat16 if a.ndim > 1 else a.dtype), t)
    params = bf(jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), lm)))
    cache = jax.eval_shape(lambda: T.empty_paged_cache(
        lm, svc["kv_pages"], svc["decode_slots"], svc["page"]))
    s, pps = svc["decode_slots"], svc["max_seq"] // svc["page"]
    i32 = lambda *d: jax.ShapeDtypeStruct(d, jnp.int32)   # noqa: E731
    step = T.make_paged_batch_decode(lm, svc["page"])[1]
    return jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, i32(2, s, pps), i32(s),
        jax.ShapeDtypeStruct((s,), jnp.bool_)).compile().as_text()


def metrics(seconds: float, seed: int) -> int:
    from benchmarks import run as bench_run
    from benchmarks.harness import compare, xplane

    cfg, m = _config()
    by_instr = {}

    reduce_trace = xplane.reduce_trace        # (the runner's is this one)

    def reduce_whole(path, **_kw):
        by_instr.update(instruction_seconds(path, "jit_step"))
        return reduce_trace(path, top=1 << 30)

    bench_run.xplane.reduce_trace = reduce_whole
    win = bench_run.run_window(spec.Cell(CELL), seed, seconds, trace=True)
    run, red = win.run, win.run.trace["reduced"]
    step_ops, execs, total = _by_operation(red, "jit_step")
    fill_ops, fills, fill_total = _by_operation(red, "jit_fill", top=20)
    line = win.judged(compare.compare(win.reference(), win.sample))
    line["breakdown"] = {"idle_gaps": line["breakdown"]["idle_gaps"]}
    # the routing's scope, by the compiled step's own metadata
    names = scoped_instructions(compiled_step_text(cfg, m), "moe_route")
    seen = {n: s for n, s in by_instr.items() if n in names}
    step_secs = sum(by_instr.values())
    route = {"instructions_in_scope": len(names),
             "of_them_in_the_trace": len(seen),
             "seconds": sum(seen.values()), "step_seconds": step_secs,
             "share_of_step_pct": 100.0 * sum(seen.values())
             / max(step_secs, 1e-12),
             "ms_a_step": 1e3 * sum(seen.values()) / max(execs, 1),
             "largest": sorted(seen.items(), key=lambda kv: -kv[1])[:12]}
    c0, c1 = run.c0["kv"], run.c1["kv"]
    # a counter's growth over the window; what did not move as it stands
    def grew(sec):
        return {k: v - c0[sec][k] if c0[sec].get(k, v) != v else v
                for k, v in c1[sec].items()}

    tokens = [t for r in run.requests for t in r.tokens]
    repeats = sum(int(a == b) for r in run.requests
                  for a, b in zip(r.tokens, r.tokens[1:]))
    out = {"seed": seed, "seconds": seconds, "line": line,
           "moe_route": route, "step_ops": step_ops[:80],
           "fill_ops": fill_ops[:40], "step_executions": execs,
           "step_seconds": total, "fill_executions": fills,
           "fill_seconds": fill_total,
           "moe": grew("moe"),
           "fill": grew("fill"), "alloc": c1["alloc"],
           "window": c1["window"],
           "repeat_share_pct": 100.0 * repeats / max(len(tokens), 1),
           "distinct_tokens": len(set(tokens)), "tokens": len(tokens),
           "programs": {p: [len(d), sum(d)]
                        for p, d in red["programs"].items()}}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "smallthinker_metrics.json"), "w") as f:
        json.dump(out, f)
    for k in ("moe_route", "moe", "fill", "alloc", "repeat_share_pct",
              "distinct_tokens", "tokens", "programs"):
        print(json.dumps({k: out[k]}))
    print(json.dumps(line), flush=True)
    return 0 if line["correct"] else 1


def main(argv) -> int:
    toy = bool(os.environ.get("CHIP_SMALLTHINKER_TOY"))   # a rehearsal
    if jax.default_backend() != "tpu" and not toy:
        raise SystemExit("this needs the chip")
    if argv and argv[0] == "metrics":
        return metrics(float(argv[1]), int(argv[2]))
    return numerics(int(argv[1]) if len(argv) > 1 else 1,
                    int(argv[2]) if len(argv) > 2 else 4, toy)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
