"""A layer schedule with state-space layers beside attention layers
(``LMConfig.mixers``), at toy widths with the structure of the
benchmark's ``jamba2-3b``: a 4-layer schedule with one attention
layer, 5 query heads on 1 key/value head, no rotary, ``d_state`` 16,
``d_conv`` 4, a gated FFN, a tied table, a final norm.

The yardstick is ``_plain_forward``: the whole sequence at once, one
layer at a time, full causal softmax, the recurrence as a Python loop
over time; no cache, no pages, no slots, no bucket.
"""

import struct
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from brpc_tpu.models import transformer_lm as T
from brpc_tpu.ops import paged_attention, quant, selective_scan
from brpc_tpu.streaming import StreamOptions

PAGE = 8


def _cfg(**kw):
    base = dict(vocab=97, dim=40, heads=5, kv_heads=1, depth=4,
                max_seq=64, remat=False, rope=False, ffn="gated_silu",
                ffn_dim=96, tie_embed=True, final_norm=True,
                mixers=("ssm", "attn", "ssm", "ssm"), ssm_dt_rank=6)
    base.update(kw)
    return T.LMConfig(**base)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return cfg, T.init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture
def f32_matmuls(monkeypatch):
    """Every weight matmul of the serving path goes through
    ``quant.qmatmul`` (bf16 operands, a bf16 result); in float32 the
    paged path and the plain forward differ by summation order alone,
    which is what lets a tolerance catch a state kept in bf16."""
    monkeypatch.setattr(quant, "qmatmul", lambda x, w: x @ w)


def _plain_forward(cfg, params, ids):
    """Logits ``(len(ids), vocab)`` of one sequence."""
    mm = quant.qmatmul
    norm = T._rmsnorm
    s = len(ids)
    x = params["embed"][jnp.asarray(ids)]
    hd, g = cfg.head_dim, cfg.heads // cfg.kv_heads
    for i in range(cfg.depth):
        bp = params[f"blk{i}"]
        h = norm(x, bp["ln1"])
        if cfg.mixers[i] == "attn":
            q, k, v = T._split_qkv(cfg, mm(h, bp["wqkv"]))
            q = q.reshape(s, cfg.heads, hd)
            k = jnp.repeat(k.reshape(s, cfg.kv_heads, hd), g, axis=1)
            v = jnp.repeat(v.reshape(s, cfg.kv_heads, hd), g, axis=1)
            sc = jnp.einsum("qhd,khd->hqk", q, k) / hd ** 0.5
            causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
            p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), -1)
            att = jnp.einsum("hqk,khd->qhd", p, v).reshape(s, -1)
            x = x + mm(att, bp["wo"])
        else:
            di, n, r = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_dt_rank
            u, z = jnp.split(mm(h, bp["in_proj"]), 2, axis=-1)
            up = jnp.pad(u, ((cfg.ssm_conv - 1, 0), (0, 0)))
            u = jax.nn.silu(sum(bp["conv_w"][j] * up[j:j + s]
                                for j in range(cfg.ssm_conv))
                            + bp["conv_b"])
            dt, b, c = jnp.split(mm(u, bp["x_proj"]), [r, r + n], -1)
            dt = jax.nn.softplus(mm(norm(dt, bp["dt_norm"]),
                                    bp["dt_proj"]) + bp["dt_bias"])
            b, c = norm(b, bp["b_norm"]), norm(c, bp["c_norm"])
            a = -jnp.exp(bp["a_log"])                       # (n, di)
            st = jnp.zeros((n, di), jnp.float32)
            ys = []
            for t in range(s):
                st = jnp.exp(dt[t][None] * a) * st \
                    + (dt[t] * u[t])[None] * b[t][:, None]
                ys.append(jnp.sum(st * c[t][:, None], axis=0))
            y = jnp.stack(ys) + bp["d"] * u
            x = x + mm(y * jax.nn.silu(z), bp["out_proj"])
        hh = norm(x, bp["ln2"])
        gate, up = jnp.split(mm(hh, bp["w1"]), 2, axis=-1)
        x = x + mm(jax.nn.silu(gate) * up, bp["w2"])
    return np.asarray(mm(norm(x, params["norm_f"]), params["embed"].T))


def _seq(n, seed=0):
    return np.random.default_rng(seed).integers(0, 97, size=(n,)) \
        .astype(np.int32)


class _Paged:
    """Prefill, insert and paged steps of one session in ``slot``."""

    def __init__(self, cfg, params, slots=3, pages=40):
        self.cfg, self.params, self.slots = cfg, params, slots
        prefill, step = T.make_paged_batch_decode(cfg, PAGE)
        self.prefill, self.step = jax.jit(prefill), jax.jit(step)
        self.insert = jax.jit(T.make_paged_io(cfg, PAGE)[2])
        self.cache = T.empty_paged_cache(cfg, pages, slots, PAGE)
        self.bt = np.zeros((slots, cfg.max_seq // PAGE), np.int32)

    def admit(self, slot, ctx, first_page):
        bucket = 1
        while bucket < max(len(ctx), 1):
            bucket <<= 1
        ids = np.zeros((bucket,), np.int32)
        ids[:len(ctx)] = ctx
        cache1, logits = self.prefill(self.params, ids[None],
                                      jnp.int32(len(ctx)))
        self.bt[slot] = first_page + np.arange(self.bt.shape[1])
        self.cache = self.insert(self.cache, jnp.asarray(self.bt[slot]),
                                 cache1, jnp.int32(slot))
        self.cache["len"] = self.cache["len"].at[slot].set(len(ctx))
        return np.asarray(logits[0])

    def feed(self, tokens: dict):
        tok = np.zeros((self.slots,), np.int32)
        act = np.zeros((self.slots,), bool)
        for slot, t in tokens.items():
            tok[slot], act[slot] = t, True
        self.cache, logits = self.step(
            self.params, self.cache, jnp.asarray(self.bt),
            jnp.asarray(tok), jnp.asarray(act))
        return np.asarray(logits)


def _worst_gap(cfg, params, seq, n_ctx, after_step=None):
    """The largest difference, in units of the position's logit
    standard deviation, between prefill + paged steps and the plain
    full forward, over the prefill's logits and every step's."""
    want = _plain_forward(cfg, params, seq)
    run = _Paged(cfg, params)
    got = [run.admit(1, seq[:n_ctx], first_page=3)]
    for j in range(n_ctx, len(seq)):
        got.append(run.feed({1: seq[j]})[1])
        if after_step is not None:
            after_step(run)
    rows = want[max(n_ctx - 1, 0):]
    got = np.stack(got)[(0 if n_ctx else 1):]
    return float(np.max(np.abs(got - rows).max(axis=-1) / rows.std(axis=-1)))


# float32 matmuls, float32 state: the two paths differ by summation
# order only (readings: 2.6e-6 to 4.2e-6 over the six cases).  A state
# pool rounded to bf16 after each step reads 6e-3 to 2.3e-2 (three
# seeds): 1e-4 lies between, 24 x over the one and 60 x under the other.
F32_TOL = 1e-4
# the served precision: bf16 operands AND a bf16 result of every
# matmul, at toy widths (readings 0.072 to 0.080); it catches a wrong
# formula, not a lower precision
BF16_TOL = 0.25


@pytest.mark.parametrize("n_ctx", [0, 1, 5, 13, 16, 17],
                         ids=lambda n: f"ctx{n}")
def test_prefill_then_paged_steps_match_plain_forward(model, f32_matmuls,
                                                      n_ctx):
    """Prompt lengths on, under and over their power-of-two bucket (13
    in 16, 17 in 32, 16 in 16), the empty context and a single token:
    the state inserted is the state AT THE TRUE LENGTH."""
    cfg, params = model
    assert _worst_gap(cfg, params, _seq(24, n_ctx), n_ctx) < F32_TOL


def test_bf16_state_would_fail_the_tolerance(model, f32_matmuls):
    """The control of the tolerance above: the same run with the state
    pool rounded to bf16 after each step lies outside it."""
    cfg, params = model

    def round_state(run):
        for k in run.cache:
            if k.startswith("sh"):
                run.cache[k] = run.cache[k].astype(jnp.bfloat16) \
                    .astype(jnp.float32)

    assert _worst_gap(cfg, params, _seq(24, 5), 13,
                      after_step=round_state) > 10 * F32_TOL


def test_served_precision_matches_plain_forward(model):
    cfg, params = model
    assert _worst_gap(cfg, params, _seq(24, 7), 13) < BF16_TOL


def test_inactive_slot_keeps_its_state_and_reused_slot_starts_fresh(
        model, f32_matmuls):
    cfg, params = model
    run = _Paged(cfg, params)
    a, b = _seq(20, 1), _seq(20, 2)
    run.admit(0, a[:9], first_page=1)
    run.admit(2, b[:6], first_page=20)
    held = {k: np.asarray(v[2]) for k, v in run.cache.items()
            if k[:2] in ("sh", "sc")}
    for j in range(9, 14):                      # slot 2 sits these out
        run.feed({0: a[j]})
    for k, v in held.items():
        assert np.array_equal(np.asarray(run.cache[k][2]), v), k
    assert int(run.cache["len"][2]) == 6
    # slot 0 is given to another session: it starts from ITS state
    want = _plain_forward(cfg, params, b)
    run.admit(0, b[:6], first_page=1)
    both = run.feed({0: b[6], 2: b[6]})
    for slot in (0, 2):
        gap = np.abs(both[slot] - want[6]).max() / want[6].std()
        assert gap < F32_TOL, (slot, gap)


# -- the kernels against the sequential recurrence ---------------------------

def _scan_inputs(batch, n_pos, di, n, seed=0):
    r = np.random.default_rng(seed)
    f = np.float32
    return (r.normal(size=(batch, n_pos, di)).astype(f),
            np.log1p(np.exp(r.normal(size=(batch, n_pos, di)) - 3)).astype(f),
            -np.exp(r.normal(size=(n, di)) * 0.5).astype(f),
            r.normal(size=(batch, n_pos, n)).astype(f),
            r.normal(size=(batch, n_pos, n)).astype(f),
            r.normal(size=(batch, n, di)).astype(f))


@pytest.mark.parametrize("batch,n_pos,di,n,lens", [
    (2, 256, 1024, 16, [200, 256]),     # two chunks, frozen past 200
    (1, 64, 256, 16, [33]),             # groups not a multiple of 8
    (3, 128, 2048, 4, [0, 1, 128]),     # nothing live; one position
], ids=["chunks", "short", "zero_len"])
def test_ssm_scan_kernel_matches_sequential(batch, n_pos, di, n, lens):
    u, dt, a, b, c, h0 = _scan_inputs(batch, n_pos, di, n)
    lens = jnp.asarray(lens, jnp.int32)
    y0, h_want = selective_scan.sequential(u, dt, a, b, c, h0, lens)

    def g(x):
        return jnp.asarray(x).reshape(*x.shape[:-1], di // 128, 128)

    y1, h_got = selective_scan.ssm_scan(g(u), g(dt), g(a), b, c, g(h0),
                                        lens, interpret=True)
    np.testing.assert_allclose(np.asarray(h_got).reshape(h_want.shape),
                               h_want, atol=2e-5)
    live = np.arange(n_pos)[None, :] < np.asarray(lens)[:, None]
    np.testing.assert_allclose(
        np.asarray(y1).reshape(y0.shape)[live], np.asarray(y0)[live],
        atol=2e-5)


def test_ssm_step_kernel_matches_sequential_and_skips_inactive():
    u, dt, a, b, c, h0 = _scan_inputs(8, 1, 1024, 16, seed=3)
    act = np.asarray([1, 0, 1, 1, 0, 0, 1, 1], bool)
    y0, h_want = selective_scan.sequential(
        u, dt, a, b, c, h0, jnp.asarray(act, jnp.int32))

    def g(x):
        return jnp.asarray(x).reshape(*x.shape[:-1], 8, 128)

    y1, h_got = selective_scan.ssm_step(
        g(u[:, 0]), g(dt[:, 0]), g(a), b[:, 0], c[:, 0], g(h0),
        jnp.asarray(act), interpret=True)
    h_got = np.asarray(h_got).reshape(h_want.shape)
    np.testing.assert_allclose(h_got, h_want, atol=2e-5)
    assert np.array_equal(h_got[~act], h0[~act])
    np.testing.assert_allclose(np.asarray(y1).reshape(8, -1)[act],
                               np.asarray(y0)[act, 0], atol=2e-5)


def _grouped_case(heads, kv_heads, hd, page, pps):
    r = np.random.default_rng(0)
    slots, pages = 4, 40
    q = r.normal(size=(slots, heads, hd)).astype(np.float32)
    pk, pv = (r.normal(size=(pages, page * kv_heads, hd)).astype(np.float32)
              for _ in range(2))
    bt = r.integers(1, pages, size=(slots, pps)).astype(np.int32)
    pos = np.asarray([0, page * pps - 1, 37 % (page * pps), page], np.int32)
    return q, pk, pv, bt, pos


@pytest.mark.parametrize("heads,kv_heads,hd,page,pps,scale", [
    (20, 1, 128, 16, 16, None), (4, 2, 128, 16, 8, None),
    (5, 1, 8, 4, 8, None), (32, 8, 128, 16, 8, None),
    (32, 8, 128, 16, 8, 0.0078125)],
    ids=["20on1", "4on2", "toy", "32on8", "32on8_scaled"])
def test_grouped_paged_attention_kernel(heads, kv_heads, hd, page, pps,
                                        scale):
    """The window kernel's walk at window 0 in its float32-accurate form
    (``granite-4.0-h-small``'s 32 on 8 with and without its softmax
    scale of 1/128): float32's accuracy, as the reference."""
    args = _grouped_case(heads, kv_heads, hd, page, pps)
    want = paged_attention.reference(*args, page, scale=scale)
    got = paged_attention.paged_decode_attention_grouped(
        *args, page, scale, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_the_bar_tells_the_bf16_form_from_the_float32_one():
    """The same inputs through the same walk with bfloat16 operands miss
    the bar the float32-accurate form meets: 2e-5 tells them apart."""
    args = _grouped_case(32, 8, 128, 16, 8)
    want = np.asarray(paged_attention.reference(*args, 16))
    exact, bf16 = (np.asarray(paged_attention._window_call(
        *map(jnp.asarray, args), page=16, window=0, exact=e,
        interpret=True)) for e in (True, False))
    assert np.abs(exact - want).max() < 2e-5
    assert np.abs(bf16 - want).max() > 20 * 2e-5


# -- through the batcher -------------------------------------------------------

class _FakeStream:
    def __init__(self):
        self.closed, self.close_reason, self.tokens = False, None, []
        self.id, self._native_tx = 0, None
        self.options = StreamOptions()

    def write(self, data):
        self.tokens.append(struct.unpack("<i", bytes(data))[0])
        return 0

    def close(self, reason=None):
        self.closed, self.close_reason = True, reason


def _serve(bat, prompt, max_new):
    st = _FakeStream()
    bat.join(st, prompt, max_new)
    deadline = time.monotonic() + 90.0
    while not st.closed and time.monotonic() < deadline:
        time.sleep(0.002)
    assert st.close_reason == "finished", st.close_reason
    return st.tokens


def _greedy(cfg, params, prompt, max_new):
    seq = list(prompt)
    for _ in range(max_new):
        seq.append(int(_plain_forward(cfg, params, np.asarray(seq))[-1]
                       .argmax()))
    return seq[len(prompt):]


def test_batcher_slot_reuse_prefix_declines_and_state_counters(
        model, f32_matmuls):
    """One slot, three sessions in turn: each is served what the plain
    forward decodes greedily (so the second and third started from
    THEIR state, not from what the slot's last session left, the empty
    context included); the identical second prompt is prefilled again,
    the prefix cache declining under a counted reason."""
    from brpc_tpu.models.lm_service import ContinuousBatcher
    cfg, params = model
    bat = ContinuousBatcher(cfg, params, slots=1, page=PAGE,
                            idle_linger_s=0.2)
    p1, p3 = _seq(19, 11), _seq(1, 12)
    want1 = _greedy(cfg, params, p1, 6)
    assert _serve(bat, p1, 6) == want1
    assert _serve(bat, p1, 6) == want1
    assert _serve(bat, p3, 5) == _greedy(cfg, params, p3, 5)
    st = bat.kv_stats()
    assert bat.prefills_run == 3
    assert st["prefix"]["declined_state"] == 3
    assert st["prefix"]["hits"] == st["prefix"]["nodes"] == 0
    state = st["state"]
    assert state["inserts"] == state["releases"] == 3
    assert state["slots"] == 1 and state["held"] == 0
    assert state["held_steps"] == state["slot_steps"] == st["steps"] == 17
    assert state["bytes"] == 3 * 4 * cfg.ssm_inner * (16 + 3)
    # the state insert has no phase of its own: it rides insert_dispatch
    # (the table is the process's: other tests' batchers count in it)
    assert st["phases"]["insert_dispatch"] >= 3


# -- one step in flight (ISSUE 28): the state pool under a step that ---------
# -- runs ahead of the host's reading of the last one -------------------------

def _join(bat, prompt, max_new, hang_up_after=None):
    st = _FakeStream()
    if hang_up_after is not None:
        plain = st.write

        def write(data):
            rc = plain(data)
            st.closed = len(st.tokens) >= hang_up_after
            return rc

        st.write = write
    bat.join(st, prompt, max_new)
    return st


def _closed(*streams):
    deadline = time.monotonic() + 90.0
    while not all(s.closed for s in streams) \
            and time.monotonic() < deadline:
        time.sleep(0.002)
    assert all(s.closed for s in streams), "a session never closed"


def test_batcher_sessions_joining_and_ending_mid_batch(model, f32_matmuls):
    """Five sessions of different ``max_new`` over two slots: sessions
    end while another decodes, the queued ones take over slots (and
    state blocks) that have just been let go, and every step but the
    first of a busy spell leaves before the last one's tokens are
    read: each session is served what the plain forward decodes."""
    from brpc_tpu.models.lm_service import ContinuousBatcher
    cfg, params = model
    bat = ContinuousBatcher(cfg, params, slots=2, page=PAGE,
                            idle_linger_s=0.2)
    asks = [(_seq(n, 30 + i), m) for i, (n, m) in enumerate(
        [(9, 6), (4, 1), (12, 3), (1, 2), (6, 5)])]
    streams = [_join(bat, p, m) for p, m in asks]
    _closed(*streams)
    for (p, m), st in zip(asks, streams):
        assert st.close_reason == "finished"
        assert st.tokens == _greedy(cfg, params, p, m), (len(p), m)
    look = bat.kv_stats()["lookahead"]
    assert look["ahead"] + look["sync"] == bat.steps_run()
    assert look["ahead"] > look["sync"] >= 1


def test_step_that_runs_ahead_leaves_an_ended_sessions_state_alone(
        model, f32_matmuls):
    """A ends at ``max_new`` = 4 while B decodes on: the steps queued
    while A's last tokens were still unread must not have moved A's
    slot.  Its state blocks and ``len`` are those of a session fed
    exactly its context and four tokens (the prompt's last and three of
    its own), one step at a time, and NOT those of one step more."""
    from brpc_tpu.models.lm_service import ContinuousBatcher
    cfg, params = model
    bat = ContinuousBatcher(cfg, params, slots=2, page=PAGE,
                            idle_linger_s=0.2)
    pa, pb = _seq(10, 41), _seq(6, 42)
    a, b = _join(bat, pa, 4), _join(bat, pb, 14)
    _closed(a, b)
    assert a.tokens == _greedy(cfg, params, pa, 4)
    assert b.tokens == _greedy(cfg, params, pb, 14)
    assert bat.kv_stats()["lookahead"]["ahead"] >= 10
    fed = list(pa) + a.tokens[:3]               # 9 of context + 4 steps
    assert int(bat._cache["len"][0]) == len(fed) == len(pa) - 1 + 4
    run = _Paged(cfg, params, slots=2)
    run.admit(0, np.asarray(fed[:9], np.int32), first_page=1)
    for t in fed[9:]:
        run.feed({0: t})
    keys = [k for k in run.cache if k[:2] in ("sh", "sc")]
    assert len(keys) == 2 * 3
    for k in keys:
        np.testing.assert_allclose(np.asarray(bat._cache[k][0]),
                                   np.asarray(run.cache[k][0]),
                                   rtol=1e-5, atol=1e-6, err_msg=k)
    run.feed({0: a.tokens[3]})                  # the step too many
    moved = max(float(np.abs(np.asarray(bat._cache[k][0])
                             - np.asarray(run.cache[k][0])).max())
                for k in keys)
    assert moved > 1e-3


def test_hung_up_sessions_step_in_flight_is_overwritten_by_the_next_join(
        model, f32_matmuls):
    """One slot.  A's client hangs up after two tokens, which the
    batcher finds at the next emit, with one more step of A's queued:
    that step moves the slot's state and writes a row of A's page.  B
    takes the slot in the next pass; its insert is queued after the
    step nobody reads, so B decodes from ITS state, and is never handed
    a token of A's."""
    from brpc_tpu.models.lm_service import ContinuousBatcher
    cfg, params = model
    bat = ContinuousBatcher(cfg, params, slots=1, page=PAGE,
                            idle_linger_s=0.2)
    pa, pb = _seq(13, 51), _seq(9, 52)
    a = _join(bat, pa, 20, hang_up_after=2)
    b = _join(bat, pb, 7)
    _closed(b)
    assert a.tokens == _greedy(cfg, params, pa, 2)
    assert a.close_reason is None
    assert b.tokens == _greedy(cfg, params, pb, 7)
    assert bat.steps_run() == 2 + 2 + 7         # read, found out, unread
    state = bat.kv_stats()["state"]
    assert state["inserts"] == state["releases"] == 2


def test_info_shows_the_schedule_and_the_state_pool(model):
    import json

    from brpc_tpu.models.lm_service import LMService
    cfg, params = model
    svc = LMService(cfg=cfg, params=params, page=PAGE,
                    decode_slots=2)
    info = json.loads(svc.Info(None, b""))
    assert info["mixers"] == "sass" and info["kv_heads"] == 1
    # the pool by kind of state layer (a "kda" layer's beside these:
    # tests/test_linear_experts.py)
    kinds = {"ssm": {"layers": 3, "slot_bytes": T.state_slot_bytes(cfg)}}
    assert info["state_pool"] == {"slots": 2, "kinds": kinds,
                                  "bytes": 2 * T.state_slot_bytes(cfg)}
    assert b"sass" in svc.model_fingerprint()
    # off the TPU every layer runs the plain form
    assert info["attention"] == {"forms": {"reference": 1}}


def test_kv_stats_names_the_decode_form_of_each_attention_layer(model):
    """On the TPU grouped heads with no window run the ring walk's
    float32-accurate form, whole heads the first block's VPU kernel; off
    it every layer runs the plain gather."""
    from unittest import mock

    from brpc_tpu.models.lm_service import ContinuousBatcher
    from brpc_tpu.ops import device_ops
    cfg, params = model
    bat = ContinuousBatcher(cfg, params, slots=1, page=PAGE)
    assert bat.kv_stats()["attn"]["forms"] == {"reference": 1}
    with mock.patch.object(device_ops, "_on_tpu", lambda: True):
        assert bat.kv_stats()["attn"]["forms"] == {"ring_f32": 1}
        assert T.attention_forms(_cfg(kv_heads=5)) == {"vpu": 1}
        assert T.attention_forms(_cfg(mixers=("attn",) * 4)) == \
            {"ring_f32": 4}


# -- what declines, by name ----------------------------------------------------

def _batcher(**kw):
    from brpc_tpu.models.lm_service import ContinuousBatcher
    cfg = _cfg()
    return ContinuousBatcher(cfg, {}, **{"page": PAGE, **kw})


def _generate_declines():
    from brpc_tpu.client.controller import Controller
    from brpc_tpu.models.lm_service import LMService, pack_generate_request
    cfg = _cfg()
    svc = LMService(cfg=cfg, params=T.init_params(jax.random.PRNGKey(1),
                                                  cfg))
    cntl = Controller()
    assert svc.Generate(cntl, pack_generate_request(
        np.zeros((1, 4), np.int32), 2)) is None
    assert "first block only" in cntl.error_text
    raise T.UnsupportedBlock(cntl.error_text)


DECLINES = {
    "training": lambda: T.make_forward(_cfg()),
    "contiguous_decode": lambda: T.make_decode(_cfg()),
    "kv_export_specs": lambda: T.kv_page_specs(_cfg()),
    "kv_export": lambda: T.export_decode_cache(_cfg(), {}),
    "scan_layers": lambda: T.init_params(jax.random.PRNGKey(0),
                                         _cfg(scan_layers=True)),
    "host_spill": lambda: T.make_paged_io(_cfg(), PAGE)[0]({}, None),
    "host_resume": lambda: T.make_paged_io(_cfg(), PAGE)[1]({}, None, None),
    "catch_up": lambda: T.make_paged_io(_cfg(), PAGE, chunk=8)[3](),
    "batcher_park": lambda: _batcher(host_slots=4),
    "batcher_chunked": lambda: _batcher(prefill_chunk_tokens=16),
    "kv_import": lambda: _batcher().join_imported(None, 0, 4, 2, {}),
    "generate": _generate_declines,
    # grouped heads alone (no state layer) decline the same way
    "grouped_heads_only": lambda: T.make_decode(
        _cfg(mixers=None, depth=2)),
}


@pytest.mark.parametrize("path", sorted(DECLINES))
def test_unported_paths_decline_by_name(path):
    with pytest.raises(T.UnsupportedBlock):
        DECLINES[path]()


def test_default_config_is_the_first_block_and_serves_as_before():
    """Today's defaults are today's block: the paged engine streams
    what ``generate`` (the contiguous factories, untouched) decodes."""
    from brpc_tpu.models.lm_service import ContinuousBatcher
    cfg = T.LMConfig(vocab=64, dim=32, heads=4, depth=2, max_seq=64,
                     remat=False)
    assert cfg.plain_block() and cfg.mixers == ("attn", "attn")
    assert cfg.kv_heads == 4 and not cfg.has_state
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    assert sorted(params["blk0"]) == ["ln1", "ln2", "w1", "w2", "wo", "wqkv"]
    prompt = _seq(21, 5) % 64
    want = np.asarray(T.generate(params, cfg, prompt[None, :], 6))[0]
    bat = ContinuousBatcher(cfg, params, slots=2, page=16,
                            idle_linger_s=0.2)
    assert _serve(bat, prompt, 6) == want.tolist()
    assert bat.kv_stats()["state"]["bytes"] == 0      # no state layer
    assert bat.kv_stats()["prefix"]["declined_state"] == 0
