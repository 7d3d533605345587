"""The paged decode-attention kernel (ISSUE 26), in interpret mode.

- THE KERNEL against ``paged_attention.reference`` (the plain gather of
  the block table the step had, and has off the TPU) and against a
  per-slot float32 softmax over the live positions only, written here:
  page boundaries, ragged batches, an inactive slot, aliased pages, 16
  and 32 heads, and a garbage page full of NaN that nothing past
  ``pos`` may bring into the output;
- NO ``max_seq`` COPY: the lowered kernel holds no array of
  ``slots x max_seq x heads x hd`` elements (the reference does);
- THE COUNTER: ``kv_stats()["attn"]`` grows by the pages of live
  positions and by the block table's width, a step and an active slot.
"""
import re
import struct
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from brpc_tpu.ops import paged_attention as pa

PAGE = 16


def _normal(rng, shape):
    return rng.standard_normal(shape).astype(np.float32)


def _case(pos, heads=4, hd=8, pps=8, alias=None, nan_page0=False,
          wave_pages=3, seed=0):
    """Pools, queries and a block table whose dead entries are page 0.
    ``pos[b] < 0`` makes slot ``b`` inactive: a table of zeros, and
    position 0 attended (what the step passes for such a slot).
    ``alias=(a, b)`` gives slot ``b`` the pages of slot ``a``."""
    rng = np.random.default_rng(seed)
    slots = len(pos)
    num_pages = slots * pps + 1
    pk = _normal(rng, (num_pages, PAGE, heads, hd))
    pv = _normal(rng, (num_pages, PAGE, heads, hd))
    if nan_page0:
        pk[0] = np.nan
        pv[0] = np.nan
    q = _normal(rng, (slots, heads, hd))
    free = list(rng.permutation(np.arange(1, num_pages)))
    bt = np.zeros((slots, pps), np.int32)
    att_pos = np.zeros((slots,), np.int32)
    for b, p in enumerate(pos):
        if p < 0:
            continue
        att_pos[b] = p
        for i in range(p // PAGE + 1):
            bt[b, i] = free.pop()
    if alias is not None:
        src, dst = alias
        n = min(att_pos[src], att_pos[dst]) // PAGE + 1
        bt[dst, :n] = bt[src, :n]
    return dict(q=q, pk=pk, pv=pv, bt=bt, pos=att_pos,
                wave_pages=wave_pages)


def _softmax_per_slot(c):
    """Float32, one slot and one head at a time, live positions only."""
    q, pk, pv, bt, pos = c["q"], c["pk"], c["pv"], c["bt"], c["pos"]
    slots, heads, hd = q.shape
    out = np.zeros((slots, heads, hd), np.float32)
    for b in range(slots):
        n = int(pos[b]) + 1
        pages = bt[b, :(n - 1) // PAGE + 1]
        k = pk[pages].reshape(-1, heads, hd)[:n]
        v = pv[pages].reshape(-1, heads, hd)[:n]
        for h in range(heads):
            s = (k[:, h] @ q[b, h]) / np.float32(hd ** 0.5)
            w = np.exp(s - s.max())
            out[b, h] = (w / w.sum()) @ v[:, h]
    return out


def _run_kernel(c, monkeypatch):
    heads, hd = c["q"].shape[1:]
    # several waves a slot even at toy sizes, and a last wave that is
    # partly dead
    monkeypatch.setattr(pa, "_WAVE_BYTES",
                        c["wave_pages"] * PAGE * heads * hd * 4)
    assert pa.pages_per_wave(PAGE, heads, hd, c["bt"].shape[1]) \
        == c["wave_pages"]
    return np.asarray(pa.paged_decode_attention(
        *(jnp.asarray(c[k]) for k in ("q", "pk", "pv", "bt", "pos")),
        interpret=True))


MAX_SEQ = 8 * PAGE

CASES = {
    "pos-0": dict(pos=[0, 0]),
    "pos-15": dict(pos=[15, 15]),
    "pos-16": dict(pos=[16, 16]),
    "pos-17": dict(pos=[17, 17]),
    "pos-last": dict(pos=[MAX_SEQ - 1, MAX_SEQ - 1]),
    "ragged": dict(pos=[0, 47, 48, MAX_SEQ - 1, 5, 100]),
    "one-page-waves": dict(pos=[33, 70, 2], wave_pages=1),
    "whole-table-wave": dict(pos=[33, 70, 2], wave_pages=8),
    "inactive-slot": dict(pos=[40, -1, 9, -1]),
    "aliased-pages": dict(pos=[77, 70, 3], alias=(0, 1)),
    "heads-16": dict(pos=[20, 63, 0], heads=16, pps=4),
    "heads-32": dict(pos=[20, 63, 0], heads=32, pps=4, wave_pages=2),
    "nan-garbage-page": dict(pos=[0, 31, 32, 90], nan_page0=True),
}


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_matches_plain_attention(name, monkeypatch):
    kw = CASES[name]
    c = _case(**kw)
    out = _run_kernel(c, monkeypatch)
    assert out.shape == c["q"].shape and np.isfinite(out).all()
    # float32 throughout: only the order of the sums differs
    np.testing.assert_allclose(out, _softmax_per_slot(c), atol=2e-5,
                               rtol=0)
    if not kw.get("nan_page0"):
        # with NaN in the garbage page the plain gather is itself NaN
        # (a zero weight times NaN): the kernel never fetches that page
        ref = np.asarray(pa.reference(
            *(jnp.asarray(c[k]) for k in ("q", "pk", "pv", "bt", "pos"))))
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=0)


def test_step_attention_is_the_reference_off_the_tpu():
    c = _case(pos=[5, 40])
    args = [jnp.asarray(c[k]) for k in ("q", "pk", "pv", "bt", "pos")]
    np.testing.assert_array_equal(np.asarray(pa.attention(*args)),
                                  np.asarray(pa.reference(*args)))


def _array_sizes(text):
    sizes = set()
    for dims in re.findall(r"tensor<((?:\d+x)+)[a-z]+\d+>", text):
        sizes.add(int(np.prod([int(d) for d in dims.split("x") if d])))
    return sizes


def test_lowered_kernel_holds_no_max_seq_copy():
    slots, heads, hd, pps = 3, 4, 8, 8
    c = _case(pos=[5, 40, 127], heads=heads, hd=hd, pps=pps)
    args = [jnp.asarray(c[k]) for k in ("q", "pk", "pv", "bt", "pos")]
    whole = slots * pps * PAGE * heads * hd
    assert c["pk"].size != whole               # the pool is not that size
    kernel = jax.jit(
        lambda *a: pa.paged_decode_attention(*a, interpret=True))
    assert whole not in _array_sizes(kernel.lower(*args).as_text())
    assert whole in _array_sizes(
        jax.jit(pa.reference).lower(*args).as_text())


# -- the kernel through the chip's own compiler, at the cells' widths --------

@pytest.fixture(scope="module")
def one_chip():
    """A described v5e (nothing attached, nothing runs): made inside
    the fixture, never at import, so every xdist worker collects the
    same tests and only the one given this file loads the library."""
    import os
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("heads", [16, 32])
def test_kernel_compiles_for_the_v5e_at_real_widths(heads, one_chip):
    """Mosaic takes the kernel at the benchmark's shapes (8 slots,
    1,025 pages of 16 x heads x 128, a 128-wide table), the pool goes
    in as it lies (no copy, no temporary), and nothing is run."""
    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = arg((1025, PAGE, heads, 128), jnp.float32)
    compiled = jax.jit(
        lambda *a: pa.paged_decode_attention(*a, interpret=False)
    ).lower(arg((8, heads, 128), jnp.float32), pool, pool,
            arg((8, 128), jnp.int32), arg((8,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes == 0



@pytest.mark.parametrize("rows", [512, 8192])
@pytest.mark.parametrize("k,n", [(7168, 4096), (2048, 7168)])
def test_expert_gmm_compiles_for_the_v5e_at_real_widths(rows, k, n, one_chip):
    """Mosaic takes the grouped product's kernel at ``kimi-k2.7-code``'s
    shapes (12 held experts, the step's buffer and the longest
    prefill's) under the name a device trace is read by, the weights go
    in as they lie (no copy), and nothing is run."""
    from brpc_tpu.ops.expert_gmm import expert_gmm

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda *a: expert_gmm(*a, interpret=False)
    ).lower(arg((rows, k), jnp.bfloat16), arg((12, k, n), jnp.bfloat16),
            arg((12,), jnp.int32)).compile()
    assert re.search(rf"%expert_gmm[.\d]* = f32\[{rows},{n}\]\S* custom-call",
                     compiled.as_text())
    assert "ragged-dot" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20)

@pytest.mark.parametrize("tokens,rows,dim", [
    (64, 512, 7168), (128, 1024, 2304), (16, 128, 4096),
    (1024, 8192, 7168), (1024, 8192, 2304), (1024, 8192, 4096)])
def test_expert_combine_compiles_for_the_v5e_at_real_widths(
        tokens, rows, dim, one_chip):
    """Mosaic takes the combine's kernel at the three expert
    configurations' shapes (a step's rows and a 1,024-row bucket's)
    under the name a device trace is read by; the buffer goes in as it
    lies (no copy, no temporary), and nothing is run."""
    from brpc_tpu.ops.expert_combine import expert_combine

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda *a: expert_combine(*a, interpret=False)
    ).lower(arg((rows, dim), jnp.float32), arg((rows,), jnp.int32),
            arg((tokens, 8), jnp.float32), arg((), jnp.int32)).compile()
    assert re.search(
        rf"%expert_combine[.\d]* = f32\[{tokens},{dim}\]\S* custom-call",
        compiled.as_text())
    assert "scatter" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def test_mla_kernel_compiles_for_the_v5e_at_real_widths(one_chip):
    """Mosaic takes the latent kernel at ``kimi-k2.7-code.codegen``'s
    shapes (64 slots of 64 x (512 + 64) queries, a pool of 7,169 pages
    of 16 float32 rows padded to 640 lanes, a 128-wide table) as ONE
    custom call under the name a device trace is read by, the pool goes
    in as it lies (no copy), and nothing is run."""
    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda *a: pa.mla_decode_attention(*a, 0.1, interpret=False)
    ).lower(arg((64, 64, 512), jnp.float32), arg((64, 64, 64), jnp.float32),
            arg((7169, PAGE, 640), jnp.float32), arg((64, 128), jnp.int32),
            arg((64,), jnp.int32)).compile()
    calls = re.findall(r"%(mla_decode_attention[.\w]*) = (\S+) custom-call",
                       compiled.as_text())
    assert len(calls) == 1 and calls[0][1].startswith("f32[64,64,512]"), calls
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20)


def test_span_kernel_over_whole_heads_compiles_for_the_v5e(one_chip):
    """Mosaic takes ``span_flash_attention`` over a WHOLE-HEAD pool at
    ``ouro-2.6b.batch``'s shapes (a span of 256 rows of 16 heads x 128
    against a 128-wide block table of a pool of 4 x 196 pages: the
    grouped layout with a group of one) as one custom call under the
    name a device trace is read by; nothing is run."""
    from brpc_tpu.ops import span_attention

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = arg((4 * 196, PAGE, 16, 128), jnp.float32)
    compiled = jax.jit(
        lambda q, pk, pv, ids, start: span_attention.span_flash_attention(
            q, pk, pv, ids, start, 0, PAGE, interpret=False)
    ).lower(arg((256, 16, 128), jnp.float32), pool, pool,
            arg((128,), jnp.int32), arg((), jnp.int32)).compile()
    text = compiled.as_text()
    calls = re.findall(r"%(span_flash_attention[.\w]*) = (\S+) custom-call",
                       text)
    assert len(calls) == 1 and calls[0][1].startswith("f32[16,256,128]"), \
        calls
    # the keys are read where they lie: no operand of the table's 2,048
    # keys, gathered, cast or transposed (the pools arrive as bitcasts)
    assert "bf16[128,16,16,128]" not in text
    assert "bf16[16,2048,128]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20)


def test_span_kernel_over_a_window_compiles_for_the_v5e(one_chip):
    """``span_flash_attention`` at a window layer of
    ``command-a-plus.longdoc`` (a span of 1,024 rows of 128 query heads
    on 8 key/value heads, a window of 4,096, the 322 entries the span
    can reach in a pool of 4,193 pages): sixteen query blocks a span,
    so the keys are gathered ONCE, 322 pages padded to eleven key
    blocks; nothing is run."""
    from brpc_tpu.ops import span_attention

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert not span_attention.in_place(1024, 16)
    assert span_attention.table_reach(816, 1024, PAGE, 4096) == 322
    pool = arg((4193, PAGE * 8, 128), jnp.float32)
    compiled = jax.jit(
        lambda q, pk, pv, ids, q0, k0: span_attention.span_flash_attention(
            q, pk, pv, ids, q0, k0, PAGE, 4096, interpret=False)
    ).lower(arg((1024, 128, 128), jnp.float32), pool, pool,
            arg((322,), jnp.int32), arg((), jnp.int32),
            arg((), jnp.int32)).compile()
    text = compiled.as_text()
    calls = re.findall(r"%(span_flash_attention[.\w]*) = (\S+) custom-call",
                       text)
    assert len(calls) == 1 and calls[0][1].startswith("f32[8,16384,128]"), \
        calls
    assert "bf16[8,5632,128]" in text


def _window_kernel_compiles(one_chip, slots, heads, kvh, window, pages):
    """The window schedule's decode kernel through Mosaic at a cell's
    shapes (an 816-wide table over either page class's pool): ONE
    custom call under the name a device trace is read by, the pools in
    as they lie (no temporary, no array of ``max_seq`` keys a slot),
    and nothing is run."""
    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = arg((pages, PAGE * kvh, 128), jnp.float32)
    lowered = jax.jit(
        lambda *a: pa.window_decode_attention(*a, PAGE, window,
                                              interpret=False)
    ).lower(arg((slots, heads, 128), jnp.float32), pool, pool,
            arg((slots, 816), jnp.int32), arg((slots,), jnp.int32))
    assert slots * 816 * PAGE * kvh * 128 not in _array_sizes(
        lowered.as_text())
    compiled = lowered.compile()
    name = "window_decode_attention" if window else "paged_decode_attention"
    calls = re.findall(rf"%({name}[.\w]*) = (\S+) custom-call",
                       compiled.as_text())
    assert len(calls) == 1 and calls[0][1].startswith(
        f"f32[{slots},{heads},128]"), calls
    assert compiled.memory_analysis().temp_size_in_bytes == 0


@pytest.mark.parametrize("window,pages", [(4096, 8321), (0, 15361)])
def test_window_kernel_at_a_group_of_seven_compiles_for_the_v5e(
        window, pages, one_chip):
    """``smallthinker-21b.longdoc``'s shapes: 32 slots of 28 query heads
    on 4 key/value heads of 128 (7-row slices of the queries, ``(7, 1)``
    and ``(7, 128)`` accumulators, no whole sublane tile), pages of 32
    KB a pool, 16 a wave."""
    _window_kernel_compiles(one_chip, 32, 28, 4, window, pages)


@pytest.mark.parametrize("window,pages", [(4096, 4193), (0, 9601)])
def test_window_kernel_at_a_group_of_sixteen_compiles_for_the_v5e(
        window, pages, one_chip):
    """``command-a-plus.longdoc``'s shapes: 16 slots of 128 query heads
    on 8 key/value heads of 128, pages of 64 KB a pool, 8 a wave."""
    _window_kernel_compiles(one_chip, 16, 128, 8, window, pages)


@pytest.mark.parametrize("window,pages,entries", [(4096, 8321, 322),
                                                  (0, 15361, 816)])
def test_span_kernel_at_a_group_of_seven_compiles_for_the_v5e(
        window, pages, entries, one_chip):
    """``span_flash_attention`` at ``smallthinker-21b.longdoc``'s shapes
    (a span of 1,024 rows of 28 query heads on 4 key/value heads: 7,168
    rows a key/value head, so the keys are gathered once and walked in
    sixteen query blocks of 64 tokens = 448 rows), a window layer's 322
    reachable entries and a global layer's whole table; nothing is
    run."""
    from brpc_tpu.ops import span_attention

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert not span_attention.in_place(1024, 7)
    assert span_attention.table_reach(816, 1024, PAGE, window) == entries
    pool = arg((pages, PAGE * 4, 128), jnp.float32)
    compiled = jax.jit(
        lambda q, pk, pv, ids, q0, k0: span_attention.span_flash_attention(
            q, pk, pv, ids, q0, k0, PAGE, window, interpret=False)
    ).lower(arg((1024, 28, 128), jnp.float32), pool, pool,
            arg((entries,), jnp.int32), arg((), jnp.int32),
            arg((), jnp.int32)).compile()
    calls = re.findall(r"%(span_flash_attention[.\w]*) = (\S+) custom-call",
                       compiled.as_text())
    assert len(calls) == 1 and calls[0][1].startswith("f32[4,7168,128]"), \
        calls


@pytest.mark.parametrize("rows", [192, 6144])
@pytest.mark.parametrize("k,n", [(2560, 1536), (768, 2560)])
def test_expert_gmm_over_64_small_experts_compiles_for_the_v5e(
        rows, k, n, one_chip):
    """The grouped product at ``smallthinker-21b``'s shapes: 64 groups,
    none absent, the step's ``32 x 6`` rows and a span's ``1,024 x 6``;
    an expert's ``w1`` (7.9 MB) goes by in two column blocks, its ``w2``
    (3.9 MB) whole."""
    from brpc_tpu.ops import expert_gmm as eg

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert eg._blocks(k, n, 2, eg._BLOCK_BYTES) == (
        (2560, 768) if k == 2560 else (768, 2560))
    compiled = jax.jit(
        lambda *a: eg.expert_gmm(*a, interpret=False)
    ).lower(arg((rows, k), jnp.bfloat16), arg((64, k, n), jnp.bfloat16),
            arg((64,), jnp.int32)).compile()
    # (the step's 192 rows are two 128-row tiles: the call writes 256)
    padded = -(-rows // 128) * 128
    assert re.search(
        rf"%expert_gmm[.\d]* = f32\[{padded},{n}\]\S* custom-call",
        compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20)


# -- the counter ------------------------------------------------------------

class _Stream:
    def __init__(self):
        from brpc_tpu.streaming import StreamOptions
        self.closed = False
        self.tokens = []
        self.id = 0
        self._native_tx = None
        self.options = StreamOptions()

    def write(self, data):
        self.tokens.append(struct.unpack("<i", bytes(data))[0])
        return 0

    def close(self, reason=None):
        self.closed = True


def _serve(bat, n_prompt, max_new, seed):
    st = _Stream()
    prompt = np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (n_prompt,), 0, 64, jnp.int32))
    bat.join(st, prompt, max_new)
    deadline = time.monotonic() + 120.0
    while not st.closed and time.monotonic() < deadline:
        time.sleep(0.002)
    assert st.closed and len(st.tokens) == max_new


def test_kv_stats_counts_the_pages_a_step_attends_over():
    from brpc_tpu.models.lm_service import ContinuousBatcher
    from brpc_tpu.models.transformer_lm import LMConfig, init_params
    cfg = LMConfig(vocab=64, dim=32, heads=4, depth=2, max_seq=64,
                   remat=False)
    params = init_params(jax.random.PRNGKey(0), cfg)
    bat = ContinuousBatcher(cfg, params, slots=2, page=PAGE)
    assert bat.kv_stats()["attn"] == {"pages_read": 0, "pages_table": 0}
    pps = cfg.max_seq // PAGE
    read = table = 0
    for seed, (n_prompt, max_new) in enumerate([(14, 6), (31, 5), (3, 2)]):
        _serve(bat, n_prompt, max_new, seed)
        # token j of a session is made by a step at position
        # len(prompt) - 1 + j
        read += sum((n_prompt - 1 + j) // PAGE + 1 for j in range(max_new))
        table += max_new * pps
        assert bat.kv_stats()["attn"] == {"pages_read": read,
                                          "pages_table": table}
    assert table == bat.steps_run() * pps
