"""The serving programs' one attention layer (ISSUE 29): ``_qkv`` ->
rows scattered through a block table -> attention over the gathered
pages -> ``_attn_out``, as the chunk slice runs it
(``transformer_lm._paged_span_layer``), held to the TRAINING
forward (``make_forward``, which shares none of it) position by
position, at widths from one token (a step) over a few to a whole
chunk."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from brpc_tpu.models import transformer_lm as T

PAGE, SEQ, SLOTS = 8, 16, 2


@pytest.mark.parametrize("width", [1, 4, 16])
def test_span_layer_matches_forward_teacher_forced(width):
    """Two slots fed the same 16 positions in spans of ``width``: the
    logits of every position are the full forward's for the identical
    prefix (bf16 matmul tolerance, as
    ``test_decode_matches_forward_teacher_forced``)."""
    cfg = T.LMConfig(vocab=64, dim=32, heads=4, depth=2, max_seq=32,
                     remat=False)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (SLOTS, SEQ), 0,
                             cfg.vocab, jnp.int32)
    want = np.asarray(jax.jit(T.make_forward(cfg))(params, ids))
    pps = cfg.max_seq // PAGE
    # slot s holds pages 1 + s, 3 + s, ...: interleaved, none the
    # garbage page
    bt = jnp.asarray(1 + np.arange(SLOTS)[:, None]
                     + SLOTS * np.arange(pps)[None, :], jnp.int32)
    cache = T.empty_paged_cache(cfg, SLOTS * pps + 1, SLOTS, PAGE)

    @jax.jit
    def span(cache, start, tokens):
        cache = dict(cache)
        pos = jnp.broadcast_to(start + jnp.arange(width), (SLOTS, width))
        page_idx = bt[jnp.arange(SLOTS)[:, None], pos // PAGE]
        x = params["embed"][tokens]
        for i in range(cfg.depth):
            x, cache[f"pk{i}"], cache[f"pv{i}"] = T._paged_span_layer(
                cfg, params[f"blk{i}"], x, cache[f"pk{i}"],
                cache[f"pv{i}"], bt, page_idx, pos % PAGE, pos)
        return cache, T._logits(cfg, params, x)

    for start in range(0, SEQ, width):
        cache, got = span(cache, jnp.int32(start),
                          ids[:, start:start + width])
        np.testing.assert_allclose(
            np.asarray(got), want[:, start:start + width],
            rtol=2e-2, atol=2e-2)
