"""Block-sparse Pallas paged-attention kernel tests — the CHUNKED
family covering decode (chunk of 1) and chunked suffix prefill
(kernels/paged_attention).

Evidence layers:

  * kernel (interpret mode) == ref.py oracle == contiguous attention,
    for decode AND for [rows, chunk] prefill tiles at arbitrary
    past_len — deterministically and as hypothesis properties over
    random past lengths, suffix lengths, chunk widths, block sizes,
    GQA group counts, and dead-row (all-trash table) masks — the
    deterministic sweeps run in the FAST tier so CPU CI always
    exercises the Pallas path in interpret mode;
  * backend dispatch: "auto" off-TPU resolves to ref, "pallas" off-TPU
    interprets, and model-level gqa/mla_decode_paged agree across
    backends;
  * model level: chunked paged prefill (split at arbitrary chunk
    boundaries) is token-identical to the contiguous full-sequence
    `prefill`;
  * engine/serving integration: decode AND prefill block tables are
    sliced to pow2 active widths (the block-sparse I/O win), chunked
    piggyback admission interleaves with decode and is token-for-token
    identical to whole-suffix admission, and serving with the kernel
    backend is token-for-token identical to the dense-gather backend.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.kernels.paged_attention import (
    paged_decode_gqa,
    paged_decode_gqa_ref,
    paged_decode_mla,
    paged_decode_mla_ref,
    paged_prefill_gqa,
    paged_prefill_gqa_ref,
    paged_prefill_mla,
    paged_prefill_mla_ref,
    resolve_backend,
)
from repro.models import attention as attn

GQA_ARCH = "granite-moe-1b-a400m"
MLA_ARCH = "deepseek-v2-236b"


def _layout(rng, b, nb):
    """Random injective tables over a pool of b*nb blocks (+1 trash)."""
    n_blocks = b * nb
    tables = rng.permutation(n_blocks).reshape(b, nb).astype(np.int32)
    return n_blocks, tables


def _gqa_arrays(rng, b, kv, g, hd, bs, nb, dead=None):
    n_blocks, tables = _layout(rng, b, nb)
    if dead is not None:
        tables[np.asarray(dead, bool)] = n_blocks  # all-trash rows
    q = jnp.asarray(rng.normal(size=(b, kv, g, hd)), jnp.float32)
    pool_k = jnp.asarray(rng.normal(size=(n_blocks + 1, bs, kv, hd)), jnp.float32)
    pool_v = jnp.asarray(rng.normal(size=(n_blocks + 1, bs, kv, hd)), jnp.float32)
    pos = jnp.asarray(rng.integers(0, bs * nb, size=b), jnp.int32)
    return q, pool_k, pool_v, jnp.asarray(tables), pos


def _contiguous_gqa(q, pool_k, pool_v, tables, pos):
    """Oracle via the model's chunked attention over the linearized
    layout (the pre-kernel dense-gather semantics)."""
    b, kv, g, hd = q.shape
    keys = attn.paged_gather(pool_k, tables)
    vals = attn.paged_gather(pool_v, tables)
    valid = jnp.arange(keys.shape[1])[None, :] <= pos[:, None]
    out = attn._grouped_attention(
        q.reshape(b, 1, kv * g, hd), keys, vals, valid=valid
    )
    return out.reshape(b, kv, g, hd)


def _check_gqa(rng, *, kv, g, bs, nb, b=3, hd=16, dead=None):
    q, pk, pv, tables, pos = _gqa_arrays(rng, b, kv, g, hd, bs, nb, dead)
    ref = paged_decode_gqa_ref(q, pk, pv, tables, pos)
    got = paged_decode_gqa(q, pk, pv, tables, pos, interpret=True)
    cont = _contiguous_gqa(q, pk, pv, tables, pos)
    live = np.ones(b, bool) if dead is None else ~np.asarray(dead, bool)
    for name, other in (("ref", ref), ("contiguous", cont)):
        np.testing.assert_allclose(
            np.asarray(got[live], np.float32), np.asarray(other[live], np.float32),
            rtol=2e-5, atol=2e-5, err_msg=f"kernel vs {name}",
        )
    assert bool(jnp.all(jnp.isfinite(got))), "dead rows must stay finite"


# ---------------------------------------------------------- fast parity
def test_kernel_matches_ref_and_contiguous_gqa():
    for seed, (kv, g) in enumerate([(1, 4), (2, 2), (4, 1)]):
        _check_gqa(np.random.default_rng(seed), kv=kv, g=g, bs=4, nb=4)


def test_kernel_matches_ref_mla():
    rng = np.random.default_rng(7)
    b, h, r, rd, bs, nb = 2, 4, 32, 8, 4, 3
    n_blocks, tables = _layout(rng, b, nb)
    ql = jnp.asarray(rng.normal(size=(b, h, r)), jnp.float32)
    qr = jnp.asarray(rng.normal(size=(b, h, rd)), jnp.float32)
    pc = jnp.asarray(rng.normal(size=(n_blocks + 1, bs, r)), jnp.float32)
    pr = jnp.asarray(rng.normal(size=(n_blocks + 1, bs, rd)), jnp.float32)
    pos = jnp.asarray([0, 9], jnp.int32)
    scale = (16 + 8) ** -0.5
    ref = paged_decode_mla_ref(ql, qr, pc, pr, tables, pos, scale=scale)
    got = paged_decode_mla(ql, qr, pc, pr, tables, pos, scale=scale,
                           interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_kernel_dead_rows_write_trash_and_leave_live_rows_exact():
    """Trash-block contract: an all-trash table row (dead decode slot)
    attends garbage but stays finite and does not perturb live rows."""
    _check_gqa(np.random.default_rng(3), kv=2, g=2, bs=4, nb=4,
               dead=[False, True, False])


def test_backend_dispatch_off_tpu():
    assert jax.default_backend() != "tpu", "CI runs these on CPU"
    assert resolve_backend("auto") == ("ref", False)
    assert resolve_backend("pallas") == ("pallas", True)
    assert resolve_backend("ref") == ("ref", False)
    with pytest.raises(AssertionError):
        resolve_backend("cuda")


# --------------------------------------------------- model-level parity
def test_model_gqa_decode_paged_backends_agree():
    cfg = reduce_for_smoke(get_config(GQA_ARCH))
    p = attn.init_gqa(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(5)
    b, bs, nb = 2, 4, 4
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    n_blocks, tables = _layout(rng, b, nb)
    x = jnp.asarray(rng.normal(size=(b, 1, cfg.d_model)), jnp.float32)
    pk = jnp.asarray(rng.normal(size=(n_blocks + 1, bs, kv, hd)), jnp.float32)
    pv = jnp.asarray(rng.normal(size=(n_blocks + 1, bs, kv, hd)), jnp.float32)
    pos = np.asarray([3, 11], np.int32)
    o_ref, k_ref, v_ref = attn.gqa_decode_paged(
        p, cfg, x, pk, pv, jnp.asarray(tables), pos, backend="ref"
    )
    o_pal, k_pal, v_pal = attn.gqa_decode_paged(
        p, cfg, x, pk, pv, jnp.asarray(tables), pos, backend="pallas"
    )
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(k_pal), np.asarray(k_ref))
    np.testing.assert_allclose(np.asarray(v_pal), np.asarray(v_ref))


def test_model_mla_decode_paged_backends_agree():
    cfg = reduce_for_smoke(get_config(MLA_ARCH))
    p = attn.init_mla(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(6)
    b, bs, nb = 2, 4, 3
    m = cfg.mla
    n_blocks, tables = _layout(rng, b, nb)
    x = jnp.asarray(rng.normal(size=(b, 1, cfg.d_model)), jnp.float32)
    pc = jnp.asarray(rng.normal(size=(n_blocks + 1, bs, m.kv_lora_rank)),
                     jnp.float32)
    pr = jnp.asarray(rng.normal(size=(n_blocks + 1, bs, m.qk_rope_head_dim)),
                     jnp.float32)
    pos = np.asarray([2, 10], np.int32)
    o_ref, _, _ = attn.mla_decode_paged(
        p, cfg, x, pc, pr, jnp.asarray(tables), pos, backend="ref"
    )
    o_pal, _, _ = attn.mla_decode_paged(
        p, cfg, x, pc, pr, jnp.asarray(tables), pos, backend="pallas"
    )
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------ chunked prefill tile
def _chunked_contiguous_oracle(q, pool_k, pool_v, tables, past, lens):
    """Per-row contiguous-prefill oracle: linearize the pool, slice each
    row's live context, and run the model's chunked causal attention at
    the row's query offset — the pre-paged semantics the chunked kernel
    must reproduce."""
    b, c, kv, g, hd = q.shape
    keys = attn.paged_gather(pool_k, tables)
    vals = attn.paged_gather(pool_v, tables)
    out = np.zeros((b, c, kv, g, hd), np.float32)
    for row in range(b):
        p, n = int(past[row]), int(lens[row])
        if n == 0:
            continue
        o = attn._grouped_attention(
            q[row, :n].reshape(1, n, kv * g, hd),
            keys[row:row + 1, :p + n], vals[row:row + 1, :p + n],
            causal=True, q_offset=p,
        )
        out[row, :n] = np.asarray(o, np.float32).reshape(n, kv, g, hd)
    return out


def _check_chunked_gqa(rng, *, kv, g, bs, nb, c, b=3, hd=16, past=None,
                       lens=None, dead=None):
    n_blocks, tables = _layout(rng, b, nb)
    if dead is not None:
        tables[np.asarray(dead, bool)] = n_blocks  # all-trash rows
    q = jnp.asarray(rng.normal(size=(b, c, kv, g, hd)), jnp.float32)
    pool_k = jnp.asarray(
        rng.normal(size=(n_blocks + 1, bs, kv, hd)), jnp.float32
    )
    pool_v = jnp.asarray(
        rng.normal(size=(n_blocks + 1, bs, kv, hd)), jnp.float32
    )
    if past is None:
        past = rng.integers(0, nb * bs - c + 1, size=b)
    past = np.asarray(past, np.int32)
    lens = np.asarray(
        rng.integers(1, c + 1, size=b) if lens is None else lens, np.int32
    )
    if dead is not None:
        lens[np.asarray(dead, bool)] = 0  # all-pad rows
    got = paged_prefill_gqa(
        q, pool_k, pool_v, jnp.asarray(tables), jnp.asarray(past),
        jnp.asarray(lens), interpret=True,
    )
    ref = paged_prefill_gqa_ref(
        q, pool_k, pool_v, jnp.asarray(tables), jnp.asarray(past)
    )
    cont = _chunked_contiguous_oracle(q, pool_k, pool_v, tables, past, lens)
    got_np, ref_np = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    for row in range(b):
        n = int(lens[row])
        np.testing.assert_allclose(
            got_np[row, :n], ref_np[row, :n], rtol=2e-5, atol=2e-5,
            err_msg=f"row {row}: kernel vs ref",
        )
        np.testing.assert_allclose(
            got_np[row, :n], cont[row, :n], rtol=2e-5, atol=2e-5,
            err_msg=f"row {row}: kernel vs contiguous",
        )
    assert np.isfinite(got_np).all(), "pad/dead rows must stay finite"


def test_chunked_kernel_matches_ref_and_contiguous_gqa():
    for seed, (kv, g) in enumerate([(1, 4), (2, 2), (4, 1)]):
        _check_chunked_gqa(np.random.default_rng(30 + seed), kv=kv, g=g,
                           bs=4, nb=6, c=8)


def test_chunked_kernel_unaligned_past_and_all_pad_rows():
    """past_len need not be block-aligned (piggyback chunk boundaries
    land mid-block), and all-pad dummy rows (lengths 0, trash tables)
    must stay finite."""
    _check_chunked_gqa(
        np.random.default_rng(41), kv=2, g=2, bs=4, nb=6, c=5,
        past=[0, 7, 13], dead=[False, False, True],
    )


def test_chunked_kernel_matches_ref_mla():
    rng = np.random.default_rng(42)
    b, c, h, r, rd, bs, nb = 2, 5, 4, 32, 8, 4, 4
    n_blocks, tables = _layout(rng, b, nb)
    ql = jnp.asarray(rng.normal(size=(b, c, h, r)), jnp.float32)
    qr = jnp.asarray(rng.normal(size=(b, c, h, rd)), jnp.float32)
    pc = jnp.asarray(rng.normal(size=(n_blocks + 1, bs, r)), jnp.float32)
    pr = jnp.asarray(rng.normal(size=(n_blocks + 1, bs, rd)), jnp.float32)
    past = jnp.asarray([0, 9], jnp.int32)
    lens = jnp.asarray([5, 3], jnp.int32)
    scale = (16 + 8) ** -0.5
    ref = paged_prefill_mla_ref(ql, qr, pc, pr, jnp.asarray(tables), past,
                                scale=scale)
    got = paged_prefill_mla(ql, qr, pc, pr, jnp.asarray(tables), past, lens,
                            scale=scale, interpret=True)
    for row in range(b):
        n = int(lens[row])
        np.testing.assert_allclose(
            np.asarray(got)[row, :n], np.asarray(ref)[row, :n],
            rtol=2e-5, atol=2e-5,
        )


def test_decode_is_chunk_of_one():
    """The decode wrappers ARE the chunked kernel at C=1: identical
    outputs for identical inputs."""
    rng = np.random.default_rng(50)
    b, kv, g, hd, bs, nb = 3, 2, 2, 16, 4, 4
    n_blocks, tables = _layout(rng, b, nb)
    q = jnp.asarray(rng.normal(size=(b, kv, g, hd)), jnp.float32)
    pk = jnp.asarray(rng.normal(size=(n_blocks + 1, bs, kv, hd)), jnp.float32)
    pv = jnp.asarray(rng.normal(size=(n_blocks + 1, bs, kv, hd)), jnp.float32)
    pos = jnp.asarray([0, 6, 15], jnp.int32)
    dec = paged_decode_gqa(q, pk, pv, jnp.asarray(tables), pos, interpret=True)
    chk = paged_prefill_gqa(
        q[:, None], pk, pv, jnp.asarray(tables), pos, jnp.ones_like(pos),
        interpret=True,
    )[:, 0]
    np.testing.assert_array_equal(np.asarray(dec), np.asarray(chk))


# ------------------------------------------------- hypothesis property
@pytest.mark.slow
def test_chunked_prefill_kernel_property_random_layouts():
    """Chunked paged prefill == ref.py == contiguous causal attention
    for random past lengths (block-aligned or not), suffix lengths,
    chunk widths, block sizes, GQA group counts, and dead-row masks."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=10, deadline=None)
    @given(
        seed=st.integers(0, 2 ** 16),
        bs=st.sampled_from([2, 4, 8]),
        c=st.sampled_from([2, 4, 8]),
        past_max=st.integers(0, 12),
        heads=st.sampled_from([(1, 4), (2, 2), (2, 1), (4, 1)]),
        dead=st.lists(st.booleans(), min_size=3, max_size=3),
    )
    def inner(seed, bs, c, past_max, heads, dead):
        kv, g = heads
        dead = dead if not all(dead) else [False] + dead[1:]
        rng = np.random.default_rng(seed)
        nb = -(-(past_max + c) // bs) + 1
        past = rng.integers(0, past_max + 1, size=3)
        _check_chunked_gqa(rng, kv=kv, g=g, bs=bs, nb=nb, c=c, past=past,
                           dead=dead)

    inner()


@pytest.mark.slow
def test_paged_kernel_property_random_layouts():
    """Pallas paged decode == ref.py == contiguous attention for random
    row lengths, block sizes, GQA group counts, and dead-row masks."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=10, deadline=None)
    @given(
        seed=st.integers(0, 2 ** 16),
        bs=st.sampled_from([2, 4, 8]),
        nb=st.integers(1, 4),
        heads=st.sampled_from([(1, 4), (2, 2), (2, 1), (4, 1)]),
        dead=st.lists(st.booleans(), min_size=3, max_size=3),
    )
    def inner(seed, bs, nb, heads, dead):
        kv, g = heads
        dead = dead if not all(dead) else [False] + dead[1:]
        _check_gqa(np.random.default_rng(seed), kv=kv, g=g, bs=bs, nb=nb,
                   dead=dead)

    inner()


# ------------------------------------------------- engine integration
@pytest.fixture(scope="module")
def serve_setup():
    from repro.models.model import init_params

    cfg = reduce_for_smoke(get_config(GQA_ARCH))
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _serve(cfg, params, backend, reqs):
    import copy

    from repro.serving.loop import ServingLoop

    loop = ServingLoop(cfg, params, batch_size=2, n_groups=1, cache_len=32,
                       paged_attn_backend=backend)
    for r in reqs:
        loop.submit(copy.deepcopy(r))
    done = loop.run(max_steps=400)
    return loop, {r.rid: r.generated for r in done}


def test_engine_slices_tables_to_pow2_active_width(serve_setup):
    """The block-sparse I/O win: short-context decode must gather far
    fewer table columns than blocks_per_slot, in pow2 buckets."""
    from repro.serving.batching import Request

    cfg, params = serve_setup
    rng = np.random.default_rng(21)
    reqs = [
        Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 5).astype(np.int32),
                max_new_tokens=4)
        for i in range(3)
    ]
    loop, _ = _serve(cfg, params, None, reqs)
    widths = loop.engine.decode_table_widths
    nb = loop.kv.blocks_per_slot  # 8 for cache_len=32, block_size=4
    assert widths, "paged decode never ran"
    assert all(w & (w - 1) == 0 for w in widths), widths  # powers of two
    # 5 prompt + 4 generated tokens end at pos 8 -> at most 4 blocks
    assert max(widths) <= 4 < nb


@pytest.mark.slow
def test_serving_identical_across_backends(serve_setup):
    """Serving with the Pallas kernel (interpret on CPU) is
    token-for-token identical to the dense-gather backend.

    Run at fp32 params: in bf16 the kernel's fp32 online softmax and the
    reference's bf16-rounded scores differ by an ulp, which can flip a
    near-tied greedy argmax and diverge the stream without any kernel
    bug. In fp32 the two agree to rounding and token identity is the
    robust invariant."""
    from repro.models.model import init_params
    from repro.serving.batching import Request

    cfg, _ = serve_setup
    cfg = dataclasses.replace(cfg, param_dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(17)
    reqs = [
        Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab_size, 4 + 3 * i).astype(np.int32),
            max_new_tokens=3,
        )
        for i in range(3)
    ]
    _, out_ref = _serve(cfg, params, "ref", reqs)
    _, out_pal = _serve(cfg, params, "pallas", reqs)
    assert out_pal == out_ref


# -------------------------------------- model-level chunked == contiguous
def test_model_prefill_paged_chunked_equals_contiguous_prefill(serve_setup):
    """Splitting a cold paged prefill into chunks at an arbitrary
    (mid-block) boundary yields the same last-token logits as the
    single-call paged prefill AND as the contiguous full-sequence
    `prefill` — the unified-path invariant behind piggyback chunking."""
    from repro.models.model import prefill, prefill_paged
    from repro.serving.paged_kv import PagedKVCache

    cfg, params = serve_setup
    rng = np.random.default_rng(23)
    plen = 11
    prompt = rng.integers(0, cfg.vocab_size, plen).astype(np.int32)

    ref_logits, _ = prefill(
        params, cfg, {"tokens": jnp.asarray(prompt[None, :])},
        cache_len=16, token_mask=jnp.ones((1, plen), bool),
    )

    def paged_run(splits):
        kv = PagedKVCache(cfg, 1, 16, block_size=4)
        kv.admit_slot(0, prompt)
        tables = jnp.asarray(kv.table_rows([0]))
        pools, logits = kv.pools, None
        bounds = [0, *splits, plen]
        for lo, hi in zip(bounds, bounds[1:]):
            logits, pools, _ = prefill_paged(
                params, cfg, {"tokens": jnp.asarray(prompt[None, lo:hi])},
                pools, tables, jnp.asarray([lo], jnp.int32),
                jnp.ones((1, hi - lo), bool),
            )
        return logits

    one_shot = paged_run([])
    chunked = paged_run([7])  # mid-block split (block_size 4)
    np.testing.assert_allclose(
        np.asarray(chunked), np.asarray(one_shot), rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(
        np.asarray(chunked), np.asarray(ref_logits), rtol=2e-4, atol=2e-4
    )
    assert int(jnp.argmax(chunked[0])) == int(jnp.argmax(ref_logits[0]))


def test_model_mla_prefill_paged_chunked_matches_contiguous():
    """MLA: the absorbed chunked paged prefill agrees with the expanded
    contiguous `prefill` (argmax-identical; absolute tolerance at the
    arch's bf16 absorbed-vs-expanded level) and chunk splitting is
    exactly stable."""
    from repro.models.model import init_params, prefill, prefill_paged
    from repro.serving.paged_kv import PagedKVCache

    cfg = reduce_for_smoke(get_config(MLA_ARCH))
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    plen = 11
    prompt = rng.integers(0, cfg.vocab_size, plen).astype(np.int32)
    ref_logits, _ = prefill(
        params, cfg, {"tokens": jnp.asarray(prompt[None, :])},
        cache_len=16, token_mask=jnp.ones((1, plen), bool),
    )

    def paged_run(splits):
        kv = PagedKVCache(cfg, 1, 16, block_size=4)
        kv.admit_slot(0, prompt)
        tables = jnp.asarray(kv.table_rows([0]))
        pools, logits = kv.pools, None
        bounds = [0, *splits, plen]
        for lo, hi in zip(bounds, bounds[1:]):
            logits, pools, _ = prefill_paged(
                params, cfg, {"tokens": jnp.asarray(prompt[None, lo:hi])},
                pools, tables, jnp.asarray([lo], jnp.int32),
                jnp.ones((1, hi - lo), bool),
            )
        return logits

    one_shot = paged_run([])
    chunked = paged_run([7])  # mid-block split
    np.testing.assert_array_equal(np.asarray(chunked), np.asarray(one_shot))
    np.testing.assert_allclose(
        np.asarray(chunked, np.float32), np.asarray(ref_logits, np.float32),
        rtol=0.05, atol=0.05,
    )
    assert int(jnp.argmax(chunked[0])) == int(jnp.argmax(ref_logits[0]))


# -------------------------------------------- chunked piggyback serving
def _churn_requests(cfg, rng, long_len=40):
    from repro.serving.batching import Request

    reqs = [
        Request(rid=0, prompt=rng.integers(0, cfg.vocab_size, long_len)
                .astype(np.int32), max_new_tokens=4)
    ]
    for i in range(3):
        reqs.append(Request(
            rid=1 + i,
            prompt=rng.integers(0, cfg.vocab_size, 4 + i).astype(np.int32),
            max_new_tokens=6,
        ))
    return reqs


def test_chunked_piggyback_interleaves_decode_with_long_prefill(serve_setup):
    """The head-of-line fix: while a long prompt's prefill streams in
    budgeted chunks, short requests admitted in the same wave must
    already be decoding (round-robin chunk scheduling + per-iteration
    piggyback) — decode never stalls behind the long prompt."""
    import copy

    from repro.serving.loop import ServingLoop

    cfg, params = serve_setup
    rng = np.random.default_rng(31)
    reqs = _churn_requests(cfg, rng)
    loop = ServingLoop(cfg, params, batch_size=4, n_groups=1, cache_len=48,
                       prefill_chunk_tokens=8)
    assert loop.chunked
    for r in reqs:
        loop.submit(copy.deepcopy(r))
    loop.run(max_steps=6)
    long_slot = next(
        i for i, s in enumerate(loop.batcher.slots)
        if s.request is not None and s.request.rid == 0
    )
    assert loop.batcher.slots[long_slot].prefilling, (
        "40-token prompt at budget 8 must still be mid-prefill"
    )
    shorts_decoding = [
        s.request for s in loop.batcher.slots
        if s.request is not None and s.request.rid != 0
        and len(s.request.generated) >= 2
    ]
    assert shorts_decoding, "short requests must decode during the long prefill"
    assert loop.stats.decode_steps >= 1
    done = loop.run(max_steps=400)
    assert len(done) == len(reqs)
    # the long prompt streamed in ceil((40 - past) / 8) >= 5 chunk calls
    assert loop.stats.prefill_chunks > loop.stats.admitted


def test_chunked_piggyback_token_identical_to_whole_suffix(serve_setup):
    """Flagship satellite: chunked piggyback admission generates exactly
    the same tokens as whole-suffix admission prefill.

    Run at fp32 params: chunk calls slice block tables to different pow2
    widths than the whole-suffix call, which perturbs XLA reduction
    order at the ~1e-7 level — under bf16 params that one-ulp noise can
    flip a near-tied MoE router top-k and diverge a whole token stream,
    so bf16 identity would only hold seed-by-seed. fp32 makes the
    invariant (no SYSTEMATIC divergence) robustly testable."""
    import copy

    from repro.models.model import init_params
    from repro.serving.loop import ServingLoop

    cfg, _ = serve_setup
    cfg = dataclasses.replace(cfg, param_dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(37)
    reqs = _churn_requests(cfg, rng)

    def serve(chunked):
        loop = ServingLoop(
            cfg, params, batch_size=2, n_groups=1, cache_len=48,
            chunked_prefill=chunked, prefill_chunk_tokens=8,
        )
        for r in reqs:
            loop.submit(copy.deepcopy(r))
        done = loop.run(max_steps=600)
        assert len(done) == len(reqs)
        return loop, {r.rid: r.generated for r in done}

    loop_c, out_c = serve(True)
    loop_w, out_w = serve(False)
    assert loop_c.stats.prefill_chunks > 0 and loop_w.stats.prefill_chunks == 0
    assert out_c == out_w


def test_engine_slices_prefill_tables_to_pow2_active_width(serve_setup):
    """The prefill analogue of the decode slicing test: chunk prefill
    must read pow2-bucketed table widths, not blocks_per_slot."""
    from repro.serving.batching import Request
    from repro.serving.loop import ServingLoop

    cfg, params = serve_setup
    rng = np.random.default_rng(43)
    loop = ServingLoop(cfg, params, batch_size=2, n_groups=1, cache_len=64)
    for i in range(3):
        loop.submit(Request(
            rid=i, prompt=rng.integers(0, cfg.vocab_size, 5 + i)
            .astype(np.int32), max_new_tokens=3,
        ))
    done = loop.run(max_steps=200)
    assert len(done) == 3
    widths = loop.engine.prefill_table_widths
    nb = loop.kv.blocks_per_slot  # 16 for cache_len=64, block_size=4
    assert widths, "paged chunked prefill never ran"
    assert all(w & (w - 1) == 0 or w == nb for w in widths), widths
    # prompts end at position <= 7 -> at most 2 blocks of 4
    assert max(widths) <= 2 < nb
