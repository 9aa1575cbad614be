"""Speculative multi-token decode tests (serving/spec_decode.py,
engine.verify_slots_paged, PagedKVCache.truncate).

Three layers of evidence:

  * host units: the radix read-only extension probe and the
    prompt-lookup drafter (n-gram fallback, radix priority, lifecycle);
  * the tentpole kernel invariant: ONE chunk-of-k verify call through
    the chunked paged-attention + masked MoE path produces bit-exactly
    the logits of k sequential decode steps in fp32 — so greedy
    accept-prefix can never change a token;
  * serving identity: the spec loop's token streams equal the plain
    loop's, token for token, for honest AND adversarially corrupted
    drafts (a wrong draft may only cost throughput, never correctness).
"""
import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.models.model import init_params
from repro.serving.batching import Request
from repro.serving.loop import ServingLoop
from repro.serving.paged_kv import RadixPrefixIndex
from repro.serving.spec_decode import DraftConfig, PromptLookupDrafter

ARCH = "granite-moe-1b-a400m"


@pytest.fixture(scope="module")
def fp32_setup():
    cfg = reduce_for_smoke(get_config(ARCH))
    cfg = dataclasses.replace(
        cfg, param_dtype="float32", compute_dtype="float32"
    )
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


# -------------------------------------------- radix extension probe
def test_lookup_extension_walks_committed_chain():
    r = RadixPrefixIndex(2)
    r.insert([1, 2, 3, 4, 5, 6], [10, 11, 12])
    assert r.lookup_extension([1, 2], 4) == [3, 4, 5, 6]
    assert r.lookup_extension([1, 2], 3) == [3, 4, 5]  # k caps the probe
    # partial remainder: must be a prefix of exactly one child chunk
    assert r.lookup_extension([1, 2, 3], 2) == [4, 5]
    assert r.lookup_extension([1, 2, 3], 10) == [4, 5, 6]
    assert r.lookup_extension([1], 2) == [2, 3]
    # misses: unknown block, diverging remainder, exhausted chain
    assert r.lookup_extension([9, 9], 3) == []
    assert r.lookup_extension([1, 9], 3) == []
    assert r.lookup_extension([1, 2, 9], 3) == []
    assert r.lookup_extension([1, 2, 3, 4, 5, 6], 2) == []
    assert r.lookup_extension([1, 2], 0) == []


def test_lookup_extension_prefers_smallest_child_deterministically():
    r = RadixPrefixIndex(2)
    r.insert([1, 2, 7, 8], [10, 11])
    r.insert([1, 2, 3, 4], [10, 12])
    # two children under (1, 2): the probe picks min(...) — stable
    # across runs, no RNG (repro-lint RL007 territory)
    assert r.lookup_extension([1, 2], 2) == [3, 4]
    assert r.lookup_extension([1, 2, 7], 1) == [8]


def test_lookup_extension_is_read_only():
    """The probe must not touch LRU state: `match` ticks the clock and
    re-stamps the chain, `lookup_extension` may not (a speculative probe
    per decode step would otherwise pin hot chains forever)."""
    r = RadixPrefixIndex(2)
    r.insert([1, 2, 3, 4], [10, 11])
    r.insert([5, 6], [12])
    clock = r._clock
    stamps = {b: n.stamp for b, n in r._nodes.items()}
    assert r.lookup_extension([1, 2], 2) == [3, 4]
    assert r.lookup_extension([5], 1) == [6]
    assert r._clock == clock
    assert {b: n.stamp for b, n in r._nodes.items()} == stamps
    # ... so eviction order is exactly what it was before the probes
    assert r.evict_lru(lambda b: True) == 11


# ------------------------------------------------------------ drafter
def test_ngram_drafter_proposes_recurring_suffix():
    d = PromptLookupDrafter(DraftConfig(k=4, max_ngram=3))
    d.begin_slot(0, [5, 6, 7, 9, 5, 6])
    # suffix [5, 6] recurred at index 0; propose what followed it
    assert d.draft(0) == [7, 9, 5, 6]
    d.extend(0, [7])
    assert d.history(0)[-1] == 7
    # now the longest recurring suffix is [5, 6, 7]
    assert d.draft(0) == [9, 5, 6, 7]
    assert d.draft(0, 1) == [9]  # per-call cap below cfg.k
    d.free_slot(0)
    d.begin_slot(0, [1, 1])
    assert d.draft(0) == [1]  # 1-gram tail match
    d.free_slot(0)


def test_drafter_prefers_radix_extension_over_ngram():
    r = RadixPrefixIndex(2)
    r.insert([5, 6, 7, 9, 21, 22], [10, 11, 12])
    d = PromptLookupDrafter(DraftConfig(k=3), radix=r)
    # history has an n-gram match ([5,6] -> 7) AND a committed radix
    # extension; the radix (exact replay evidence) must win
    d.begin_slot(0, [5, 6, 7, 9])
    assert d.draft(0) == [21, 22]
    # radix miss falls back to the n-gram proposal
    d.begin_slot(1, [5, 6, 8, 5, 6])
    assert d.draft(1) == [8, 5, 6]
    # no evidence at all: empty draft (the step decodes a chunk of 1)
    d.begin_slot(2, [1, 2, 3, 4])
    assert d.draft(2) == []


# --------------------------------- tentpole: chunk-of-k verify parity
K_DRAFT = 4


def test_verify_chunk_matches_sequential_steps(fp32_setup):
    """THE spec-decode invariant: one verify_slots_paged call over the
    chunk [t0, d1..dk-1] reproduces k sequential step_slots_paged calls
    — a chunk of 1 is BITWISE the decode step (same kernel), and wider
    chunks agree to fp32 rounding (XLA specializes S=1 dense ops to a
    different accumulation order) with EXACTLY equal greedy tokens, so
    accept-prefix can never flip a token vs plain decode."""
    cfg, params = fp32_setup
    rng = np.random.default_rng(17)
    prompt = rng.integers(0, cfg.vocab_size, 10).astype(np.int32)
    loop = ServingLoop(cfg, params, batch_size=2, n_groups=1, cache_len=24)
    kv, eng = loop.kv, loop.engine
    past = kv.admit_slot(0, prompt)
    plen = len(prompt)
    logits = eng.prefill_slots_paged(
        prompt[None, past:], [0],
        np.asarray([plen - past], np.int32), np.asarray([past], np.int32),
    )
    cur = int(np.asarray(jnp.argmax(logits[0], -1)))

    # sequential greedy decode: k steps, recording logits and tokens
    seq_logits, chain = [], [cur]
    for j in range(K_DRAFT):
        kv.ensure_block(0, plen + j)
        lg, _ = eng.step_slots_paged(
            np.asarray([[chain[-1]]], np.int32),
            np.asarray([plen + j], np.int32),
            [0], kv.table_rows([0]), live=np.asarray([True]),
        )
        seq_logits.append(np.asarray(lg[0], np.float32))
        chain.append(int(np.asarray(jnp.argmax(lg[0], -1))))
    assert int(kv.lengths[0]) == plen + K_DRAFT

    # roll the cache back to the committed prompt: chunk-of-1 verify of
    # the first step must be BIT-IDENTICAL to the decode step
    kv.truncate(0, plen)
    assert int(kv.lengths[0]) == plen
    kv.ensure_block(0, plen)
    one, _ = eng.verify_slots_paged(
        np.asarray([[chain[0]]], np.int32), [0],
        np.asarray([1], np.int32), np.asarray([plen], np.int32),
    )
    np.testing.assert_array_equal(
        np.asarray(one, np.float32)[0, 0], seq_logits[0],
        err_msg="chunk-of-1 verify is not bitwise the decode step",
    )

    # ... and the full chunk-of-k call must reproduce every sequential
    # step: same greedy token exactly, logits to fp32 rounding
    kv.truncate(0, plen)
    chunk = np.asarray([chain[:K_DRAFT]], np.int32)
    for p in range(plen, plen + K_DRAFT):
        kv.ensure_block(0, p)
    ver, _ = eng.verify_slots_paged(
        chunk, [0], np.asarray([K_DRAFT], np.int32),
        np.asarray([plen], np.int32),
    )
    ver = np.asarray(ver, np.float32)
    for j in range(K_DRAFT):
        np.testing.assert_allclose(
            ver[0, j], seq_logits[j], rtol=1e-5, atol=1e-5,
            err_msg=f"verify position {j} diverges from sequential step",
        )
        assert int(np.argmax(ver[0, j])) == chain[j + 1], (
            f"verify position {j} flips the greedy token"
        )
    assert eng.verify_compiles >= 1
    assert all(w & (w - 1) == 0 for w in eng.verify_widths)


def test_verify_dead_rows_padded_to_trash(fp32_setup):
    """A dead row in the verify group must scatter to the trash block
    (same contract as plain decode) — the sanitizer sweeps this."""
    cfg, params = fp32_setup
    rng = np.random.default_rng(19)
    loop = ServingLoop(cfg, params, batch_size=2, n_groups=1, cache_len=24)
    kv, eng = loop.kv, loop.engine
    for s in (0, 1):
        prompt = rng.integers(0, cfg.vocab_size, 6).astype(np.int32)
        past = kv.admit_slot(s, prompt)
        eng.prefill_slots_paged(
            prompt[None, past:], [s],
            np.asarray([6 - past], np.int32), np.asarray([past], np.int32),
        )
    kv.ensure_block(0, 6)
    kv.ensure_block(0, 7)
    logits, _ = eng.verify_slots_paged(
        np.asarray([[3, 4], [0, 0]], np.int32), [0, 1],
        np.asarray([2, 0], np.int32), np.asarray([6, 6], np.int32),
        live=np.asarray([True, False]),
    )
    assert int(kv.lengths[0]) == 8
    assert int(kv.lengths[1]) == 6  # dead row wrote nothing
    assert np.all(np.isfinite(np.asarray(logits[0], np.float32)))


# -------------------------------------------- serving-level identity
def _serve(cfg, params, prompts, new_tokens, *, spec, loop=None, rid0=0,
           **kw):
    if loop is None:
        cache_len = max(len(p) for p in prompts) + new_tokens + 2
        loop = ServingLoop(cfg, params, batch_size=2, n_groups=1,
                           cache_len=cache_len, spec_decode=spec, **kw)
    for i, p in enumerate(prompts):
        loop.submit(Request(rid=rid0 + i, prompt=np.asarray(p, np.int32),
                            max_new_tokens=new_tokens))
    done = loop.run(max_steps=500)
    return loop, {r.rid - rid0: list(r.generated) for r in done
                  if r.rid >= rid0}


def test_spec_serving_identical_to_plain(fp32_setup):
    """Flagship: the speculative loop's token streams equal the plain
    loop's token for token (fp32), across two waves — the second wave
    replays wave-1 prompts against a warm radix, so real multi-token
    accepts happen — plus one long prompt that chunk-prefills while
    other slots are mid-decode."""
    cfg, params = fp32_setup
    rng = np.random.default_rng(23)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (9, 12, 31, 7)]
    plain, toks_plain = _serve(cfg, params, prompts, 6, spec=False)
    spec, toks_spec = _serve(cfg, params, prompts, 6, spec=True)
    assert toks_spec == toks_plain
    # wave 2: same prompts, warm radix — drafts must actually land
    spec2, toks_spec2 = _serve(cfg, params, prompts, 6, spec=True,
                               loop=spec, rid0=100)
    assert toks_spec2 == toks_plain
    st = spec.stats
    assert st.spec_drafted_tokens > 0
    assert st.spec_accepted_tokens > 0, (
        "warm-radix replay accepted zero drafts — the drafter or the "
        "accept-prefix logic is inert"
    )
    snap = st.snapshot()
    assert snap["serving.spec_acceptance_rate"] == pytest.approx(
        st.spec_accepted_tokens / st.spec_drafted_tokens
    )
    assert snap["serving.spec_drafted_tokens"] == st.spec_drafted_tokens
    assert "spec_acc=" in st.summary()


def test_spec_requires_paged_prefix_cacheable_arch():
    """Verify rolls rejected drafts back out of the paged blocks, which
    a recurrent state cannot follow: spec decode on jamba is refused."""
    cfg = reduce_for_smoke(get_config("jamba-v0.1-52b"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(AssertionError, match="spec_decode requires"):
        ServingLoop(cfg, params, batch_size=2, n_groups=1, cache_len=16,
                    spec_decode=True)


def test_spec_identity_survives_corrupted_drafts(fp32_setup):
    """Adversarial drafter: flip draft tokens at fixed positions. The
    verify/accept/rollback machinery must still emit the plain greedy
    stream — bad drafts cost throughput, never correctness."""
    cfg, params = fp32_setup
    rng = np.random.default_rng(29)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (10, 13)]
    _, toks_plain = _serve(cfg, params, prompts, 5, spec=False)
    for corrupt_at in (0, 1, 2):
        loop, toks = _serve(cfg, params, prompts, 5, spec=True)
        base_draft = loop.drafter.draft

        def bad_draft(slot, k=None, _at=corrupt_at):
            out = list(base_draft(slot, k))
            if len(out) > _at:
                out[_at] = (out[_at] + 1) % cfg.vocab_size
            return out

        loop.drafter.draft = bad_draft
        _, toks2 = _serve(cfg, params, prompts, 5, spec=True, loop=loop,
                          rid0=100)
        assert toks == toks_plain
        assert toks2 == toks_plain, (
            f"corrupting draft position {corrupt_at} changed the "
            f"committed stream"
        )


@pytest.mark.slow
def test_spec_identity_property_random_drafts(fp32_setup):
    """Hypothesis widening: arbitrary draft corruption masks, draft
    lengths, and prompt shapes (including a mid-prefill long prompt)
    never change the committed stream."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    cfg, params = fp32_setup

    @settings(max_examples=6, deadline=None)
    @given(
        seed=st.integers(0, 2 ** 16),
        k=st.integers(1, 5),
        flips=st.lists(st.integers(0, 4), max_size=3),
        long_prompt=st.booleans(),
    )
    def inner(seed, k, flips, long_prompt):
        rng = np.random.default_rng(seed)
        lens = [8, 11] + ([29] if long_prompt else [])
        prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
                   for n in lens]
        _, toks_plain = _serve(cfg, params, prompts, 4, spec=False)
        loop, toks = _serve(
            cfg, params, prompts, 4, spec=True,
            spec_config=DraftConfig(k=k),
        )
        assert toks == toks_plain
        base_draft = loop.drafter.draft

        def bad_draft(slot, kk=None):
            out = list(base_draft(slot, kk))
            for f in flips:
                if f < len(out):
                    out[f] = (out[f] + 1 + f) % cfg.vocab_size
            return out

        loop.drafter.draft = bad_draft
        _, toks2 = _serve(cfg, params, prompts, 4, spec=True, loop=loop,
                          rid0=100)
        assert toks2 == toks_plain

    inner()
