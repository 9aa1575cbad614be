"""GLM-4.5-Air's block on the serving path, at a small size in float32.

The block: GQA with q/k/v biases and rotary embeddings on the first half
of each head, a leading dense SwiGLU layer, then MoE layers whose router
scores every expert with a sigmoid, picks the top-k of the scores plus a
selection bias, and gates the picked experts by their unbiased scores
over their sum; one shared expert runs on every token. The configuration
here is one chip's share of each MoE layer: the router has 16 outputs
and the chip holds experts 4-7.

The plain reference below is a float64 NumPy forward of those equations
(as in `transformers`' `modeling_glm4_moe.py`), over the held experts
only: what experts held on other chips would add is left out, in the
program and the reference alike.
"""
import copy
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.models.model import init_params, stack_plan
from repro.models.moe import init_moe, moe_forward, shared_ffn
from repro.serving.batching import Request
from repro.serving.loop import ServingLoop
from repro.serving.tiered_moe import init_tiered_state, tier_sizes, tiered_moe_forward

# served logits against the reference's, as a share of the reference's
# largest logit: both are float32-or-better forwards of the same
# equations and differ only in summation order (~1e-6 here), while
# softmax routing, full rotary or selection without the bias moves the
# logits by a few percent or more
TOL = 1e-4


def _glm_share_cfg():
    cfg = reduce_for_smoke(get_config("glm-4.5-air"))
    moe = replace(cfg.moe, n_routed=16, n_experts=4, expert_offset=4, top_k=4)
    return replace(cfg, n_kv_heads=2, moe=moe, param_dtype="float32",
                   compute_dtype="float32")


def _with_biases(params, seed=1):
    """Non-zero q/k/v and router selection biases, so both paths are
    compared (the program initialises them to zero)."""
    rng = np.random.default_rng(seed)

    def fill(tree):
        out = {}
        for k, v in tree.items():
            if isinstance(v, dict):
                out[k] = fill(v)
            elif k in ("bq", "bk", "bv", "router_bias"):
                out[k] = jnp.asarray(rng.normal(0, 0.3, v.shape), v.dtype)
            else:
                out[k] = v
        return out

    return fill(params)


@pytest.fixture(scope="module")
def glm():
    cfg = _glm_share_cfg()
    return cfg, _with_biases(init_params(jax.random.PRNGKey(0), cfg))


# ------------------------------------------------------------ reference
def _rms(x, eps):
    return x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta, frac):
    """Rotate-half over the first `frac` of each head's dims. x [S,H,hd]."""
    rot = int(round(x.shape[-1] * frac))
    half = rot // 2
    freqs = 1.0 / theta ** (np.arange(half) / half)
    ang = np.arange(x.shape[0])[:, None] * freqs
    c, s = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rot]
    return np.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, x[..., rot:]], -1)


def _swiglu(h, wg, wu, wd):
    g = h @ wg
    return (g / (1 + np.exp(-g)) * (h @ wu)) @ wd


def _np(tree):
    return jax.tree.map(lambda a: np.asarray(a, np.float64), tree)


def reference_logits(params, cfg, tokens, *, scoring="sigmoid",
                     rotary_frac=None, selection_bias=True):
    """Logits [S, V] of `tokens` through the plain forward; the keyword
    arguments swap in the equations the negative tests check against."""
    p = _np(params)
    mo = cfg.moe
    frac = cfg.rotary_frac if rotary_frac is None else rotary_frac
    unrolled, n_groups, period = stack_plan(cfg)  # where each layer's params are
    layers = [p[f"layer{li}"] for li in unrolled] + [
        jax.tree.map(lambda a: a[g], p["stack"][f"slot{j}"])
        for g in range(n_groups) for j in range(len(period))]
    s = len(tokens)
    x = p["embed"]["table"][np.asarray(tokens)]
    causal = np.tril(np.ones((s, s), bool))
    for li, lp in enumerate(layers):
        a = lp["mixer"]
        h = _rms(x, cfg.norm_eps)
        q = np.einsum("sd,dhk->shk", h, a["wq"]) + a["bq"]
        k = np.einsum("sd,dhk->shk", h, a["wk"]) + a["bk"]
        v = np.einsum("sd,dhk->shk", h, a["wv"]) + a["bv"]
        q, k = _rope(q, cfg.rope_theta, frac), _rope(k, cfg.rope_theta, frac)
        group = cfg.n_heads // cfg.n_kv_heads
        k, v = np.repeat(k, group, axis=1), np.repeat(v, group, axis=1)
        sc = np.einsum("shk,thk->hst", q, k) / np.sqrt(q.shape[-1])
        sc = np.where(causal, sc, -np.inf)
        pr = np.exp(sc - sc.max(-1, keepdims=True))
        pr /= pr.sum(-1, keepdims=True)
        x = x + np.einsum("hst,thk,hkd->sd", pr, v, a["wo"])
        h = _rms(x, cfg.norm_eps)
        f = lp["ffn"]
        if li == 0:  # the leading dense layer
            x = x + _swiglu(h, f["w_gate"], f["w_up"], f["w_down"])
            continue
        logits = h @ f["router"]
        if scoring == "sigmoid":
            scores = 1.0 / (1.0 + np.exp(-logits))
        else:
            scores = np.exp(logits - logits.max(-1, keepdims=True))
            scores /= scores.sum(-1, keepdims=True)
        pick = scores + f["router_bias"] if selection_bias else scores
        idx = np.argsort(-pick, axis=-1, kind="stable")[:, : mo.top_k]
        w = np.take_along_axis(scores, idx, -1)
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
        y = _swiglu(h, f["shared"]["w_gate"][0], f["shared"]["w_up"][0],
                    f["shared"]["w_down"][0])
        for j in range(mo.n_experts):  # the held experts, by global id
            gate = (w * (idx == mo.expert_offset + j)).sum(-1, keepdims=True)
            y = y + gate * _swiglu(h, f["w_gate"][j], f["w_up"][j], f["w_down"][j])
        x = x + y
    return _rms(x, cfg.norm_eps) @ p["head"]["w"]


# --------------------------------------------------------- serving path
def _serve_recording_logits(cfg, params, n=3, new_tokens=5):
    """Serve `n` requests (one per slot) through ServingLoop, recording
    the logits each prefill and decode call returned at each position.
    Returns [(prompt + generated, {position: logits})]."""
    rng = np.random.default_rng(11)
    reqs = [Request(rid=r, prompt=rng.integers(0, cfg.vocab_size, 5 + 2 * r)
                    .astype(np.int32), max_new_tokens=new_tokens) for r in range(n)]
    loop = ServingLoop(cfg, params, batch_size=n, n_groups=1, cache_len=24,
                       prefill_chunk_tokens=4)
    eng, seen = loop.engine, {r.rid: {} for r in reqs}
    orig_prefill, orig_step = eng.prefill_slots_paged, eng.step_slots_paged

    def rid(slot):
        return loop.batcher.slots[int(slot)].request.rid

    def prefill(suffixes, slot_indices, lengths, past_len):
        logits = orig_prefill(suffixes, slot_indices, lengths, past_len)
        for row, i in enumerate(slot_indices):
            seen[rid(i)][int(past_len[row] + lengths[row] - 1)] = np.asarray(logits[row])
        return logits

    def step(tokens, pos, slot_indices, tables, live=None):
        logits, counts = orig_step(tokens, pos, slot_indices, tables, live=live)
        for row, i in enumerate(slot_indices):
            if live is None or live[row]:
                seen[rid(i)][int(pos[row])] = np.asarray(logits[row])
        return logits, counts

    eng.prefill_slots_paged, eng.step_slots_paged = prefill, step
    for r in reqs:
        loop.submit(copy.deepcopy(r))
    done = {r.rid: r for r in loop.run(max_steps=200)}
    assert sorted(done) == [r.rid for r in reqs]
    out = []
    for r in reqs:
        full = np.concatenate([r.prompt, np.asarray(done[r.rid].generated, np.int32)])
        out.append((full, seen[r.rid]))
    return out


@pytest.fixture(scope="module")
def served(glm):
    cfg, params = glm
    return _serve_recording_logits(cfg, params)


def _worst_gap(served, params, cfg, **variant):
    """Largest |served - reference| logit over every recorded position,
    as a share of the reference's largest logit there."""
    worst = 0.0
    for full, by_pos in served:
        want = reference_logits(params, cfg, full[:-1], **variant)
        # the last prompt position (prefill) and each decode position
        assert min(by_pos) < len(full) - len(by_pos) + 1
        for pos, got in by_pos.items():
            ref = want[pos]
            worst = max(worst, np.abs(got - ref).max() / np.abs(ref).max())
    return worst


def test_prefill_then_decode_matches_the_reference(glm, served):
    cfg, params = glm
    assert all(len(by_pos) >= 5 for _, by_pos in served)
    assert _worst_gap(served, params, cfg) <= TOL


@pytest.mark.parametrize("variant", [
    {"scoring": "softmax"}, {"rotary_frac": 1.0}, {"selection_bias": False},
], ids=["softmax_routing", "full_rotary", "no_selection_bias"])
def test_other_equations_miss_the_tolerance(glm, served, variant):
    """Each part of the block the reference checks shows: serving
    routed by softmax, rotating whole heads, or choosing experts without
    the bias would each have failed the comparison above."""
    cfg, params = glm
    assert _worst_gap(served, params, cfg, **variant) > 10 * TOL


def test_routed_pairs_split_into_tiers_and_elsewhere(glm):
    cfg, params = glm
    loop = ServingLoop(cfg, params, batch_size=4, n_groups=2, cache_len=24)
    eng, live_rows = loop.engine, []
    orig = eng.step_slots_paged

    def step(tokens, pos, slot_indices, tables, live=None):
        live_rows.append(len(slot_indices) if live is None else int(np.sum(live)))
        return orig(tokens, pos, slot_indices, tables, live=live)

    eng.step_slots_paged = step
    rng = np.random.default_rng(5)
    for r in range(6):
        loop.submit(Request(rid=r, prompt=rng.integers(0, cfg.vocab_size, 6)
                            .astype(np.int32), max_new_tokens=4))
    loop.run(max_steps=300)
    st = eng.stats
    n_moe = cfg.n_layers - 1
    held = st.routed_hot + st.routed_warm + st.routed_cold
    assert held + st.routed_elsewhere == sum(live_rows) * cfg.moe.top_k * n_moe
    assert held == st.moe_assignments
    # 4 of 16 experts held: most pairs go elsewhere, some stay
    assert 0 < held < st.routed_elsewhere


# ------------------------------------------------------- the share test
def _share(p, cfg, i, n):
    """Share `i` of `n` of an uncut layer: the same router, experts
    [i*E/n, (i+1)*E/n) held."""
    e = cfg.moe.n_experts // n
    mo = replace(cfg.moe, n_routed=cfg.moe.n_experts, n_experts=e, expert_offset=i * e)
    held = {k: p[k][i * e:(i + 1) * e] for k in ("w_gate", "w_up", "w_down")}
    return {**p, **held}, replace(cfg, moe=mo)


def _tier_state(p, cfg):
    """A layer's tier buffers holding its flat expert weights, placed
    by the tables `init_tiered_state` starts from."""
    st = init_tiered_state(jax.random.PRNGKey(3), cfg, tier_sizes(cfg))
    w = np.stack([p["w_gate"], p["w_up"], np.swapaxes(p["w_down"], 1, 2)], axis=1)
    tier, slot = np.asarray(st["expert_tier"]), np.asarray(st["expert_slot"])
    for tid, key in enumerate(("hot", "warm", "cold")):
        buf = np.asarray(st[key]).copy()
        for ge in np.nonzero(tier == tid)[0]:
            buf[slot[ge]] = w[ge - cfg.moe.expert_offset]
        st[key] = jnp.asarray(buf)
    return st


@pytest.mark.parametrize("path", ["tiered", "flat"])
def test_shares_add_up_to_the_uncut_layer(path):
    """8 routed experts over 4 shares of 2: the shares' routed parts,
    with the shared expert counted once, equal the uncut layer."""
    base = _glm_share_cfg()
    cfg = replace(base, moe=replace(base.moe, n_routed=0, n_experts=8,
                                    expert_offset=0, top_k=4))
    p = _with_biases(init_moe(jax.random.PRNGKey(2), cfg), seed=4)
    x = jax.random.normal(jax.random.PRNGKey(5), (3, 5, cfg.d_model), jnp.float32)

    def layer(p, cfg):
        if path == "flat":
            out = moe_forward(p, cfg, x, full_capacity=True)
            return out.y, out.expert_counts
        return tiered_moe_forward(p, _tier_state(p, cfg), cfg, x,
                                  cold_capacity_frac=1.0)

    want, want_counts = layer(p, cfg)
    n = 4
    parts = [layer(*_share(p, cfg, i, n)) for i in range(n)]
    got = sum(y for y, _ in parts) - (n - 1) * shared_ffn(p["shared"], x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    for _, counts in parts:  # every share routes over all 8
        np.testing.assert_array_equal(np.asarray(counts), np.asarray(want_counts))
    # a share alone is not the layer
    assert np.abs(np.asarray(parts[0][0] - want)).max() > 1e-2
