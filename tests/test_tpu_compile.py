"""Compile the serving path's Pallas kernels for a TPU v5e, without one.

The TPU compiler is installed even where no chip is attached: it
compiles for a described `v5e:2x2` topology and raises what the chip's
compiler would raise (unaligned block shapes, too much VMEM), which
interpret-mode tests cannot show. Shapes are granite-moe-1b-a400m's
published widths and the serving loop's defaults: Kv=8 heads of 64 with
G=2 query heads each, 4-token KV blocks, 4-row prefill chunks of 32
tokens, decode groups of 4 rows, d_model 1024 and experts of width 512.
The expert migrations compile at the benchmark's tier split: 24 layers
of 8 hot, 10 warm and 16 cold slots. The same kernels and migrations
compile again at GLM-4.5-Air's widths (the `glm-4.5-air-stage` cell):
96 query heads over 8 kv heads of 128 (G=12), d_model 4096, experts of
width 1408 held 32 to a chip in 4 MoE layers (8/10/16 slots). The
paged decode, prefill and verify programs compile with their KV pools
donated, as the engine runs them, and keep the device ops their
separate entry points compiled to before the three became one paged
forward.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker
imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.expert_gemv.expert_gemv import expert_ffn_gemv, f_tile
from repro.kernels.moe_gemm.moe_gemm import moe_gemm
from repro.kernels.paged_attention import paged_prefill_gqa, paged_prefill_mla
from repro.serving.tiered_moe import apply_migrations

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
KV, G, HD, BS = 8, 2, 64, 4  # granite kv heads, group, head dim; loop block
D, F = 1024, 512             # granite d_model, d_expert
GLM_G, GLM_HD = 12, 128      # glm-4.5-air: 96 query heads over 8 kv heads
GLM_D, GLM_F = 4096, 1408    # glm-4.5-air d_model, moe_intermediate_size
ROWS, NB = 4, 64             # rows per call, block-table width


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_compile_cache():
    """An entry compiled for a described chip cannot be read back
    without one, so these compiles stay out of the persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def s(one_chip):
    """Shape of an argument placed on the described chip."""
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)


def _paged_prefill_gqa(s, chunk, g, hd, rows=ROWS, nb=NB):
    n = rows * nb + 1
    return paged_prefill_gqa.lower(
        s((rows, chunk, KV, g, hd), BF16), s((n, BS, KV, hd), BF16),
        s((n, BS, KV, hd), BF16), s((rows, nb), I32), s((rows,), I32),
        s((rows,), I32), interpret=False,
    ).compile().as_text()


@pytest.mark.parametrize("chunk", [1, 32])
def test_paged_prefill_gqa_compiles(s, chunk):
    """Decode (chunk 1) and prefill chunks through the one GQA kernel."""
    assert "tpu_custom_call" in _paged_prefill_gqa(s, chunk, G, HD)


@pytest.mark.parametrize("chunk", [1, 32])
def test_paged_prefill_gqa_compiles_at_glm_widths(s, chunk):
    """12 query heads per kv head of 128: each block's query tile is
    [chunk x 12, 128] per kv head."""
    assert "tpu_custom_call" in _paged_prefill_gqa(s, chunk, GLM_G, GLM_HD)


@pytest.mark.parametrize("rows,chunk,g,hd,nb", [
    (64, 1, G, HD, 288), (128, 1, GLM_G, GLM_HD, 288), (64, 1, G, HD, 320),
    (4, 32, G, HD, 256), (4, 32, GLM_G, GLM_HD, 256),
], ids=["granite_decode", "glm_decode", "chat_decode", "granite_prefill",
        "glm_prefill"])
def test_paged_prefill_gqa_compiles_at_cell_shapes(s, rows, chunk, g, hd, nb):
    """The cells' calls: decode over 64 (granite) and 128 (GLM) rows at
    decode-long's 288-column cap and chat's 320, and 4-row prefill
    chunks of 32 tokens at 256 columns. The kernel's K/V buffers hold
    up to 256 tokens of every kv head, twice, beside the query tile."""
    assert "tpu_custom_call" in _paged_prefill_gqa(s, chunk, g, hd, rows, nb)


def test_paged_prefill_mla_compiles_at_chunk_one(s):
    """deepseek-v2's 128 heads, latent 512 + rope 64, one query token.
    (At chunk 32 its fp32 [C*H, r] scratch alone is 8 MiB and the
    compiler refuses it for VMEM.)"""
    h, r, rd, n = 128, 512, 64, ROWS * NB + 1
    compiled = paged_prefill_mla.lower(
        s((ROWS, 1, h, r), F32), s((ROWS, 1, h, rd), F32),
        s((n, BS, r), BF16), s((n, BS, rd), BF16), s((ROWS, NB), I32),
        s((ROWS,), I32), s((ROWS,), I32), scale=(128 + 64) ** -0.5,
        interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "k_dim,n_dim", [(D, 2 * F), (F, D), (GLM_D, 2 * GLM_F), (GLM_F, GLM_D)],
    ids=["gate_up", "down", "glm_gate_up", "glm_down"])
def test_moe_gemm_compiles(s, k_dim, n_dim):
    """The two grouped GEMMs of `grouped_expert_ffn` on prefill buffers:
    8 hot-tier experts x 128 rows (4 rows x 32-token chunk)."""
    e, t = 8, 8 * 128
    compiled = moe_gemm.lower(
        s((t, k_dim), BF16), s((e, k_dim, n_dim), BF16), s((t // 128,), I32),
        interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _expert_ffn_gemv(s, tokens, d, f):
    return expert_ffn_gemv.lower(
        s((tokens, d), BF16), s((d, f), BF16), s((d, f), BF16),
        s((f, d), BF16), interpret=False,
    ).compile().as_text()


@pytest.mark.parametrize("tokens", [4, 8])
def test_expert_ffn_gemv_compiles(s, tokens):
    """One expert's decode buffer in `cold_expert_ffn`: a 4-row decode
    group (batch 8, 2 groups) and a full 8-row batch."""
    assert "tpu_custom_call" in _expert_ffn_gemv(s, tokens, D, F)


@pytest.mark.parametrize("tokens", [8, 128])
def test_expert_ffn_gemv_compiles_at_glm_widths(s, tokens):
    """GLM-4.5-Air's experts at D=4096, F=1408, whose whole-F panels
    would ask ~25 MiB of VMEM: the F tile divides F, so nothing is
    padded."""
    hlo = _expert_ffn_gemv(s, tokens, GLM_D, GLM_F)
    assert "tpu_custom_call" in hlo
    assert not re.search(r"\bpad\(", hlo)


def test_granite_gemv_keeps_its_tile():
    """Granite's expert (D=1024, F=512) streams one whole-F panel, as
    before the tile was chosen from VMEM; GLM-4.5-Air's cannot, and
    takes the widest lane-aligned divisor of 1408 that fits."""
    for tokens in (4, 8, 64):
        assert f_tile(tokens, D, F, 2) == 512
    assert f_tile(128, GLM_D, GLM_F, 2) == 128
    assert f_tile(4, 64, 32, 4) == 32  # no multiple of 128 divides F


def _assert_migrations_in_place(s, stacked, layers, d, f, width):
    lead = (layers,) if stacked else ()
    state = {k: s(lead + (n, 3, d, f), BF16)
             for k, n in (("hot", 8), ("warm", 10), ("cold", 16))}
    state.update({k: s(lead + (width,), I32) for k in ("expert_tier", "expert_slot")})
    args = (state, s((8, 5), I32)) + ((s((), I32),) if stacked else ())
    hlo = jax.jit(apply_migrations, donate_argnums=0).lower(*args).compile().as_text()
    assert "input_output_alias" in hlo
    bufs = {f"bf16[{','.join(map(str, (lead or (1,)) + (n, 3, d, f)))}]"
            for n in (8, 10, 16)} | {f"bf16[{n},3,{d},{f}]" for n in (8, 10, 16)}
    outs = [m.group(1) for m in re.finditer(r"= (.*?) copy(?:-start)?\(", hlo)]
    copies = [o for o in outs if any(b in o for b in bufs)]
    assert not copies, copies[:3]


@pytest.mark.parametrize("stacked", [True, False], ids=["stack", "layer"])
def test_migrations_update_tier_buffers_in_place(s, stacked):
    """The engine's migration programs (tier state donated) compile to
    updates of the swapped slots: no op copies a whole tier buffer."""
    _assert_migrations_in_place(s, stacked, 24, D, F, 32)


@pytest.mark.parametrize("stacked", [True, False], ids=["stack", "layer"])
def test_migrations_update_tier_buffers_in_place_at_glm_widths(s, stacked):
    """4 MoE layers of [3, 4096, 1408] experts; the tables have one
    entry per router output (128), of which the chip holds 32."""
    _assert_migrations_in_place(s, stacked, 4, GLM_D, GLM_F, 128)


def _paged_program(monkeypatch, s, phase, rows, width, slots=4, ctx=64):
    """The paged forward of `phase` (decode: 1 token a row, prefill: 32,
    verify: 4) compiled with its KV pools donated, as the engine runs
    it: granite at published widths, 4 layers deep (one layer per scan
    group), `slots` slots of `ctx` tokens, `rows` rows over tables cut
    to `width` columns, with the Pallas kernels on the path. Prefill's
    expert counts are dropped, as the engine drops them. Returns the
    optimized HLO and the pools' shapes."""
    import dataclasses

    import repro.kernels.backend as kb
    import repro.kernels.paged_attention as pa
    from repro.configs import get_config
    from repro.models.model import forward_paged, init_params
    from repro.serving.engine import init_tiered_for_model, strip_expert_weights
    from repro.serving.paged_kv import init_paged_cache
    from repro.serving.tiered_moe import TierSizes

    for mod in (kb, pa):
        monkeypatch.setattr(mod, "resolve_backend", lambda *a, **k: ("pallas", False))
    cfg = dataclasses.replace(get_config("granite-moe-1b-a400m"), n_layers=4)
    key = jax.random.PRNGKey(0)
    params = strip_expert_weights(jax.eval_shape(lambda: init_params(key, cfg)), cfg)
    tiers = jax.eval_shape(lambda: init_tiered_for_model(key, cfg, TierSizes(8, 10, 14)))
    pools, states = jax.eval_shape(lambda: init_paged_cache(cfg, slots, ctx, BS))
    place = lambda t: jax.tree.map(lambda a: s(a.shape, a.dtype), t)  # noqa: E731
    chunk = {"decode": 1, "prefill": 32, "verify": 4}[phase]
    toks, mask = jnp.zeros((rows, chunk), I32), jnp.ones((rows, chunk), bool)
    # decode steps the rows' (here empty) recurrent state; chunks start it
    rows_state = None if chunk > 1 else jax.tree.map(
        lambda a: jnp.zeros((rows,) + a.shape[1:], a.dtype), states)

    def fn(p, pl, t, past, ts):
        out = forward_paged(p, cfg, toks, pl, rows_state, t, past, mask, ts,
                            all_logits=phase == "verify")
        return out[:3] if phase == "prefill" else out

    hlo = jax.jit(fn, donate_argnums=1).lower(
        place(params), place(pools), s((rows, width), I32), s((rows,), I32),
        place(tiers)).compile().as_text()
    return hlo, jax.tree.leaves(pools)


@pytest.mark.parametrize("phase", ["decode", "prefill", "verify"])
def test_paged_steps_update_the_kv_pool_in_place(monkeypatch, s, phase):
    """With the pools donated, as the engine donates them, the paged
    decode, prefill and verify programs alias the pool input to the
    output and copy no whole stacked pool: the scan carries the pools
    and writes each group's back in place. (As a scan output the new
    pools were a fresh buffer, and donation cost one whole-pool copy per
    step.)"""
    hlo, pools = _paged_program(monkeypatch, s, phase, rows=4, width=16)
    assert "input_output_alias" in hlo and "tpu_custom_call" in hlo
    whole = {f"bf16[{','.join(map(str, a.shape))}]" for a in pools}
    outs = [m.group(1) for m in re.finditer(r"= (.*?) copy(?:-start)?\(", hlo)]
    copies = [o for o in outs if any(w in o for w in whole)]
    assert not copies, copies[:3]


@pytest.mark.parametrize("phase,rows,ops", [
    ("decode", 64, (4, 12, 4)),
    ("prefill", 4, (7, 11, 4)),
    ("verify", 64, (7, 12, 4)),
])
def test_paged_programs_keep_their_device_ops(monkeypatch, s, phase, rows, ops):
    """Decode, prefill and verify are one paged forward, and each phase
    compiles to the device work its own entry point compiled to before
    they merged: the same number of Pallas calls (attention and expert
    FFNs), scatters (K/V writes and expert dispatch) and relayout copies
    of a group's pool in the kernel's lane view, [N+1, 4, 4, 128]. The
    shapes are decode-long's: 64 slots of 1152 tokens (18433 blocks
    with the trash block), tables cut to 64 columns."""
    hlo, pools = _paged_program(monkeypatch, s, phase, rows=rows, width=64,
                                slots=64, ctx=1152)
    lane = f"bf16[{pools[0].shape[1]},{BS},{KV // 2},{2 * HD}]"
    copies = [m for m in re.finditer(r"= (\S+) copy(?:-start)?\(", hlo)
              if m.group(1).startswith(lane)]
    assert (
        hlo.count('custom_call_target="tpu_custom_call"'),
        len(re.findall(r"= [^=]*? scatter\(", hlo)),
        len(copies),
    ) == ops
