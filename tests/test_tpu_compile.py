"""Compile the serving path's Pallas kernels for a TPU v5e, without one.

The TPU compiler is installed even where no chip is attached: it
compiles for a described `v5e:2x2` topology and raises what the chip's
compiler would raise (unaligned block shapes, too much VMEM), which
interpret-mode tests cannot show. Shapes are granite-moe-1b-a400m's
published widths and the serving loop's defaults: Kv=8 heads of 64 with
G=2 query heads each, 4-token KV blocks, 4-row prefill chunks of 32
tokens, decode groups of 4 rows, d_model 1024 and experts of width 512.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.expert_gemv.expert_gemv import expert_ffn_gemv
from repro.kernels.moe_gemm.moe_gemm import moe_gemm
from repro.kernels.paged_attention import paged_prefill_gqa, paged_prefill_mla

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
KV, G, HD, BS = 8, 2, 64, 4  # granite kv heads, group, head dim; loop block
D, F = 1024, 512             # granite d_model, d_expert
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


@pytest.mark.parametrize("chunk", [1, 32])
def test_paged_prefill_gqa_compiles(s, chunk):
    """Decode (chunk 1) and prefill chunks through the one GQA kernel."""
    n = ROWS * NB + 1
    compiled = paged_prefill_gqa.lower(
        s((ROWS, chunk, KV, G, HD), BF16), s((n, BS, KV, HD), BF16),
        s((n, BS, KV, HD), BF16), s((ROWS, NB), I32), s((ROWS,), I32),
        s((ROWS,), I32), interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


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


@pytest.mark.parametrize("k_dim,n_dim", [(D, 2 * F), (F, D)],
                         ids=["gate_up", "down"])
def test_moe_gemm_compiles(s, k_dim, n_dim):
    """The two grouped GEMMs of `grouped_expert_ffn` on prefill buffers:
    8 hot-tier experts x 128 rows (4 rows x 32-token chunk)."""
    e, t = 8, 8 * 128
    compiled = moe_gemm.lower(
        s((t, k_dim), BF16), s((e, k_dim, n_dim), BF16), s((t // 128,), I32),
        interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("tokens", [4, 8])
def test_expert_ffn_gemv_compiles(s, tokens):
    """One expert's decode buffer in `cold_expert_ffn`: a 4-row decode
    group (batch 8, 2 groups) and a full 8-row batch."""
    compiled = expert_ffn_gemv.lower(
        s((tokens, D), BF16), s((D, F), BF16), s((D, F), BF16),
        s((F, D), BF16), interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
