"""The served path's Pallas kernels compile for a TPU v5e.

Interpret mode (every other test here) runs kernel bodies as XLA ops and
never meets Mosaic's layout rules: a block whose last two dims are
neither the array's own nor (8, 128)-aligned, or a kernel that asks for
too much VMEM, only fails when the kernel is compiled for the chip. The
TPU compiler ships with jaxlib and compiles for a *described* v5e:2x2
topology with no chip attached, so these tests lower and compile each
kernel at real model widths — about two seconds each — and guard every
later change at no chip time. Nothing runs; results are not checked.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and a collection-time load
would give parallel test workers different test sets. This process
runs JAX on the CPU, where ``interpret=None`` would resolve to interpret
mode, so the kernels are asked for the compiled lowering explicitly.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# (heads, kv_heads, head_dim) of the served configs
QWEN2_0_5B = (14, 2, 64)
MINITRON_8B = (32, 8, 128)
STABLELM_1_6B = (32, 32, 64)
NEMOTRON_4_15B = (48, 8, 128)
BATCH, BLOCK, MAX_SEQ = 8, 16, 544


@pytest.fixture(scope="module")
def one_chip():
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")    # no compiler logs outside
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # entries compiled for a described chip cannot be read back without
    # one; keep them out of any persistent cache the environment set.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    mp.undo()


def _compile(fn, sharding, *shapes):
    """AOT-compile ``fn`` for the described chip from (shape, dtype)s."""
    args = [jax.ShapeDtypeStruct(s, jnp.dtype(d), sharding=sharding)
            for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("heads,c,pages", [
    (QWEN2_0_5B, 1, "bfloat16"),
    (QWEN2_0_5B, 16, "bfloat16"),
    (QWEN2_0_5B, 1, "int8"),
    (QWEN2_0_5B, 16, "int8"),
    (MINITRON_8B, 1, "bfloat16"),
    (STABLELM_1_6B, 1, "bfloat16"),
    (STABLELM_1_6B, 256, "bfloat16"),
    (MINITRON_8B, 256, "bfloat16"),
    (NEMOTRON_4_15B, 256, "bfloat16"),
], ids=["qwen2-decode-bf16", "qwen2-prefill-bf16", "qwen2-decode-int8",
        "qwen2-prefill-int8", "minitron-decode-bf16", "stablelm-decode-bf16",
        "stablelm-prefill256-bf16", "minitron-prefill256-bf16",
        "nemotron-prefill256-bf16"])
def test_paged_attention_compiles(one_chip, heads, c, pages):
    """Decode (C=1) and prefill-chunk (C=16, 256) paged E2Softmax
    attention over a head-major page pool, bf16 and int8 pages (the int8
    pool dequantizes in-kernel and keeps V as codes through P·V). Every
    head folds into one grid step at decode; 256-row chunks split the
    heads into blocks that fit VMEM, which an overflow here would show."""
    from repro.kernels.flash_e2softmax import (  # repro: noqa RPR001
        flash_e2softmax_paged)
    from repro.models.layers import KV_INT8_SCALE
    h, kvh, d = heads
    nb = -(-MAX_SEQ // BLOCK)
    n = BATCH * nb + 1
    int8 = pages == "int8"
    fn = functools.partial(
        flash_e2softmax_paged, causal=c > 1, sole=True,
        interpret=False,  # repro: noqa RPR002
        kv_scale=KV_INT8_SCALE if int8 else None, quant_pv=int8)
    _compile(fn, one_chip, ((BATCH, h, c, d), "bfloat16"),
             ((n, kvh, BLOCK, d), pages), ((n, kvh, BLOCK, d), pages),
             ((BATCH, nb), "int32"), ((BATCH, 2), "int32"))


@pytest.mark.parametrize("c", [1, 16], ids=["decode", "prefill"])
def test_paged_attention_head_map_compiles(one_chip, c):
    """The tensor-parallel replicated-KV fallback: a shard's 3 q heads of
    nemotron_4_15b (48/8 heads on a 16-way model axis) attend the full 8-head
    pool through an explicit q-head -> KV-head map."""
    from repro.kernels.flash_e2softmax import (  # repro: noqa RPR001
        flash_e2softmax_paged)
    _, kvh, d = NEMOTRON_4_15B
    nb = -(-MAX_SEQ // BLOCK)
    n = BATCH * nb + 1

    def fn(q, k, v, tables, meta, kvmap):
        return flash_e2softmax_paged(
            q, k, v, tables, meta, kv_head_map=kvmap, causal=c > 1,
            sole=True, interpret=False)  # repro: noqa RPR002
    _compile(fn, one_chip, ((BATCH, 3, c, d), "bfloat16"),
             ((n, kvh, BLOCK, d), "bfloat16"),
             ((n, kvh, BLOCK, d), "bfloat16"), ((BATCH, nb), "int32"),
             ((BATCH, 2), "int32"), ((3,), "int32"))


@pytest.mark.parametrize("rows,d", [(8, 896), (300, 896), (8, 4096)])
def test_fused_add_rmsnorm_compiles(one_chip, rows, d):
    """Fused residual add + AIRMSNorm at qwen2_0_5b (896) and
    minitron_8b (4096) widths; 300 rows pads to the 256-row block."""
    from repro.kernels.ailayernorm import (  # repro: noqa RPR001
        fused_add_norm_pallas)
    fn = functools.partial(fused_add_norm_pallas, rms=True,
                           interpret=False)  # repro: noqa RPR002
    _compile(fn, one_chip, ((rows, d), "bfloat16"), ((rows, d), "bfloat16"),
             ((d,), "float32"))


def test_int8_matmul_compiles(one_chip):
    """The W8A8 GEMM at qwen2_0_5b's MLP-up shape for a decode batch."""
    from repro.kernels.int8_matmul import (  # repro: noqa RPR001
        int8_matmul_pallas)
    fn = functools.partial(int8_matmul_pallas,
                           interpret=False)  # repro: noqa RPR002
    _compile(fn, one_chip, ((8, 896), "int8"), ((896, 4864), "int8"))
