"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Interpret mode on the CPU accepts block shapes and primitives that the
chip's compiler (Mosaic) refuses.  These tests compile each kernel for a
described, not attached, v5e chip with the TPU compiler installed here,
and check that the program holds the kernel (``tpu_custom_call``).  They
say nothing about results or speed.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker imports
this file.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.lww_merge import lww_merge_many
from repro.kernels.ssd_scan import ssd_scan
from repro.kernels.vector_clock import vc_join_classify
from repro.models import get_config

LLAMA = get_config("llama3.2-3b")
MAMBA = get_config("mamba2-1.3b").ssm
i32, f32, bf16 = jnp.int32, jnp.float32, jnp.bfloat16


def _attn(T, S):
    return [((1, LLAMA.n_heads, T, LLAMA.head_dim), bf16),
            ((1, LLAMA.n_kv_heads, S, LLAMA.head_dim), bf16),
            ((1, LLAMA.n_kv_heads, S, LLAMA.head_dim), bf16)]


def _decode(B, S):
    return [((B, LLAMA.n_heads, LLAMA.head_dim), bf16),
            ((B, LLAMA.n_kv_heads, S, LLAMA.head_dim), bf16),
            ((B, LLAMA.n_kv_heads, S, LLAMA.head_dim), bf16),
            ((B,), i32)]


def _ssd(T):
    H, P, N, G = (MAMBA.n_heads, MAMBA.head_dim, MAMBA.state_dim,
                  MAMBA.n_groups)
    return [((1, T, H, P), bf16), ((1, T, H), bf16), ((H,), f32),
            ((1, T, G, N), bf16), ((1, T, G, N), bf16),
            ((1, H, N, P), bf16)]


# name -> (kernel, [(shape, dtype)] of its arguments)
CASES = {
    "lww_merge_many_f32": (lww_merge_many, [
        ((2, 1024, 1), i32), ((2, 1024, 1), i32), ((2, 1024, 512), f32)]),
    "lww_merge_many_bf16": (lww_merge_many, [
        ((2, 1024, 1), i32), ((2, 1024, 1), i32), ((2, 1024, 2048), bf16)]),
    "lww_merge_many_d640": (lww_merge_many, [
        ((2, 1024, 1), i32), ((2, 1024, 1), i32), ((2, 1024, 640), f32)]),
    "vc_join_classify": (vc_join_classify, [((1024, 8), i32), ((1024, 8), i32)]),
    "flash_attention_t64": (flash_attention, _attn(64, 64)),
    "flash_attention_t128": (flash_attention, _attn(128, 128)),
    "decode_attention_s160": (decode_attention, _decode(8, 160)),
    "decode_attention_s1024": (decode_attention, _decode(8, 1024)),
    "ssd_scan": (functools.partial(ssd_scan, chunk=MAMBA.chunk), _ssd(256)),
}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip can be written to the
    # persistent cache but never read back here: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    kernel, args = CASES[name]
    specs = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
             for shape, dtype in args]
    fn = functools.partial(kernel, interpret=False)
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()
