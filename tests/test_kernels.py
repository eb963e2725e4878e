"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs. pure-jnp oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.decode_attention import decode_attention as decode_kernel
from repro.kernels.flash_attention import flash_attention as flash_kernel
from repro.kernels.lww_merge import lww_merge as lww_kernel
from repro.kernels.lww_merge import lww_merge_many as lww_many_kernel
from repro.kernels.rglru_scan import rglru_scan as rglru_kernel
from repro.kernels.ssd_scan import ssd_scan as ssd_kernel
from repro.kernels.vector_clock import causal_merge, vc_join_classify

RNG = np.random.default_rng(42)


def _rand(shape, dtype):
    x = RNG.normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype)


# ---------------------------------------------------------------------------
# lattice merge kernels
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("K,D", [(8, 128), (16, 256), (32, 512), (64, 1024)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_lww_merge_sweep(K, D, dtype):
    ca = jnp.asarray(RNG.integers(0, 100, (K, 1)), jnp.int32)
    na = jnp.asarray(RNG.integers(0, 8, (K, 1)), jnp.int32)
    cb = jnp.asarray(RNG.integers(0, 100, (K, 1)), jnp.int32)
    nb = jnp.asarray(RNG.integers(0, 8, (K, 1)), jnp.int32)
    va, vb = _rand((K, D), dtype), _rand((K, D), dtype)
    out = lww_kernel(ca, na, va, cb, nb, vb, interpret=True)
    exp = ref.lww_merge_ref(ca, na, va, cb, nb, vb)
    for o, e in zip(out, exp):
        np.testing.assert_allclose(np.asarray(o, np.float32),
                                   np.asarray(e, np.float32))


@pytest.mark.parametrize("R", [2, 3, 7])
def test_lww_merge_many_sweep(R):
    K, D = 16, 256
    cs = jnp.asarray(RNG.integers(0, 100, (R, K, 1)), jnp.int32)
    ns = jnp.asarray(RNG.integers(0, 8, (R, K, 1)), jnp.int32)
    vs = _rand((R, K, D), jnp.float32)
    out = lww_many_kernel(cs, ns, vs, interpret=True)
    exp = ref.lww_merge_many_ref(cs, ns, vs)
    for o, e in zip(out, exp):
        np.testing.assert_allclose(np.asarray(o), np.asarray(e))


@pytest.mark.parametrize("D", [640, 768])
def test_lww_merge_many_payload_not_multiple_of_block(D):
    """D % 128 == 0 but D % 512 != 0: the shape guard in ``ops`` sends
    such payloads to the Pallas kernel on a TPU, so the kernel must tile
    them.  Kernel and ``ops.lww_merge_many`` both match the jnp reference
    bit for bit."""
    R, K = 3, 16
    cs = jnp.asarray(RNG.integers(0, 100, (R, K, 1)), jnp.int32)
    ns = jnp.asarray(RNG.integers(0, 8, (R, K, 1)), jnp.int32)
    vs = _rand((R, K, D), jnp.float32)
    exp = ref.lww_merge_many_ref(cs, ns, vs)
    for out in (lww_many_kernel(cs, ns, vs, interpret=True),
                ops.lww_merge_many(cs, ns, vs)):
        for o, e in zip(out, exp):
            np.testing.assert_array_equal(
                np.asarray(o).view(np.uint32), np.asarray(e).view(np.uint32))


@pytest.mark.parametrize("K,N", [(8, 4), (32, 16), (64, 64)])
def test_vc_join_classify_sweep(K, N):
    a = jnp.asarray(RNG.integers(0, 6, (K, N)), jnp.int32)
    b = jnp.asarray(RNG.integers(0, 6, (K, N)), jnp.int32)
    join, adom, bdom = vc_join_classify(a, b, interpret=True)
    ej, ea, eb = ref.vc_join_classify_ref(a, b)
    np.testing.assert_array_equal(np.asarray(join), np.asarray(ej))
    np.testing.assert_array_equal(np.asarray(adom).ravel(), np.asarray(ea).ravel())
    np.testing.assert_array_equal(np.asarray(bdom).ravel(), np.asarray(eb).ravel())


def test_causal_merge_matches_ref():
    K, N, D = 16, 8, 128
    va, vb = _rand((K, D), jnp.float32), _rand((K, D), jnp.float32)
    a = jnp.asarray(RNG.integers(0, 4, (K, N)), jnp.int32)
    b = jnp.asarray(RNG.integers(0, 4, (K, N)), jnp.int32)
    out = causal_merge(a, va, b, vb, interpret=True)
    exp = ref.causal_merge_ref(a, va, b, vb)
    for o, e in zip(out, exp):
        np.testing.assert_allclose(np.asarray(o), np.asarray(e))


def test_causal_merge_kernel_matches_python_lattice():
    """Kernel dominance semantics == CausalLattice dominance semantics."""
    from repro.core.lattices import VectorClock
    K, N = 8, 4
    a = jnp.asarray(RNG.integers(0, 3, (K, N)), jnp.int32)
    b = jnp.asarray(RNG.integers(0, 3, (K, N)), jnp.int32)
    _, adom, bdom = vc_join_classify(a, b, interpret=True)
    for i in range(K):
        va = VectorClock({f"n{j}": int(a[i, j]) for j in range(N)})
        vb = VectorClock({f"n{j}": int(b[i, j]) for j in range(N)})
        assert bool(adom[i, 0]) == va.dominates(vb)
        assert bool(bdom[i, 0]) == vb.dominates(va)


# ---------------------------------------------------------------------------
# attention kernels
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,Hq,Hkv,T,S,Dh", [
    (1, 4, 4, 128, 128, 64),     # MHA
    (2, 8, 2, 128, 128, 64),     # GQA 4:1
    (1, 4, 1, 256, 256, 32),     # MQA
    (1, 2, 2, 128, 256, 64),     # cross (T != S)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, Hq, Hkv, T, S, Dh, dtype):
    q = _rand((B, Hq, T, Dh), dtype)
    k = _rand((B, Hkv, S, Dh), dtype)
    v = _rand((B, Hkv, S, Dh), dtype)
    causal = T == S
    out, _lse = flash_kernel(q, k, v, causal=causal, window=None,
                             block_q=64, block_kv=64, interpret=True)
    exp = ref.attention_ref(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [32, 128])
def test_flash_attention_sliding_window(window):
    B, H, T, Dh = 1, 2, 256, 32
    q, k, v = (_rand((B, H, T, Dh), jnp.float32) for _ in range(3))
    out, _ = flash_kernel(q, k, v, causal=True, window=window,
                          block_q=64, block_kv=64, interpret=True)
    exp = ref.attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-5, rtol=2e-5)


def test_flash_lse_matches_ref():
    B, H, T, Dh = 1, 2, 128, 32
    q, k, v = (_rand((B, H, T, Dh), jnp.float32) for _ in range(3))
    _, lse = flash_kernel(q, k, v, causal=True, window=None,
                          block_q=64, block_kv=64, interpret=True)
    kk = k
    s = jnp.einsum("bhtd,bhsd->bhts", q, kk) / (Dh ** 0.5)
    mask = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(mask[None, None], s, -1e30)
    exp = jax.nn.logsumexp(s, axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(exp),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("B,Hq,Hkv,S,Dh,bs", [
    (2, 4, 2, 256, 64, 64),
    (1, 8, 1, 512, 32, 128),
    (3, 6, 3, 128, 64, 128),
])
def test_decode_attention_sweep(B, Hq, Hkv, S, Dh, bs):
    q = _rand((B, Hq, Dh), jnp.float32)
    k = _rand((B, Hkv, S, Dh), jnp.float32)
    v = _rand((B, Hkv, S, Dh), jnp.float32)
    lengths = jnp.asarray(RNG.integers(1, S + 1, (B,)), jnp.int32)
    out = decode_kernel(q, k, v, lengths, block_kv=bs, interpret=True)
    exp = ref.decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# recurrence kernels
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,T,D,chunk,bd", [
    (2, 128, 256, 32, 128), (1, 256, 64, 64, 64), (3, 64, 128, 64, 128),
])
def test_rglru_scan_sweep(B, T, D, chunk, bd):
    a = jnp.asarray(RNG.uniform(0.4, 0.99, (B, T, D)), jnp.float32)
    u = _rand((B, T, D), jnp.float32)
    h0 = _rand((B, D), jnp.float32)
    y, hT = rglru_kernel(a, u, h0, chunk=chunk, block_d=bd, interpret=True)
    ye, hTe = ref.rglru_scan_ref(a, u, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ye), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hTe), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("B,T,H,P,G,N,chunk", [
    (2, 64, 4, 32, 2, 16, 16),
    (1, 128, 8, 64, 1, 32, 32),
    (1, 32, 2, 16, 2, 8, 8),
])
def test_ssd_scan_sweep(B, T, H, P, G, N, chunk):
    x = _rand((B, T, H, P), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, (B, T, H)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2.0, (H,)), jnp.float32)
    Bm = _rand((B, T, G, N), jnp.float32)
    Cm = _rand((B, T, G, N), jnp.float32)
    h0 = _rand((B, H, N, P), jnp.float32) * 0.1
    y, hT = ssd_kernel(x, dt, A, Bm, Cm, h0, chunk=chunk, interpret=True)
    ye, hTe = ref.ssd_scan_ref(x, dt, A, Bm, Cm, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ye), atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hTe), atol=2e-3, rtol=2e-3)


def test_ssd_chunked_jnp_matches_ref():
    """The differentiable chunked mirror (used by the VJP) is also correct."""
    from repro.kernels.ops import _ssd_chunked_jnp
    B, T, H, P, G, N = 1, 64, 4, 16, 1, 8
    x = _rand((B, T, H, P), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, (B, T, H)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2.0, (H,)), jnp.float32)
    Bm = _rand((B, T, G, N), jnp.float32)
    Cm = _rand((B, T, G, N), jnp.float32)
    h0 = _rand((B, H, N, P), jnp.float32) * 0.1
    y, hT = _ssd_chunked_jnp(x, dt, A, Bm, Cm, h0, 16)
    ye, hTe = ref.ssd_scan_ref(x, dt, A, Bm, Cm, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ye), atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hTe), atol=2e-3, rtol=2e-3)


# ---------------------------------------------------------------------------
# gradients through the ops layer (custom VJPs vs. reference autodiff)
# ---------------------------------------------------------------------------


def test_flash_gradients_match_reference():
    B, Hq, Hkv, T, Dh = 1, 4, 2, 128, 32
    q = _rand((B, Hq, T, Dh), jnp.float32)
    k = _rand((B, Hkv, T, Dh), jnp.float32)
    v = _rand((B, Hkv, T, Dh), jnp.float32)
    g = _rand((B, Hq, T, Dh), jnp.float32)

    def fk(q, k, v):
        return jnp.vdot(ops.flash_attention(q, k, v, causal=True,
                                            block_q=32, block_kv=32), g)

    def fr(q, k, v):
        return jnp.vdot(ref.attention_ref(q, k, v, causal=True), g)

    gk = jax.grad(fk, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-4, rtol=3e-4)


def test_rglru_gradients_match_reference():
    B, T, D = 2, 64, 32
    a = jnp.asarray(RNG.uniform(0.5, 0.95, (B, T, D)), jnp.float32)
    u = _rand((B, T, D), jnp.float32)
    h0 = _rand((B, D), jnp.float32)
    gy, ghT = _rand((B, T, D), jnp.float32), _rand((B, D), jnp.float32)

    def fk(a, u, h0):
        y, hT = ops.rglru_scan(a, u, h0, chunk=16, block_d=16)
        return jnp.vdot(y, gy) + jnp.vdot(hT, ghT)

    def fr(a, u, h0):
        y, hT = ref.rglru_scan_ref(a, u, h0)
        return jnp.vdot(y, gy) + jnp.vdot(hT, ghT)

    gk = jax.grad(fk, argnums=(0, 1, 2))(a, u, h0)
    gr = jax.grad(fr, argnums=(0, 1, 2))(a, u, h0)
    for x, y in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   atol=2e-4, rtol=2e-4)


def test_ssd_gradients_match_reference():
    B, T, H, P, G, N = 1, 32, 2, 16, 1, 8
    x = _rand((B, T, H, P), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, (B, T, H)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2.0, (H,)), jnp.float32)
    Bm, Cm = _rand((B, T, G, N), jnp.float32), _rand((B, T, G, N), jnp.float32)
    h0 = _rand((B, H, N, P), jnp.float32) * 0.1
    gy, ghT = _rand((B, T, H, P), jnp.float32), _rand((B, H, N, P), jnp.float32)

    def fk(*args):
        y, hT = ops.ssd_scan(*args, chunk=8)
        return jnp.vdot(y, gy) + jnp.vdot(hT, ghT)

    def fr(*args):
        y, hT = ref.ssd_scan_ref(*args)
        return jnp.vdot(y, gy) + jnp.vdot(hT, ghT)

    gk = jax.grad(fk, argnums=tuple(range(6)))(x, dt, A, Bm, Cm, h0)
    gr = jax.grad(fr, argnums=tuple(range(6)))(x, dt, A, Bm, Cm, h0)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-3, rtol=2e-3)
