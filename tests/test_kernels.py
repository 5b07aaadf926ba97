"""Per-kernel shape/dtype sweeps vs the ref.py pure-jnp oracles
(interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
from repro.kernels.rmsnorm import ops as rms_ops, ref as rms_ref
from repro.kernels.ssm_scan import ops as ssm_ops, ref as ssm_ref


def tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


def _qkv(key, B, S, H, Hkv, hd, dtype=jnp.float32):
    """q with H heads and k, v with Hkv (GQA), all (B, S, heads, hd)."""
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, hd)).astype(dtype)
    k, v = (jax.random.normal(kx, (B, S, Hkv, hd)).astype(dtype)
            for kx in (kk, kv))
    return q, k, v


def _flash_ref(q, k, v, causal):
    """``ref.attention_ref`` on K/V repeated to q's heads."""
    rep = q.shape[2] // k.shape[2]
    tr = lambda t: t.transpose(0, 2, 1, 3)
    k, v = (jnp.repeat(t, rep, axis=2) for t in (k, v))
    return tr(fa_ref.attention_ref(tr(q), tr(k), tr(v), causal=causal))


#: (B, H, Hkv, S, hd): GQA groups 1, 3 and 4
_FLASH_SHAPES = [(1, 1, 1, 128, 64), (2, 3, 3, 256, 64), (1, 2, 2, 512, 128),
                 (2, 1, 1, 128, 32), (1, 9, 3, 1024, 64), (2, 4, 1, 512, 64)]
#: (block_q, block_kv): small tiles, and the tile knobs' domain
#: (128, 256, 512) with block_q != block_kv
_FLASH_BLOCKS = [(64, 64), (128, 128), (64, 128), (256, 256), (512, 512),
                 (128, 512), (512, 256)]


@pytest.mark.parametrize("B,H,Hkv,S,hd,bq,bk", [
    s + b for s in _FLASH_SHAPES for b in _FLASH_BLOCKS
    if s[3] % b[0] == 0 and s[3] % b[1] == 0])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, H, Hkv, S, hd, bq, bk, dtype):
    q, k, v = _qkv(jax.random.PRNGKey(S + hd), B, S, H, Hkv, hd, dtype)
    out = fa_ops.flash_attention(q, k, v, causal=True, block_q=bq,
                                 block_kv=bk)
    ref = _flash_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol(dtype))


def test_flash_attention_split_group(monkeypatch):
    """A GQA group that overruns the VMEM budget is split: each grid
    step then takes part of a KV head's query heads, still against
    that KV head."""
    from repro.kernels.flash_attention import flash_attention as fa_kernel
    assert fa_kernel.head_group(3, 128, 128, 64, jnp.bfloat16) == 3
    assert fa_kernel.head_group(9, 512, 512, 64, jnp.bfloat16) == 3
    monkeypatch.setattr(fa_kernel, "VMEM_BUDGET", 0)
    assert fa_kernel.head_group(3, 128, 128, 64, jnp.bfloat16) == 1
    # a shape no other test compiles, so the jit cache holds no unsplit
    # kernel for it
    q, k, v = _qkv(jax.random.PRNGKey(11), 1, 256, 6, 2, 32)
    out = fa_ops.flash_attention(q, k, v, causal=True, block_q=128,
                                 block_kv=64)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_flash_ref(q, k, v, True)),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("S,bq,bk", [(2048, 128, 128), (1024, 128, 512),
                                     (1024, 512, 128), (384, 128, 256),
                                     (304, 304, 16)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_pair_table(S, bq, bk, causal):
    """The grid walks exactly the live (q tile, kv tile) pairs, each q
    tile's run consecutive from kv tile 0 and ending on its diagonal
    (every kv tile when not causal)."""
    from repro.kernels.flash_attention.flash_attention import (
        pair_table, unpack)
    nq, nk = S // bq, S // bk
    qi, kj, last = unpack(pair_table(nq, nk, bq, bk, causal))
    live = [(i, j) for i in range(nq) for j in range(nk)
            if not causal or j * bk <= i * bq + bq - 1]
    assert sorted(zip(qi.tolist(), kj.tolist())) == live
    for i in range(nq):
        run = np.flatnonzero(qi == i)
        end = min((i * bq + bq - 1) // bk, nk - 1) if causal else nk - 1
        assert run.tolist() == list(range(run[0], run[0] + end + 1))
        assert kj[run].tolist() == list(range(end + 1))
        assert last[run].tolist() == [0] * end + [1]
    if (S, bq, bk, causal) == (2048, 128, 128, True):
        assert len(qi) == 136         # of 256 tiles


@pytest.mark.parametrize("shape", [(8, 64), (3, 37, 512), (2, 4, 16, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(sum(shape)))
    x = jax.random.normal(k1, shape).astype(dtype)
    s = (jax.random.normal(k2, shape[-1:]) * 0.1 + 1.0).astype(dtype)
    out = rms_ops.rmsnorm(x, s)
    ref = rms_ref.rmsnorm_ref(x, s)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol(dtype))


@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (1, 64, 2, 16, 8, 16), (2, 128, 4, 32, 16, 32), (1, 256, 1, 64, 64, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssm_scan_sweep(B, S, H, P, N, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(S * H), 5)
    X = jax.random.normal(ks[0], (B, S, H, P)).astype(dtype)
    Bm = (jax.random.normal(ks[1], (B, S, N)) * 0.5).astype(dtype)
    Cm = (jax.random.normal(ks[2], (B, S, N)) * 0.5).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)))
    la = -dt * jnp.exp(jax.random.normal(ks[4], (H,)) * 0.2)[None, None]
    Y, h = ssm_ops.ssm_scan(X, Bm, Cm, dt, la, chunk=chunk)
    Yr, hr = ssm_ref.ssm_scan_ref(X, Bm, Cm, dt, la)
    t = dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 \
        else dict(atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(Y, np.float32),
                               np.asarray(Yr, np.float32), **t)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), **t)


@pytest.mark.parametrize("B,H,Hkv,S,hd,length", [
    (1, 4, 4, 128, 64, 128), (2, 8, 2, 256, 64, 200),
    (1, 16, 16, 512, 128, 33)])
@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_flash_decode_sweep(B, H, Hkv, S, hd, length, kv_dtype):
    from repro.kernels.flash_decode import ops as fd, ref as fd_ref
    from repro.models.layers import quantize_kv
    ks = jax.random.split(jax.random.PRNGKey(S + H), 3)
    q = jax.random.normal(ks[0], (B, 1, H, hd))
    kc = jax.random.normal(ks[1], (B, S, Hkv, hd))
    vc = jax.random.normal(ks[2], (B, S, Hkv, hd))
    kq, ksc = quantize_kv(kc, kv_dtype)
    vq, vsc = quantize_kv(vc, kv_dtype)
    out = fd.flash_decode(q, kq, vq, length, ksc, vsc, block_kv=64)
    tr = lambda t: t.transpose(0, 2, 1, 3) if t is not None else None
    ref = fd_ref.decode_ref(tr(q), tr(kq), tr(vq), tr(ksc), tr(vsc),
                            jnp.array([length])).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("H,Hkv,S,bq,bk", [
    (2, 2, 128, 128, 128), (9, 3, 512, 128, 512), (4, 1, 512, 256, 128),
    (9, 3, 300, 128, 128)])          # ragged: a padded kv_len tail
def test_flash_attention_noncausal(H, Hkv, S, bq, bk):
    q, k, v = _qkv(jax.random.PRNGKey(7), 2, S, H, Hkv, 64)
    out = fa_ops.flash_attention(q, k, v, causal=False, block_q=bq,
                                 block_kv=bk)
    ref = _flash_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# ------------------------------------------------- ragged shapes (PR 7)
# The public wrappers fit any requested block to the dtype's tile
# (kernels/tiling.py) and pad a length with no aligned divisor —
# sequence lengths that are NOT a multiple of the tile must stay
# correct, not assert-crash, and Mosaic only ever sees aligned blocks.

def test_fit_block():
    from repro.kernels.tiling import fit_block, fit_blocks
    assert fit_block(128, 256) == 128      # divides: identity
    assert fit_block(512, 256) == 256      # clamp to n
    assert fit_block(128, 192) == 96       # largest aligned divisor
    assert fit_block(128, 97) == 104       # one block, rounded up to 8
    assert fit_block(64, 97) == 64         # no aligned divisor: pad
    assert fit_block(0, 64) == 8           # request floors at the tile
    assert fit_block(128, 300, align=16) == 128
    assert fit_block(128, 7, align=16) == 16
    for align in (8, 16, 32):
        for n in (1, 7, 97, 100, 192, 300, 1003, 4096):
            for block in (0, 64, 100, 128, 512):
                b = fit_block(block, n, align)
                assert b % align == 0 and b >= align
    assert fit_blocks(192, (128, 128), 8) == (192, (96, 96))
    assert fit_blocks(300, (128, 64), 16) == (384, (128, 64))
    assert fit_blocks(300, (256, 64), 16) == (512, (256, 64))
    assert fit_blocks(100, (128, 128), 16) == (112, (112, 112))


@pytest.mark.parametrize("S", [192, 96, 300])
@pytest.mark.parametrize("blocks", [(128, 128), (256, 64)])
@pytest.mark.parametrize("H,Hkv", [(2, 2), (9, 3)])
def test_flash_attention_ragged(S, blocks, H, Hkv):
    bq, bk = blocks
    q, k, v = _qkv(jax.random.PRNGKey(S), 1, S, H, Hkv, 64)
    out = fa_ops.flash_attention(q, k, v, causal=True, block_q=bq,
                                 block_kv=bk)
    ref = _flash_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("rows", [111, 5])
def test_rmsnorm_ragged_rows(rows):
    k1, k2 = jax.random.split(jax.random.PRNGKey(rows))
    x = jax.random.normal(k1, (rows, 64))
    s = jax.random.normal(k2, (64,)) * 0.1 + 1.0
    out = rms_ops.rmsnorm(x, s, block_rows=256)
    ref = rms_ref.rmsnorm_ref(x, s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("S,chunk", [(96, 64), (130, 32)])
def test_ssm_scan_ragged(S, chunk):
    B, H, P, N = 1, 2, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(S), 5)
    X = jax.random.normal(ks[0], (B, S, H, P))
    Bm = jax.random.normal(ks[1], (B, S, N)) * 0.5
    Cm = jax.random.normal(ks[2], (B, S, N)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)))
    la = -dt * jnp.exp(jax.random.normal(ks[4], (H,)) * 0.2)[None, None]
    Y, h = ssm_ops.ssm_scan(X, Bm, Cm, dt, la, chunk=chunk)
    Yr, hr = ssm_ref.ssm_scan_ref(X, Bm, Cm, dt, la)
    np.testing.assert_allclose(np.asarray(Y), np.asarray(Yr),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                               atol=1e-4, rtol=1e-4)


def test_flash_decode_ragged_cache():
    B, H, Hkv, S, hd, length = 1, 4, 2, 192, 64, 150
    ks = jax.random.split(jax.random.PRNGKey(S), 3)
    q = jax.random.normal(ks[0], (B, 1, H, hd))
    kc = jax.random.normal(ks[1], (B, S, Hkv, hd))
    vc = jax.random.normal(ks[2], (B, S, Hkv, hd))
    from repro.kernels.flash_decode import ops as fd, ref as fd_ref
    out = fd.flash_decode(q, kc, vc, length, block_kv=128)  # fit -> 96
    tr = lambda t: t.transpose(0, 2, 1, 3)
    ref = fd_ref.decode_ref(tr(q), tr(kc), tr(vc), None, None,
                            jnp.array([length])).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-2, rtol=2e-2)
