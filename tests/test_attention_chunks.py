"""Causal XLA attention in query chunks (``layers.full_attention``).

Chunk [s, e) of the queries scores keys [0, e) only; the pairs above the
diagonal, which the one-shot computation masks to exp(-1e30 - m) = 0,
are never computed.  The chunked result and its gradients equal the
one-shot computation (``layers._attend`` over the whole masked square),
and every shape but causal self-attention over more than ``CHUNK_ROWS``
queries lowers one chunk; at most ``MAX_CHUNKS`` chunks, wider ones past
``MAX_CHUNKS * CHUNK_ROWS`` queries."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.params import default_config
from repro.models import layers as L

RT = default_config()
C = L.CHUNK_ROWS


def _qkv(S, dtype):
    ks = jax.random.split(jax.random.PRNGKey(S), 4)
    q, k, v = (jax.random.normal(kk, (2, S, 9, 32), dtype) for kk in ks[:3])
    w = jax.random.normal(ks[3], (2, S, 9, 32), jnp.float32)
    return q, k, v, w


def _one_shot(q, k, v):
    S = q.shape[1]
    return L._attend(q, k, v, jnp.arange(S)[:, None] >= jnp.arange(S)[None, :])


def _chunked(q, k, v):
    return L.full_attention(q, k, v, causal=True, rt=RT)


@pytest.mark.parametrize("S", [4 * C, 4 * C + 76], ids=["even", "ragged"])
@pytest.mark.parametrize("dtype,tol", [("float32", 5e-6),
                                       ("bfloat16", 2e-2)])
def test_chunked_attention_matches_one_shot(S, dtype, tol):
    """Values and the gradients of a scalar loss w.r.t. q, k and v, at a
    GQA-repeated shape (9 heads) where chunking engages."""
    chunks = L.query_chunks(S, S, causal=True)
    assert len(chunks) == -(-S // C) and chunks[0] == (0, C)
    assert chunks[-1][1] == S
    q, k, v, w = _qkv(S, jnp.dtype(dtype))

    def value_and_grads(fn):
        loss = lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * w)
        out = jax.jit(fn)(q, k, v)
        grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
        return (out,) + grads

    got, want = value_and_grads(_chunked), value_and_grads(_one_shot)
    for name, g, x in zip(("out", "dq", "dk", "dv"), got, want):
        g, x = np.asarray(g, np.float32), np.asarray(x, np.float32)
        scale = float(np.max(np.abs(x)))
        assert g.dtype == x.dtype and g.shape == x.shape
        np.testing.assert_allclose(g, x, atol=tol * scale, rtol=tol,
                                   err_msg=name)


def _dots_and_scopes(sq, sk, causal):
    q = jax.ShapeDtypeStruct((1, sq, 2, 16), jnp.float32)
    kv = jax.ShapeDtypeStruct((1, sk, 2, 16), jnp.float32)
    fn = lambda q, k, v: L.full_attention(q, k, v, causal=causal, rt=RT)
    lowered = jax.jit(fn).lower(q, kv, kv)
    dots = re.findall(r" dot\(", lowered.as_text(dialect="hlo"))
    products = [name for name in re.findall(r'op_name="([^"]*)"',
                                            lowered.compile().as_text())
                if "dot_general" in name]
    return len(dots), bool(products) and all(
        "/attention_scores/" in name for name in products)


@pytest.mark.parametrize("sq,sk,causal,chunks", [
    (4 * C, 4 * C, True, 4),
    (C, C, True, 1),
    (4 * C, 4 * C, False, 1),
    (4 * C, 5 * C, True, 1),
    (C + 1, C + 1, True, 2),
    ((L.MAX_CHUNKS + 1) * C, (L.MAX_CHUNKS + 1) * C, True,
     -(-(L.MAX_CHUNKS + 1) // 2)),
], ids=["engaged", "short", "non-causal", "cross", "two-chunks",
        "wide-chunks"])
def test_path_selection_lowers_the_chunks_it_names(sq, sk, causal, chunks):
    """Only causal self-attention over more than one chunk of queries is
    chunked, in chunks of ``CHUNK_ROWS`` rows up to ``MAX_CHUNKS`` chunks
    and of twice as many rows beyond; each chunk lowers its score and
    value products, named by the ``attention_scores`` scope."""
    assert len(L.query_chunks(sq, sk, causal=causal)) == chunks
    dots, named = _dots_and_scopes(sq, sk, causal)
    assert dots == 2 * chunks
    assert named
