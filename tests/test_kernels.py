"""Per-kernel validation: Pallas (interpret mode on CPU) vs pure-jnp oracle,
swept over shapes and dtypes (assignment requirement c).

Tolerances follow public kernel-test practice: fp32 rtol 1e-5-ish, bf16
rtol >= 1e-2 (long reductions).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import flash_decode
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.rwkv6_scan import wkv6
from repro.models.rwkv6 import wkv6_chunked, wkv6_serial
from repro.models.attention import chunked_attention


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

ATTN_SHAPES = [
    # (B, H, H_kv, S, D, block_q, block_k)
    (1, 2, 2, 64, 32, 16, 16),
    (2, 4, 2, 128, 64, 32, 64),   # GQA group 2, uneven blocks
    (1, 8, 1, 64, 16, 64, 16),    # MQA
    (2, 2, 2, 96, 32, 32, 32),    # S not a power of two
]


@pytest.mark.parametrize("shape", ATTN_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(shape, dtype, causal):
    B, H, H_kv, S, D, bq, bk = shape
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, H, S, D), dtype)
    k = jax.random.normal(ks[1], (B, H_kv, S, D), dtype)
    v = jax.random.normal(ks[2], (B, H_kv, S, D), dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                          interpret=True)
    expect = ref.ref_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **tol(dtype))


def test_chunked_attention_matches_ref():
    """The model's jnp streaming attention is bit-comparable to the oracle
    (it is the dry-run path, so it must be exact)."""
    ks = jax.random.split(jax.random.key(1), 3)
    B, H, H_kv, S, D = 2, 4, 2, 96, 32
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H_kv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H_kv, D), jnp.float32)
    out = chunked_attention(q, k, v, causal=True, q_block=32)
    expect = ref.ref_attention(q.transpose(0, 2, 1, 3),
                               k.transpose(0, 2, 1, 3),
                               v.transpose(0, 2, 1, 3), causal=True)
    np.testing.assert_allclose(np.asarray(out.transpose(0, 2, 1, 3)),
                               np.asarray(expect), rtol=2e-5, atol=2e-5)


def test_chunked_attention_window_matches_masked_ref():
    ks = jax.random.split(jax.random.key(2), 3)
    B, H, S, D, W = 1, 2, 64, 16, 8
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H, D), jnp.float32)
    out = chunked_attention(q, k, v, causal=True, window=W, q_block=16)
    # reference: full attention with band mask
    import math
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(D)
    idx = jnp.arange(S)
    mask = (idx[:, None] >= idx[None, :]) & (idx[:, None] - idx[None, :] < W)
    s = jnp.where(mask[None, None], s, -1e30)
    expect = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# flash decode
# ---------------------------------------------------------------------------

DECODE_SHAPES = [
    # (B, H, H_kv, S_max, D, block_k)
    (2, 4, 2, 128, 32, 32),
    (1, 8, 1, 256, 64, 64),
    (3, 4, 4, 64, 16, 16),
]


@pytest.mark.parametrize("shape", DECODE_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_matches_ref(shape, dtype):
    B, H, H_kv, S, D, bk = shape
    ks = jax.random.split(jax.random.key(3), 4)
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    k = jax.random.normal(ks[1], (B, H_kv, S, D), dtype)
    v = jax.random.normal(ks[2], (B, H_kv, S, D), dtype)
    cache_len = jax.random.randint(ks[3], (B,), 1, S + 1)
    out = flash_decode(q, k, v, cache_len, block_k=bk, interpret=True)
    expect = ref.ref_decode(q, k, v, cache_len)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **tol(dtype))


# ---------------------------------------------------------------------------
# RG-LRU scan
# ---------------------------------------------------------------------------

RGLRU_SHAPES = [
    (1, 64, 128, 32, 128),   # (B, S, D, chunk, block_d)
    (2, 128, 256, 64, 128),
    (2, 96, 128, 32, 64),
]


@pytest.mark.parametrize("shape", RGLRU_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rglru_scan_matches_ref(shape, dtype):
    B, S, D, chunk, bd = shape
    ks = jax.random.split(jax.random.key(4), 2)
    x = jax.random.normal(ks[0], (B, S, D), dtype)
    a = jax.random.uniform(ks[1], (B, S, D), jnp.float32, 0.5, 0.999).astype(dtype)
    out = rglru_scan(x, a, chunk=chunk, block_d=bd, interpret=True)
    expect = ref.ref_rglru(x, a)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **tol(dtype))


def test_rglru_assoc_scan_matches_serial():
    from repro.models.rglru import rglru_scan as assoc
    ks = jax.random.split(jax.random.key(5), 2)
    x = jax.random.normal(ks[0], (2, 77, 32), jnp.float32)
    a = jax.random.uniform(ks[1], (2, 77, 32), jnp.float32, 0.3, 0.99)
    h, h_last = assoc(x, a)
    expect = ref.ref_rglru(x, a)
    np.testing.assert_allclose(np.asarray(h), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h_last), np.asarray(expect[:, -1]),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# RWKV-6 WKV
# ---------------------------------------------------------------------------

WKV_SHAPES = [
    (1, 2, 64, 16, 16),   # (B, H, S, D, chunk)
    (2, 2, 96, 32, 32),
    (1, 4, 128, 64, 32),
]


def _wkv_inputs(shape, dtype):
    B, H, S, D, chunk = shape
    ks = jax.random.split(jax.random.key(6), 5)
    r = jax.random.normal(ks[0], (B, H, S, D), dtype)
    k = jax.random.normal(ks[1], (B, H, S, D), dtype)
    v = jax.random.normal(ks[2], (B, H, S, D), dtype)
    logw = -jnp.exp(jax.random.normal(ks[3], (B, H, S, D)) - 1.0)
    logw = jnp.maximum(logw, -5.0).astype(jnp.float32)
    u = (jax.random.normal(ks[4], (H, D)) * 0.1).astype(jnp.float32)
    return r, k, v, logw, u, chunk


@pytest.mark.parametrize("shape", WKV_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_wkv6_kernel_matches_serial_ref(shape, dtype):
    r, k, v, logw, u, chunk = _wkv_inputs(shape, dtype)
    out = wkv6(r, k, v, logw, u, chunk=chunk, interpret=True)
    expect = ref.ref_wkv6(r, k, v, logw, u)
    # chunked vs serial differ in f32 reduction order: rtol 1e-3 (long
    # reductions; see kernel-taxonomy Part E)
    t = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **t)


def test_wkv6_chunked_model_path_matches_serial():
    """The model's chunked jnp path (B, S, H, D layout) vs serial oracle."""
    B, H, S, D = 2, 2, 80, 16
    r, k, v, logw, u, _ = _wkv_inputs((B, H, S, D, 16), jnp.float32)
    to_bshd = lambda t: t.transpose(0, 2, 1, 3)
    y_c, s_c = wkv6_chunked(to_bshd(r), to_bshd(k), to_bshd(v),
                            to_bshd(logw), u, chunk=16)
    y_s, s_s = wkv6_serial(to_bshd(r), to_bshd(k), to_bshd(v),
                           to_bshd(logw), u)
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_s),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_c), np.asarray(s_s),
                               rtol=1e-4, atol=1e-4)


def test_wkv6_state_carry_across_calls():
    """Splitting a sequence into two serial calls must equal one call."""
    B, H, S, D = 1, 2, 32, 16
    r, k, v, logw, u, _ = _wkv_inputs((B, H, S, D, 16), jnp.float32)
    to_bshd = lambda t: t.transpose(0, 2, 1, 3)
    r2, k2, v2, lw2 = map(to_bshd, (r, k, v, logw))
    y_full, s_full = wkv6_serial(r2, k2, v2, lw2, u)
    h = S // 2
    y1, s1 = wkv6_serial(r2[:, :h], k2[:, :h], v2[:, :h], lw2[:, :h], u)
    y2, s2 = wkv6_serial(r2[:, h:], k2[:, h:], v2[:, h:], lw2[:, h:], u, s0=s1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# latency histogram (batched execution plane)
# ---------------------------------------------------------------------------

HIST_SHAPES = [
    # (L lanes, N samples, B bins)
    (1, 64, 8),
    (4, 128, 16),
    (3, 96, 24),   # N, B not powers of two
    (9, 256, 16),  # lanes past one block of 8
    (1, 640, 8),   # one lane, padded to a block of 8
    (2, 5000, 8),  # several sample tiles, the last one ragged
    (2, 300, 64),  # the execution plane's bin count
    (2, 300, 100),  # bins and edges padded to 128
]


def _hist_inputs(shape, seed=0):
    L, N, B = shape
    ks = jax.random.split(jax.random.key(seed), 2)
    # log-spaced edges per lane (the transient plane's convention)
    lo = 0.5 + jnp.arange(L, dtype=jnp.float32)[:, None]
    edges = lo * jnp.logspace(0.0, 2.0, B + 1)[None, :]
    samples = jax.random.uniform(ks[0], (L, N), jnp.float32,
                                 minval=0.1, maxval=200.0)
    valid = (jax.random.uniform(ks[1], (L, N)) < 0.7).astype(jnp.float32)
    return samples, valid, edges


@pytest.mark.parametrize("shape", HIST_SHAPES)
def test_latency_hist_kernel_matches_ref(shape):
    from repro.kernels.latency_hist import latency_hist

    samples, valid, edges = _hist_inputs(shape)
    out = latency_hist(samples, valid, edges, interpret=True)
    expect = ref.ref_latency_hist(samples, valid, edges)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))
    # masked samples never land anywhere; every valid one lands somewhere
    assert int(out.sum()) == int(valid.sum())


def test_latency_hist_kernel_edge_samples():
    """Samples exactly on an edge, below the first, beyond the last and
    at infinity bin as the reference bins them, across sample tiles."""
    from repro.kernels.latency_hist import latency_hist

    samples, valid, edges = _hist_inputs((3, 2600, 12), seed=4)
    samples = (samples.at[:, 0].set(edges[:, 3]).at[:, 1].set(0.0)
               .at[:, 2].set(1e9).at[:, 3].set(jnp.inf)
               .at[:, 2048].set(edges[:, 0]).at[:, 2599].set(edges[:, -1]))
    valid = valid.at[:, :4].set(1.0)
    out = latency_hist(samples, valid, edges, interpret=True)
    expect = ref.ref_latency_hist(samples, valid, edges)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


def test_latency_hist_matches_searchsorted_binning():
    """The oracle's bin convention is exactly transient.py's
    searchsorted(edges) - 1 with end-bin clamping."""
    L, N, B = 2, 40, 12
    samples, valid, edges = _hist_inputs((L, N, B), seed=3)
    # include exact-edge and out-of-range samples
    samples = samples.at[:, 0].set(edges[:, 3]).at[:, 1].set(1e9)
    samples = samples.at[:, 2].set(0.0)
    hist = ref.ref_latency_hist(samples, valid, edges)
    for l in range(L):
        bins = np.clip(np.searchsorted(np.asarray(edges[l]),
                                       np.asarray(samples[l])) - 1, 0, B - 1)
        expect = np.zeros(B, np.int32)
        for b, v in zip(bins, np.asarray(valid[l])):
            expect[b] += int(v)
        np.testing.assert_array_equal(np.asarray(hist[l]), expect)


def test_latency_hist_ops_dispatch():
    from repro.kernels.ops import latency_hist as op

    samples, valid, edges = _hist_inputs((2, 64, 8), seed=5)
    cpu = op(samples, valid, edges)                  # ref fast path
    pallas = op(samples, valid, edges, use_pallas=True)  # interpret mode
    np.testing.assert_array_equal(np.asarray(cpu), np.asarray(pallas))
