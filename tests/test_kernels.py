"""Per-kernel validation: shape/dtype sweeps, interpret=True vs the pure-jnp
oracle in ref.py (the deliverable-c kernel test requirement)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, AxisType
from jax.sharding import PartitionSpec as P

from repro.kernels import flash_attention as FA
from repro.kernels import lstm_cell as LC
from repro.kernels import moe_gmm as GM
from repro.kernels import ref as R
from repro.kernels import rwkv_scan as WK
from repro.models import layers as L


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32) * 0.5
    return x.astype(dtype)


@pytest.mark.parametrize("b,t,h,hd", [(2, 256, 4, 64), (1, 128, 2, 128),
                                      (1, 192, 3, 64), (2, 96, 5, 32)])
@pytest.mark.slow
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64), (False, 0)])
def test_flash_attention_sweep(b, t, h, hd, dtype, causal, window):
    ks = jax.random.split(jax.random.PRNGKey(b * t + h), 3)
    q = _rand(ks[0], (b, t, h, hd), dtype)
    k = _rand(ks[1], (b, t, h, hd), dtype)
    v = _rand(ks[2], (b, t, h, hd), dtype)
    out = FA.flash_attention(q, k, v, causal=causal, window=window,
                             block_q=64, block_k=64, interpret=True)
    ref = R.attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    assert float(jnp.abs(out.astype(jnp.float32)
                         - ref.astype(jnp.float32)).max()) < tol


@pytest.mark.parametrize("b,t,h,hd", [(1, 70, 2, 32),    # t % block != 0
                                      (2, 130, 2, 32),   # one partial tail
                                      (1, 7, 2, 32),     # tq < 16 (min bq)
                                      (1, 1, 2, 32)])    # single row
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 3), (False, 0)])
def test_flash_attention_edge_shapes(b, t, h, hd, causal, window):
    """ISSUE 8 satellite: non-block-multiple sequence lengths, tiny tq below
    the 16-row minimum block, and window+causal combined — the padded tail
    rows/cols must be masked out, not attended."""
    ks = jax.random.split(jax.random.PRNGKey(t * 7 + window), 3)
    q = _rand(ks[0], (b, t, h, hd), jnp.float32)
    k = _rand(ks[1], (b, t, h, hd), jnp.float32)
    v = _rand(ks[2], (b, t, h, hd), jnp.float32)
    out = FA.flash_attention(q, k, v, causal=causal, window=window,
                             block_q=64, block_k=64, interpret=True)
    ref = R.attention_ref(q, k, v, causal=causal, window=window)
    assert out.shape == ref.shape
    err = float(jnp.abs(out - ref).max())
    assert err < 2e-5, (b, t, causal, window, err)


def test_flash_attention_cross_lengths():
    """Tq != Tk (non-causal cross attention)."""
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = _rand(ks[0], (2, 100, 2, 64), jnp.float32)
    k = _rand(ks[1], (2, 260, 2, 64), jnp.float32)
    v = _rand(ks[2], (2, 260, 2, 64), jnp.float32)
    out = FA.flash_attention(q, k, v, causal=False, block_q=64, block_k=64,
                             interpret=True)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / 8.0
    p = jax.nn.softmax(s, -1)
    ref = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    assert float(jnp.abs(out - ref).max()) < 2e-5


def _dense_causal(q, k, v, window, softcap):
    """``layers._dense_attention`` over repeated K/V heads and the causal
    (and windowed) mask: today's XLA path."""
    t, rep = q.shape[1], q.shape[2] // k.shape[2]
    i = jnp.arange(t)
    mask = i[None, :] <= i[:, None]
    if window:
        mask &= i[None, :] > i[:, None] - window
    return L._dense_attention(q, L.repeat_kv(k, rep), L.repeat_kv(v, rep),
                              mask[None, None], softcap)


def _rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("hq,hkv", [(6, 2), (4, 4)])
@pytest.mark.parametrize("hd", [64, 128])
@pytest.mark.parametrize("t", [256, 384])
@pytest.mark.parametrize("window,softcap", [(0, 0.0), (100, 0.0), (0, 30.0)],
                         ids=["causal", "window100", "softcap30"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_causal_self_attention_matches_dense(hq, hkv, hd, t, window, softcap,
                                             dtype):
    """The splash entry point's output and its q, k and v gradients against
    the dense path, in interpret mode."""
    ks = jax.random.split(jax.random.PRNGKey(hq * hd + t + window), 4)
    q = _rand(ks[0], (1, t, hq, hd), dtype) * 4
    k = _rand(ks[1], (1, t, hkv, hd), dtype) * 4
    v = _rand(ks[2], (1, t, hkv, hd), dtype)
    g = _rand(ks[3], (1, t, hq, hd), dtype)
    kernel = lambda q, k, v: FA.causal_self_attention(
        q, k, v, window=window, softcap=softcap, interpret=True)
    out, vjp = jax.vjp(kernel, q, k, v)
    ref, vjp_ref = jax.vjp(
        lambda q, k, v: _dense_causal(q, k, v, window, softcap), q, k, v)
    assert out.dtype == dtype and out.shape == ref.shape
    # four units in the last place of the dtype, on the norm
    tol = 4 * float(jnp.finfo(dtype).eps) if dtype == jnp.bfloat16 else 1e-5
    assert _rel(out, ref) < tol
    for name, a, b in zip("qkv", vjp(g), vjp_ref(g)):
        assert _rel(a, b) < tol, name


def _route(fn, *args, abstract_mesh=None):
    """Trace ``fn`` and return the attention paths it counted."""
    with L.count_attention_paths() as counts:
        if abstract_mesh is None:
            jax.make_jaxpr(fn)(*args)
        else:
            with jax.sharding.use_abstract_mesh(abstract_mesh):
                jax.make_jaxpr(fn)(*args)
    return dict(counts)


_MESH4 = AbstractMesh((4,), ("x",), axis_types=(AxisType.Auto,))
_MESH1 = AbstractMesh((1,), ("x",), axis_types=(AxisType.Auto,))


def _in_shard_map(fn):
    """``fn`` as the body of a shard_map over the 4-device mesh, the batch
    split over it (a pipeline stage or the overlapped block)."""
    return jax.shard_map(fn, mesh=_MESH4, in_specs=P("x"), out_specs=P("x"),
                         check_vma=False)


_T = 256
_CASES = {
    # name: (wrapper of the (q, k, v) call, mesh, path expected)
    "causal_train_shape": (lambda q, k, v: L.attention(q, k, v), None,
                           "kernel"),
    "window": (lambda q, k, v: L.attention(q, k, v, window=100), None,
               "kernel"),
    "softcap": (lambda q, k, v: L.attention(q, k, v, softcap=30.0), None,
                "kernel"),
    "one_device_mesh": (lambda q, k, v: L.attention(q, k, v), _MESH1,
                        "kernel"),
    "shard_map_body": (_in_shard_map(lambda q, k, v: L.attention(q, k, v)),
                       None, "kernel"),
    "mask": (lambda q, k, v: L.attention(
        q, k, v, mask=jnp.tril(jnp.ones((4, _T, _T), bool))), None, "masked"),
    "kv_mask": (lambda q, k, v: L.attention(
        q, k, v, kv_mask=jnp.ones((4, _T), bool)), None, "dense"),
    "q_start": (lambda q, k, v: L.attention(q, k, v, q_start=_T), None,
                "dense"),
    "q_start_traced": (lambda q, k, v: L.attention(
        q, k, v, q_start=jnp.int32(0) + 0), None, "dense"),
    "non_causal": (lambda q, k, v: L.attention(q, k, v, causal=False), None,
                   "dense"),
    "t_not_128": (lambda q, k, v: L.attention(q[:, :200], k[:, :200],
                                              v[:, :200]), None, "dense"),
    "gspmd_mesh": (lambda q, k, v: L.attention(q, k, v), _MESH4, "dense"),
    "long_kv": (lambda q, k, v: L.attention(q, k, v, causal=False,
                                            dense_threshold=128), None,
                "chunked"),
}


@pytest.mark.parametrize("case", sorted(_CASES))
def test_attention_routes_by_what_it_sees(case):
    """``layers.attention`` takes the kernel only for causal self-attention
    from position 0 with no explicit mask, T a multiple of 128, on one
    device; every other call keeps its XLA path."""
    fn, mesh, want = _CASES[case]
    q = _rand(jax.random.PRNGKey(0), (4, _T, 6, 64), jnp.bfloat16)
    k = _rand(jax.random.PRNGKey(1), (4, _T, 2, 64), jnp.bfloat16)
    assert _route(fn, q, k, k, abstract_mesh=mesh) == {want: 1}


def test_attention_kernel_route_runs_the_xla_path_off_the_tpu():
    """Off the TPU a kernel-routed call lowers to today's dense path, so
    CPU numerics are unchanged, forward and backward."""
    q = _rand(jax.random.PRNGKey(0), (2, _T, 6, 64), jnp.bfloat16)
    k = _rand(jax.random.PRNGKey(1), (2, _T, 2, 64), jnp.bfloat16)
    v = _rand(jax.random.PRNGKey(2), (2, _T, 2, 64), jnp.bfloat16)
    loss = lambda fn: jax.jit(jax.value_and_grad(
        lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2)))
    got = loss(L.attention)(q, k, v)
    want = loss(lambda q, k, v: _dense_causal(q, k, v, 0, 0.0))(q, k, v)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    text = jax.jit(L.attention).lower(q, k, v).as_text()
    assert "tpu_custom_call" not in text


@pytest.mark.slow
@pytest.mark.parametrize("t,chunk", [(128, 32), (256, 64), (256, 128)])
@pytest.mark.parametrize("hd", [32, 64])
def test_wkv6_sweep(t, chunk, hd):
    b, h = 2, 3
    ks = jax.random.split(jax.random.PRNGKey(t + hd), 5)
    r = _rand(ks[0], (b, t, h, hd), jnp.float32)
    k = _rand(ks[1], (b, t, h, hd), jnp.float32)
    v = _rand(ks[2], (b, t, h, hd), jnp.float32)
    w = jnp.exp(-jnp.exp(_rand(ks[3], (b, t, h, hd), jnp.float32) - 2))
    u = _rand(ks[4], (h, hd), jnp.float32) * 0.2
    out = WK.wkv6(r, k, v, w, u, chunk=chunk, interpret=True)
    ref, _ = R.wkv6_ref(r, k, v, w, u)
    assert float(jnp.abs(out - ref).max()) < 2e-4


@pytest.mark.parametrize("g,c,d,f", [(4, 100, 192, 160), (2, 64, 64, 64),
                                     (8, 37, 130, 70)])
@pytest.mark.slow
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gmm_sweep(g, c, d, f, dtype):
    ks = jax.random.split(jax.random.PRNGKey(g * c), 2)
    x = _rand(ks[0], (g, c, d), dtype)
    w = _rand(ks[1], (g, d, f), dtype) * 0.2
    out = GM.gmm(x, w, block_c=64, block_f=64, block_d=64, interpret=True)
    ref = R.gmm_ref(x, w)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    assert float(jnp.abs(out.astype(jnp.float32)
                         - ref.astype(jnp.float32)).max()) < tol


@pytest.mark.slow
@pytest.mark.parametrize("bsz,din,hh", [(36, 96, 200), (8, 64, 64),
                                        (130, 128, 96)])
def test_lstm_cell_sweep(bsz, din, hh):
    ks = jax.random.split(jax.random.PRNGKey(bsz), 6)
    x = _rand(ks[0], (bsz, din), jnp.float32)
    h = _rand(ks[1], (bsz, hh), jnp.float32)
    c = _rand(ks[2], (bsz, hh), jnp.float32)
    wx = _rand(ks[3], (din, 4, hh), jnp.float32) * 0.2
    wh = _rand(ks[4], (hh, 4, hh), jnp.float32) * 0.2
    b = jnp.zeros((4, hh))
    hn, cn = LC.lstm_cell(x, h, c, wx, wh, b, block_b=32, block_h=64,
                          interpret=True)
    hr, cr = R.lstm_cell_ref(x, h, c, wx, wh, b)
    assert float(jnp.abs(hn - hr).max()) < 1e-5
    assert float(jnp.abs(cn - cr).max()) < 1e-5


def test_ops_dispatch_cpu_uses_ref():
    from repro.kernels import ops
    assert not ops.use_pallas()  # CPU container
    q = _rand(jax.random.PRNGKey(0), (1, 32, 2, 16), jnp.float32)
    out = ops.attention(q, q, q, causal=True)
    ref = R.attention_ref(q, q, q, causal=True)
    assert float(jnp.abs(out - ref).max()) < 1e-5
