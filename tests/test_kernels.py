"""Per-kernel correctness: sweep shapes x dtypes, assert_allclose vs the
pure-jnp oracles in kernels/ref.py.  On the CPU backend the Pallas kernels
run in interpret mode; tests/test_tpu_compile.py compiles them for the
chip."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

_RTOL = {jnp.float32: 2e-4, jnp.bfloat16: 5e-2}
_ATOL = {jnp.float32: 2e-4, jnp.bfloat16: 5e-2}


def _assert_close(got, want, dtype):
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=_RTOL[dtype], atol=_ATOL[dtype])


# ---------------------------------------------------------------- matmul
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,n", [
    (128, 128, 128),        # single tile
    (256, 512, 256),        # multi-tile all dims
    (100, 300, 70),         # unaligned (padding path)
    (1, 9216, 4096),        # FC6 row (paper Table II)
    (8, 64, 8),             # tiny
])
def test_matmul_shapes(rng, m, k, n, dtype):
    x = jnp.asarray(rng.normal(size=(m, k)), dtype)
    w = jnp.asarray(rng.normal(size=(k, n)), dtype)
    _assert_close(ops.matmul(x, w), ref.matmul_ref(x, w), dtype)


@pytest.mark.parametrize("activation", ["none", "relu", "sigmoid", "tanh"])
def test_matmul_bias_activation(rng, activation):
    x = jnp.asarray(rng.normal(size=(64, 96)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(96, 48)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(48,)), jnp.float32)
    _assert_close(ops.matmul(x, w, b, activation=activation),
                  ref.fc_ref(x, w, b, activation=activation), jnp.float32)


def test_matmul_block_sweep(rng):
    x = jnp.asarray(rng.normal(size=(512, 384)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(384, 256)), jnp.float32)
    want = ref.matmul_ref(x, w)
    for bm, bn, bk in [(128, 128, 128), (256, 256, 384), (512, 64, 192)]:
        got = ops.matmul(x, w, block_m=bm, block_n=bn, block_k=bk)
        _assert_close(got, want, jnp.float32)


# ---------------------------------------------------------------- conv2d
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("hw,cin,cout,kk,stride,pad", [
    (16, 3, 8, 3, 1, 1),
    (16, 4, 8, 3, 2, 0),
    (24, 3, 16, 5, 2, 2),
    (13, 8, 16, 3, 1, 1),      # conv3-5 geometry (reduced channels)
    (12, 3, 8, 11, 4, 2),      # conv1 geometry (reduced)
    (31, 3, 16, 11, 4, 2),     # stride-4 k11: space-to-depth, 7x7 out
    (27, 8, 16, 5, 1, 2),      # conv2 geometry: 27x27 k5, odd width
])
def test_conv2d_shapes(rng, hw, cin, cout, kk, stride, pad, dtype):
    x = jnp.asarray(rng.normal(size=(2, hw, hw, cin)), dtype)
    w = jnp.asarray(rng.normal(size=(cout, cin, kk, kk)), dtype)
    b = jnp.asarray(rng.normal(size=(cout,)), dtype)
    got = ops.conv2d(x, w, b, stride=stride, padding=pad, activation="relu")
    want = ref.conv2d_ref(x, w, b, stride=stride, padding=pad,
                          activation="relu")
    _assert_close(got, want, dtype)


# --------------------------------------------------------------- pooling
@pytest.mark.parametrize("pool_type", ["max", "avg"])
@pytest.mark.parametrize("hw,c,win,stride", [
    (13, 8, 3, 2), (27, 4, 3, 2), (8, 16, 2, 2), (9, 3, 3, 3),
    (55, 8, 3, 2), (11, 5, 3, 2),    # 3x3/s2 on odd sizes (Pool1 geometry)
])
def test_pool_shapes(rng, hw, c, win, stride, pool_type):
    x = jnp.asarray(rng.normal(size=(2, hw, hw, c)), jnp.float32)
    got = ops.pool(x, window=win, stride=stride, pool_type=pool_type)
    want = (ref.maxpool_ref(x, window=win, stride=stride) if pool_type == "max"
            else ref.avgpool_ref(x, window=win, stride=stride))
    _assert_close(got, want, jnp.float32)


# ------------------------------------------------------------------ lrn
# scale 100: activations large enough that the windowed sum moves the
# denominator (alpha/n * sum ~ k), so a wrong channel window cannot hide;
# C=130 is not a multiple of the 128-lane tile
@pytest.mark.parametrize("c,local,scale", [
    pytest.param(c, n, s, id=f"{c}-{n}" + ("" if s == 1 else f"-x{s:g}"))
    for c, n, s in [(8, 5, 1), (16, 3, 1), (96, 5, 1), (7, 5, 1),
                    (96, 5, 100), (130, 5, 100), (7, 3, 100)]])
def test_lrn_shapes(rng, c, local, scale):
    x = jnp.asarray(scale * rng.normal(size=(2, 7, 7, c)), jnp.float32)
    got = ops.lrn(x, local_size=local)
    want = ref.lrn_ref(x, local_size=local)
    _assert_close(got, want, jnp.float32)


# ------------------------------------------------------ flash attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("hq,hk", [(8, 8), (8, 2), (4, 1)])
def test_flash_attention_gqa(rng, hq, hk, dtype):
    q = jnp.asarray(rng.normal(size=(2, hq, 256, 64)), dtype)
    k = jnp.asarray(rng.normal(size=(2, hk, 256, 64)), dtype)
    v = jnp.asarray(rng.normal(size=(2, hk, 256, 64)), dtype)
    got = ops.flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    want = ref.attention_ref(q, k, v, causal=True)
    _assert_close(got, want, jnp.bfloat16)   # online softmax: bf16-level tol


@pytest.mark.parametrize("window", [32, 64, 250])
def test_flash_attention_windowed(rng, window):
    q = jnp.asarray(rng.normal(size=(1, 4, 256, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 256, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 256, 32)), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=True, window=window,
                              block_q=64, block_k=64)
    want = ref.attention_ref(q, k, v, causal=True, window=window)
    _assert_close(got, want, jnp.bfloat16)


def test_flash_attention_unaligned_padding():
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(1, 2, 100, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 100, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 100, 32)), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    want = ref.attention_ref(q, k, v, causal=True)
    _assert_close(got, want, jnp.bfloat16)


@pytest.mark.parametrize("s,t", [(100, 100), (64, 100)])
def test_flash_attention_noncausal_padded_keys(s, t):
    # encoder-style calls: the kernel masks the padded keys itself
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(1, 2, s, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, t, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, t, 32)), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=False, block_q=64,
                              block_k=64)
    want = ref.attention_ref(q, k, v, causal=False)
    _assert_close(got, want, jnp.bfloat16)


def test_interpret_mode_only_on_cpu(monkeypatch):
    assert ops.default_interpret() is (jax.default_backend() == "cpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.default_interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        ops.default_interpret()


# ------------------------------------------------------ paged attention
def _paged_case(rng, *, b, hq, hk, d, bs, nb, dtype, shuffle=True):
    """Random decode case: arena of physical pages + per-slot block tables
    (non-contiguous when shuffled) + per-slot positions on odd block
    boundaries."""
    tb = b * nb + 1                                  # + trash page
    q = jnp.asarray(rng.normal(size=(b, hq, 1, d)), dtype)
    ka = jnp.asarray(rng.normal(size=(tb, hk, bs, d)), dtype)
    va = jnp.asarray(rng.normal(size=(tb, hk, bs, d)), dtype)
    ids = np.arange(tb - 1) + 1
    if shuffle:
        ids = rng.permutation(ids)
    bt = jnp.asarray(ids[:b * nb].reshape(b, nb).astype(np.int32))
    pos = jnp.asarray(
        rng.integers(0, nb * bs, size=(b,)).astype(np.int32))
    return q, ka, va, bt, pos


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("hq,hk", [(8, 8), (8, 2), (4, 1)])
def test_paged_attention_gqa(rng, hq, hk, dtype):
    q, ka, va, bt, pos = _paged_case(rng, b=3, hq=hq, hk=hk, d=64, bs=16,
                                     nb=4, dtype=dtype)
    got = ops.paged_attention(q, ka, va, bt, pos)
    want = ref.paged_attention_ref(q, ka, va, bt, pos)
    _assert_close(got, want, jnp.bfloat16)   # online softmax: bf16-level tol


@pytest.mark.parametrize("pos_list", [[0], [15], [16], [17], [63]])
def test_paged_attention_block_boundaries(rng, pos_list):
    # positions sitting exactly on / beside page edges — the block-skip
    # predicate and the boundary mask must agree with the dense oracle
    b = len(pos_list)
    q, ka, va, bt, _ = _paged_case(rng, b=b, hq=4, hk=2, d=32, bs=16, nb=4,
                                   dtype=jnp.float32)
    pos = jnp.asarray(np.asarray(pos_list, np.int32))
    got = ops.paged_attention(q, ka, va, bt, pos)
    want = ref.paged_attention_ref(q, ka, va, bt, pos)
    _assert_close(got, want, jnp.bfloat16)


# the benchmark cells' head geometries: minicpm_2b (MHA, 36 KV heads of 64)
# and qwen2_1_5b (GQA, 12 query / 2 KV heads of 128); block 16, 2048 tokens
SERVING_GEOMETRY = {"mha_36x64": (36, 36, 64), "gqa_12_2x128": (12, 2, 128)}


def _poisoned_serving_case(rng, hq, hk, d, dtype):
    """Slots at positions {0, 15, 16, 17, the derived chunk's last and
    first tokens, 2047} and one empty slot (pos -1).  Live pages are
    shuffled; a table entry past a slot's position names either a page of
    its own (a reserved, unwritten page) or the padding page 0; those
    pages and the trash page hold NaN.  Returns the poisoned arenas and
    the same arenas with finite values there (what the reference, which
    reads every entry of the table, is given)."""
    from repro.kernels.paged_attention import pages_per_chunk
    bs, nb = 16, 128
    span = pages_per_chunk(hk * bs * d * jnp.dtype(dtype).itemsize,
                           nb) * bs
    pos = np.array([0, 15, 16, 17, span - 1, span, nb * bs - 1, -1],
                   np.int32)
    live = np.clip(pos // bs + 1, 0, nb)
    reserved = np.arange(len(pos)) % 2 == 0         # the others pad with 0
    n_pages = 1 + int(live.sum()) + int(
        (nb - live)[reserved].sum()) + 1            # padding ... trash
    ids = iter(rng.permutation(n_pages - 2) + 1)
    bt = np.zeros((len(pos), nb), np.int32)
    dead = [0, n_pages - 1]
    for s in range(len(pos)):
        for j in range(nb):
            if j < live[s]:
                bt[s, j] = next(ids)
            elif reserved[s]:
                bt[s, j] = next(ids)
                dead.append(bt[s, j])
    shape = (n_pages, hk, bs, d)
    ka, va = (rng.normal(size=shape).astype(np.float32) for _ in range(2))
    kn, vn = ka.copy(), va.copy()
    kn[dead] = np.nan
    vn[dead] = np.nan
    q = jnp.asarray(rng.normal(size=(len(pos), hq, 1, d)), dtype)
    arr = lambda a: jnp.asarray(a, dtype)
    return (q, arr(kn), arr(vn), arr(ka), arr(va), jnp.asarray(bt),
            jnp.asarray(pos))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("geometry", list(SERVING_GEOMETRY))
def test_paged_attention_serving_geometry(rng, geometry, dtype):
    """The kernel reads only live pages: NaN past every slot's position, in
    the padding page and in the trash page never reaches the output; an
    empty slot gets zeros; the rest matches the oracle."""
    hq, hk, d = SERVING_GEOMETRY[geometry]
    q, kn, vn, ka, va, bt, pos = _poisoned_serving_case(rng, hq, hk, d,
                                                        dtype)
    got = np.asarray(ops.paged_attention(q, kn, vn, bt, pos), np.float32)
    want = np.asarray(ref.paged_attention_ref(q, ka, va, bt,
                                              jnp.maximum(pos, 0)),
                      np.float32)
    assert np.isfinite(got).all()
    empty = np.asarray(pos) < 0
    np.testing.assert_array_equal(got[empty], 0.0)
    _assert_close(got[~empty], want[~empty], dtype)


@pytest.mark.parametrize("page_bytes,n_pages,want", [
    (36 * 16 * 64 * 2, 128, 4),       # minicpm_2b page, bf16: 73,728 B
    (2 * 16 * 128 * 2, 128, 32),      # qwen2_1_5b page, bf16: 8,192 B
    (2 * 16 * 128 * 2, 4, 4),         # no more than the table holds
    (4 * 2 ** 20, 128, 1),            # a page over the chunk: one a step
])
def test_pages_per_chunk_follows_page_bytes(page_bytes, n_pages, want):
    from repro.kernels.paged_attention import pages_per_chunk
    assert pages_per_chunk(page_bytes, n_pages) == want


def test_paged_step_list_walks_live_chunks_only():
    """Steps: one per live chunk (an empty slot gets one), slot-major; a
    page past the position repeats its operand's page of the step before,
    so the pipeline copies nothing for it."""
    from repro.kernels.paged_attention import _step_list
    bt = jnp.asarray(np.arange(1, 13, dtype=np.int32).reshape(3, 4))
    pos = jnp.asarray(np.array([20, -1, 40], np.int32))   # 2, 0, 3 pages
    n, slot_of, chunk_of, pages = _step_list(pos, bt, bs=16, ppc=2)
    assert int(n) == 1 + 1 + 2
    assert slot_of.shape == (3 * 2,)
    np.testing.assert_array_equal(np.asarray(slot_of)[:4], [0, 1, 2, 2])
    np.testing.assert_array_equal(np.asarray(chunk_of)[:4], [0, 0, 0, 1])
    pages = np.asarray(pages).reshape(-1, 2)[:4]
    # slot 0: pages 1, 2; slot 1 (empty) holds them; slot 2: pages 9, 10,
    # then 11 and (dead) 10 again
    np.testing.assert_array_equal(pages, [[1, 2], [1, 2], [9, 10],
                                          [11, 10]])


def test_paged_ref_trims_sequence_overhang(rng):
    # max_seq not a multiple of block_size: the gathered rows must trim the
    # tail pages' overhang, matching a dense cache of exactly max_seq
    from repro.models.attention import decode_attention
    b, hk, d, bs, nb, max_seq = 2, 2, 32, 8, 3, 21
    q, ka, va, bt, _ = _paged_case(rng, b=b, hq=4, hk=hk, d=d, bs=bs, nb=nb,
                                   dtype=jnp.float32)
    pos = jnp.asarray(np.array([5, 20], np.int32))
    dense_k = ref.paged_gather(ka, bt, max_seq)
    dense_v = ref.paged_gather(va, bt, max_seq)
    assert dense_k.shape == (b, hk, max_seq, d)
    want = decode_attention(q, dense_k, dense_v, pos=pos)
    got = ref.paged_attention_ref(q, ka, va, bt, pos, max_seq=max_seq)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_conv_vmem_budget():
    """Every AlexNet conv layer's per-image working set fits 16 MiB VMEM —
    the Table III resource-constraint analogue."""
    from repro.core.layer_model import alexnet_full_spec
    from repro.kernels.conv2d import conv2d_vmem_bytes
    for spec in alexnet_full_spec():
        if spec.kind != "conv":
            continue
        h, w, c = spec.m_i
        oc, ic, kh, kw = spec.m_k
        pad = spec.padding
        bytes_ = conv2d_vmem_bytes(h + 2 * pad, w + 2 * pad, ic, oc, kh, kw,
                                   spec.stride)
        assert bytes_ < 16 * 2**20, (spec.name, bytes_)
