"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU): shape/dtype
sweeps per kernel as required."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import codec, query as Q
from repro.core.codec import random_dna
from repro.core.tablet import build_tablet_store
from repro.kernels import ops, ref, tier_scan as TS


@pytest.mark.parametrize("n", [1, 15, 16, 17, 1000, 16384, 50001])
def test_pack2bit_shapes(n):
    c = random_dna(n, seed=n)
    got = np.asarray(ops.pack2bit(c))
    want = np.asarray(codec.pack_2bit(c))
    assert got.shape == want.shape
    assert (got == want).all()


@pytest.mark.parametrize("src_dtype", [np.uint8, np.int32, np.uint32])
def test_pack2bit_dtypes(src_dtype):
    c = random_dna(4096, seed=0).astype(src_dtype)
    got = np.asarray(ops.pack2bit(c))
    want = np.asarray(codec.pack_2bit(c.astype(np.uint8)))
    assert (got == want).all()


@pytest.mark.parametrize("B,W,text_n", [
    (1, 1, 64), (7, 2, 500), (300, 7, 3000), (512, 8, 3000), (1000, 4, 777),
])
def test_pattern_compare_sweep(B, W, text_n):
    codes = random_dna(text_n, seed=B)
    packed = codec.pack_2bit(codes)
    rng = np.random.default_rng(W)
    pos = rng.integers(0, text_n, size=B).astype(np.int32)
    pats = Q.random_patterns(B, 1, W * 16, seed=(B, W))
    _, pp, pl = Q.encode_patterns(pats, W * 16)
    win = codec.extract_window(packed, jnp.asarray(pos), W)
    lt, le, eq = ops.pattern_compare(win, pp, pl, jnp.asarray(pos),
                                     n_real=text_n)
    rlt, rle, req = ref.pattern_compare_ref(win.T, pp.T, pl,
                                            jnp.asarray(pos), n_real=text_n)
    np.testing.assert_array_equal(np.asarray(lt), np.asarray(rlt, bool))
    np.testing.assert_array_equal(np.asarray(le), np.asarray(rle, bool))
    np.testing.assert_array_equal(np.asarray(eq), np.asarray(req, bool))
    # cross-check against the core compare
    clt, ceq = Q.compare_packed(packed, text_n, jnp.asarray(pos), pp, pl)
    np.testing.assert_array_equal(np.asarray(lt), np.asarray(clt))
    np.testing.assert_array_equal(np.asarray(eq), np.asarray(ceq))


@pytest.mark.parametrize("nq,text_n", [(16, 512), (150, 2000), (260, 4096)])
def test_tablet_scan_matches_query_engine(nq, text_n):
    codes = random_dna(text_n, seed=text_n)
    store = build_tablet_store(codes)
    W = 7
    pats = Q.random_patterns(nq, 1, 12, seed=nq)
    _, pp, pl = Q.encode_patterns(pats, W * 16)
    windows = codec.extract_window(store.text_packed, store.sa, W)
    count, less, first = ops.tablet_scan(pp, pl, windows, store.sa,
                                         n_real=store.n_real)
    res = Q.query(store, pp, pl)
    np.testing.assert_array_equal(np.asarray(count), np.asarray(res.count))
    f = np.asarray(res.found)
    lb = np.asarray(res.first_rank) + store.pad_count
    np.testing.assert_array_equal(np.asarray(less)[f], lb[f])
    rc, rl, rf = ref.tablet_scan_ref(pp.T, pl, windows.T, store.sa,
                                     n_real=store.n_real)
    np.testing.assert_array_equal(np.asarray(count), np.asarray(rc))
    np.testing.assert_array_equal(np.asarray(less), np.asarray(rl))
    np.testing.assert_array_equal(np.asarray(first), np.asarray(rf))


@pytest.mark.parametrize("nq,base_n,chunks", [
    (17, 900, 3), (130, 2500, 5), (260, 1400, 4),
])
def test_tier_scan_kernel_vs_ref_vs_fused(nq, base_n, chunks):
    """The fused tier kernel (interpret), its dense oracle, and the
    pure-jnp production path agree bit-for-bit on a real TierStack."""
    from repro.api import SuffixTable
    table = SuffixTable.from_codes(random_dna(base_n, seed=base_n),
                                   is_dna=True, memtable_limit=260)
    for i in range(chunks):
        table.append(random_dna(150, seed=1000 + i))
    ts = table._tierset()
    assert ts is not None and ts.stack.num_tiers >= 2
    stack = ts.stack

    pats = Q.random_patterns(nq, 1, 12, seed=nq)
    _, pp, pl = Q.encode_patterns(pats, stack.max_query_len)

    want = TS.fused_tier_scan(stack, pp, pl)
    got = ops.tier_scan(stack, pp, pl)          # Pallas, interpret on CPU
    for name, g, w in zip(("count", "less", "matches", "first_g"),
                          got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)

    # dense ref over the same unpadded stack operands
    W = pp.shape[1]
    windows = jax.vmap(lambda pk, sa_t: codec.extract_window(pk, sa_t, W))(
        stack.text_packed, stack.sa)
    wt = jnp.transpose(windows, (0, 2, 1))
    meta = np.zeros((stack.num_tiers, 8), np.int32)
    for k, v in enumerate((stack.n_real, stack.n_rows, stack.offset,
                           stack.lo, stack.hi)):
        meta[:, k] = np.asarray(v)
    rref = ref.tier_scan_ref(pp.T.astype(jnp.uint32), pl, wt, stack.sa,
                             jnp.asarray(meta))
    for name, g, w in zip(("count", "less", "matches", "first_g"),
                          rref, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg="ref:" + name)


# ---------------------------------------------------------------------------
# pack/unpack round trips (host batch path feeds the FM-index Occ builder)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,L", [(1, 1), (3, 15), (2, 16), (5, 17), (4, 33)])
def test_unpack_2bit_batch_round_trip(B, L):
    rng = np.random.default_rng(B * 100 + L)
    codes = rng.integers(0, 4, size=(B, L)).astype(np.uint8)
    words = codec.pack_2bit_batch(codes)
    assert words.dtype == np.uint32
    got = codec.unpack_2bit_batch(words, L)
    np.testing.assert_array_equal(got, codes)
    # agrees with the jnp single-row unpack on every row
    for b in range(B):
        np.testing.assert_array_equal(
            np.asarray(codec.unpack_2bit(jnp.asarray(words[b]), L)),
            codes[b])
    # asking for more bases than the words hold is an error, not junk
    with pytest.raises(ValueError):
        codec.unpack_2bit_batch(words, words.shape[1] * 16 + 1)


# ---------------------------------------------------------------------------
# FM backward search (the frozen tier's read, XLA on every backend) vs
# brute force and the binary-search path
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,nq", [(130, 40), (2048, 200)])
def test_fm_scan_pallas_matches_oracle(n, nq):
    from repro.api.fm import FMIndex
    from repro.kernels import fm_scan as FM

    codes = random_dna(n, seed=n)
    fm = FMIndex.build(codes, None, is_dna=True, sample_rate=8)
    pats = Q.random_patterns(nq, 1, 12, seed=nq)
    _, pp, pl = Q.encode_patterns(pats, 16)
    syms = FM.syms_from_packed(pp, pl, pp.shape[1] * 16)
    lo, hi = FM.search_syms(fm.arrays, syms)
    count = np.asarray(hi) - np.asarray(lo)

    cc = np.asarray(codes).astype(np.int32)
    for i, p in enumerate(pats):
        want, _ = Q.brute_force_count(cc, codec.encode_dna(p).astype(np.int32))
        assert int(count[i]) == want, p

    # lo - 1 is the real-SA lower bound the binary search reports
    res = Q.query(build_tablet_store(codes), pp, pl)
    f = np.asarray(res.found)
    np.testing.assert_array_equal(count, np.asarray(res.count))
    np.testing.assert_array_equal(np.asarray(lo)[f] - 1,
                                  np.asarray(res.first_rank)[f])
