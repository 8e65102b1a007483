"""codec: 2-bit packing invariants (hypothesis property tests)."""
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # fall back to the vendored seeded-random shim
    from _hypothesis_compat import given, settings, strategies as st

from repro.core import codec

dna = st.text(alphabet="ACGT", min_size=1, max_size=300)


@given(dna)
@settings(max_examples=50, deadline=None)
def test_pack_roundtrip(s):
    c = codec.encode_dna(s)
    p = codec.pack_2bit(c)
    u = codec.unpack_2bit(p, len(c))
    assert (np.asarray(u) == c).all()


@given(dna)
@settings(max_examples=25, deadline=None)
def test_word_order_is_lexicographic(s):
    """Packing is big-endian: comparing the first packed word of two texts
    equals comparing their first 16 bases lexicographically."""
    c = codec.encode_dna(s)
    other = np.roll(c, 1)
    w1 = int(np.asarray(codec.pack_2bit(c))[0])
    w2 = int(np.asarray(codec.pack_2bit(other))[0])
    s1 = bytes(np.pad(c, (0, 16))[:16])
    s2 = bytes(np.pad(other, (0, 16))[:16])
    assert (w1 < w2) == (s1 < s2)
    assert (w1 == w2) == (s1 == s2)


@given(dna, st.integers(0, 400))
@settings(max_examples=50, deadline=None)
def test_extract_window(s, pos):
    c = codec.encode_dna(s)
    pos = pos % len(c)
    p = codec.pack_2bit(c)
    w = codec.extract_window(p, jnp.asarray([pos]), 2)[0]
    want = codec.pack_2bit(np.pad(c[pos:], (0, 32))[:32])[:2]
    assert (np.asarray(w) == np.asarray(want)).all()


def test_encode_rejects_non_dna():
    with pytest.raises(ValueError):
        codec.encode_dna("ACGTX")


def test_decode_inverse():
    c = codec.random_dna(97, seed=3)
    assert (codec.encode_dna(codec.decode_dna(c)) == c).all()


# ---------------------------------------------------------------------------
# encode_pattern_batch: the vectorised host encoder of a read batch
# ---------------------------------------------------------------------------
def _loop_reference(patterns, max_len, packed):
    """The per-pattern loop the host encoder replaced: its length check,
    then one ``encode_dna`` call per pattern, then the batch pack."""
    for p in patterns:
        if len(p) > max_len:
            raise ValueError(
                f"pattern of length {len(p)} exceeds max_pattern_len="
                f"{max_len} ({p[:32]!r}...); compares are depth-capped, so "
                f"it would be silently truncated")
    width = (codec.packed_length(max_len) * codec.BASES_PER_WORD if packed
             else max_len)
    codes = np.zeros((len(patterns), width), np.int32)
    for i, p in enumerate(patterns):
        codes[i, :len(p)] = codec.encode_dna(p)
    lengths = np.array([len(p) for p in patterns], np.int32)
    if packed:
        return (codec.pack_2bit_batch(codes)[:, :codec.packed_length(max_len)],
                lengths)
    return codes, lengths


def _assert_bit_identical(got, want):
    for g, w in zip(got, want):
        assert isinstance(g, np.ndarray)
        assert g.dtype == w.dtype and g.shape == w.shape
        assert np.array_equal(g, w)


@given(st.sampled_from([1, 15, 16, 20, 33, 112]),
       st.lists(st.text(alphabet="ACGTacgt", min_size=0, max_size=112),
                min_size=0, max_size=24),
       st.booleans())
@settings(max_examples=60, deadline=None)
def test_encode_pattern_batch_equals_loop(max_len, patterns, packed):
    patterns = [p[:max_len] for p in patterns]
    _assert_bit_identical(
        codec.encode_pattern_batch(patterns, max_len, packed=packed),
        _loop_reference(patterns, max_len, packed))


@pytest.mark.parametrize("packed", [True, False])
@pytest.mark.parametrize("patterns", [
    [], ["gattaca"], [""], ["", "A"], ["A" * 112, "acgt" * 28, "T"]])
def test_encode_pattern_batch_edge_batches(patterns, packed):
    got = codec.encode_pattern_batch(patterns, 112, packed=packed)
    _assert_bit_identical(got, _loop_reference(patterns, 112, packed))
    assert got[0].shape[0] == len(patterns)


@pytest.mark.parametrize("patterns", [
    ["ACGT", "ACNT"], ["AC GT"], ["acgu", "ACGX"], ["ACGTé"],
    ["A" * 21], ["ACGT", "N" * 30], ["ACGN", "A" * 21]])
def test_encode_pattern_batch_raises_as_the_loop(patterns):
    with pytest.raises(ValueError) as want:
        _loop_reference(patterns, 20, True)
    for packed in (True, False):
        with pytest.raises(ValueError) as got:
            codec.encode_pattern_batch(patterns, 20, packed=packed)
        assert type(got.value) is type(want.value)
        assert str(got.value) == str(want.value)
