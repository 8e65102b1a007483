"""The plain reference and the repeat-model text against brute force."""
import numpy as np
import pytest

from chipbench_testing import harness  # noqa: F401  (puts chipbench on the path)

import reference
import textgen

TINY = {"length": 6000, "gc_fraction": 0.41, "repeats": [
    {"kind": "family", "share": 0.10, "length": [28, 32], "families": 2,
     "consensus": 32, "divergence": 0.05},
    {"kind": "family", "share": 0.17, "length": [20, 200], "families": 1,
     "consensus": 200, "divergence": 0.05},
    {"kind": "segdup", "share": 0.05, "length": [100, 300],
     "divergence": 0.03}]}


def _patterns(text, rng, count):
    out = []
    for j in range(count):
        m = int(rng.integers(1, 41))
        if j % 2 == 0:
            s = int(rng.integers(0, text.size - m + 1))
            out.append(text[s:s + m].copy())
        else:
            out.append(rng.integers(0, 4, size=m, dtype=np.uint8))
    # the text's end: suffixes shorter than the reference's k-mer
    out += [text[-3:].copy(), text[-12:].copy(), text[-15:].copy()]
    return out


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_reference_matches_brute_force_with_repeats(seed):
    text = textgen.generate(TINY, seed)
    pats = _patterns(text, np.random.default_rng(seed), 300)
    count, first = reference.Reference(text).answers(pats, 8)
    assert count.max() >= 20          # the repeats recur
    for i, p in enumerate(pats):
        want_c, want_p = reference.brute_force(text, p, 8)
        assert count[i] == want_c
        assert list(first[i, :len(want_p)]) == want_p
        assert (first[i, len(want_p):] == -1).all()


def test_text_is_fixed_by_its_seed_and_holds_its_repeats():
    a = textgen.generate(TINY, 5)
    assert np.array_equal(a, textgen.generate(TINY, 5))
    assert not np.array_equal(a, textgen.generate(TINY, 6))
    assert a.dtype == np.uint8 and a.size == 6000 and a.max() <= 3
    gc = np.isin(a, (1, 2)).mean()
    assert 0.35 < gc < 0.47
    # a 16-mer of a family copy recurs far more than in random DNA
    ref = reference.Reference(a)
    count, _ = ref.answers([a[s:s + 16] for s in range(0, 5984, 16)], 1)
    assert count.max() >= 5


def test_sa_in_order_accepts_the_suffix_array_and_nothing_else():
    text = textgen.generate(TINY, 9)[:2000]
    sa = np.array(sorted(range(text.size),
                         key=lambda i: text[i:].tobytes()), np.int64)
    rng = np.random.default_rng(0)
    assert reference.sa_in_order(text, sa, rng)
    swapped = sa.copy()
    swapped[100:600] = swapped[100:600][::-1]     # 499 pairs out of order
    assert not reference.sa_in_order(text, swapped, rng)
    assert not reference.sa_in_order(text, sa[:-1], rng)
