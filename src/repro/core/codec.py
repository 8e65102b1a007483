"""2-bit DNA codec — the paper's §II storage layout (3.2 Gbp ~= 800 MB).

The paper assigns T,G,C,A -> 00,01,10,11.  We instead use the *alphabetical*
assignment A,C,G,T -> 0,1,2,3 so that integer order == lexicographic order;
this is required for the sorted-tablet property (DESIGN.md §8) and costs
nothing.  Packing is big-endian within each 32-bit word (first base in the
most-significant bits) so that an unsigned word compare is a lexicographic
compare of 16 bases at once — this is what the Pallas pattern_scan kernel
exploits.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

# Alphabet ------------------------------------------------------------------
DNA_ALPHABET = "ACGT"
BASES_PER_WORD = 16  # 2 bits/base, 32-bit words
_ASCII_TO_CODE = np.full(256, 255, dtype=np.uint8)
for _i, _c in enumerate(DNA_ALPHABET):
    _ASCII_TO_CODE[ord(_c)] = _i
    _ASCII_TO_CODE[ord(_c.lower())] = _i


def encode_dna(text: str | bytes | np.ndarray) -> np.ndarray:
    """ASCII DNA -> uint8 codes in {0,1,2,3}.  Raises on non-ACGT symbols."""
    if isinstance(text, str):
        text = text.encode("ascii")
    if isinstance(text, (bytes, bytearray)):
        text = np.frombuffer(bytes(text), dtype=np.uint8)
    codes = _ASCII_TO_CODE[text]
    if np.any(codes == 255):
        bad = chr(int(text[np.argmax(codes == 255)]))
        raise ValueError(f"non-DNA symbol {bad!r} in input")
    return codes


def encode_pattern_batch(patterns, max_len: int, *, packed: bool):
    """Pattern strings -> host numpy ``(rows, lengths)`` in one pass.

    ``rows`` is ``(B, packed_length(max_len))`` uint32 words (the layout
    of :func:`pack_2bit_batch`) when ``packed``, else ``(B, max_len)``
    int32 codes, zero-padded; ``lengths`` is ``(B,)`` int32.  Upper and
    lower case are accepted.  The strings are joined and decoded with
    one table lookup and one masked scatter: no numpy call per pattern,
    no device array.  Raises ``ValueError`` on a pattern longer than
    ``max_len`` (compares are depth-capped, so it would match on its
    truncated prefix) and on a non-ACGT symbol, in that order."""
    B = len(patterns)
    lengths = np.fromiter(map(len, patterns), np.int32, count=B)
    if B and int(lengths.max()) > max_len:
        p = patterns[int(np.argmax(lengths > max_len))]
        raise ValueError(
            f"pattern of length {len(p)} exceeds max_pattern_len="
            f"{max_len} ({p[:32]!r}...); compares are depth-capped, so "
            f"it would be silently truncated")
    try:
        text = np.frombuffer("".join(patterns).encode("ascii"), np.uint8)
    except UnicodeEncodeError:
        for p in patterns:          # the first offending pattern's error
            p.encode("ascii")
        raise
    codes = _ASCII_TO_CODE[text]
    if np.any(codes == 255):
        bad = chr(int(text[np.argmax(codes == 255)]))
        raise ValueError(f"non-DNA symbol {bad!r} in input")
    width = (packed_length(max_len) * BASES_PER_WORD if packed
             else max_len)
    rows = np.zeros((B, width), np.uint8)
    rows[np.arange(width)[None, :] < lengths[:, None]] = codes
    if packed:
        return pack_2bit_batch(rows), lengths
    return rows.astype(np.int32), lengths


def decode_dna(codes: np.ndarray) -> str:
    return "".join(DNA_ALPHABET[int(c)] for c in np.asarray(codes))


def random_dna(n: int, seed: int = 0) -> np.ndarray:
    """Synthetic chromosome stand-in (uniform ACGT), uint8 codes."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, 4, size=n, dtype=np.uint8)


# Packing -------------------------------------------------------------------
def packed_length(n_bases: int) -> int:
    return (n_bases + BASES_PER_WORD - 1) // BASES_PER_WORD


def pack_2bit(codes) -> np.ndarray:
    """uint8 codes {0..3} -> uint32 words, big-endian: base i of word w sits at
    bit 30-2*i.  Trailing slots are zero-padded (== 'A'; harmless because all
    compares are depth-capped by the caller).  Host numpy words: on a TPU
    the ``(n/16, 16)`` lane layout of a device pack pads to 128 lanes, about
    36 bytes of temporary HBM per base at genome scale."""
    return pack_2bit_batch(np.asarray(codes).reshape(1, -1))[0]


def pack_2bit_batch(codes: np.ndarray) -> np.ndarray:
    """Batched host-side pack: (B, L) uint8/int codes {0..3} -> (B, W)
    uint32 words, same bit layout as :func:`pack_2bit`.  Pure numpy —
    encoding a query batch must not pay one jnp dispatch per pattern."""
    codes = np.asarray(codes)
    B, L = codes.shape
    n_words = packed_length(L)
    pad = n_words * BASES_PER_WORD - L
    if pad:
        codes = np.pad(codes, ((0, 0), (0, pad)))
    lanes = codes.astype(np.uint32).reshape(B, n_words, BASES_PER_WORD)
    shifts = (30 - 2 * np.arange(BASES_PER_WORD)).astype(np.uint32)
    return np.bitwise_or.reduce(
        (lanes << shifts[None, None, :]).astype(np.uint32), axis=2)


def unpack_2bit_batch(words: np.ndarray, n_bases: int) -> np.ndarray:
    """Batched host-side unpack: (B, W) uint32 words -> (B, n_bases) uint8
    codes — the exact inverse of :func:`pack_2bit_batch` (same big-endian
    layout).  Pure numpy: the FM-index Occ builder unpacks every BWT block
    once at freeze time and must not pay a jnp dispatch per block."""
    words = np.asarray(words, dtype=np.uint32)
    B, W = words.shape
    if n_bases > W * BASES_PER_WORD:
        raise ValueError(f"n_bases={n_bases} exceeds the {W} words' "
                         f"{W * BASES_PER_WORD} slots")
    shifts = (30 - 2 * np.arange(BASES_PER_WORD)).astype(np.uint32)
    lanes = (words[:, :, None] >> shifts[None, None, :]) & np.uint32(3)
    return lanes.reshape(B, W * BASES_PER_WORD)[:, :n_bases].astype(np.uint8)


def unpack_2bit(words: jnp.ndarray, n_bases: int) -> jnp.ndarray:
    """Inverse of pack_2bit."""
    words = jnp.asarray(words, dtype=jnp.uint32)
    shifts = (30 - 2 * jnp.arange(BASES_PER_WORD, dtype=jnp.uint32)).astype(jnp.uint32)
    lanes = (words[:, None] >> shifts[None, :]) & jnp.uint32(3)
    return lanes.reshape(-1)[:n_bases].astype(jnp.uint8)


def extract_window(packed: jnp.ndarray, pos: jnp.ndarray, n_words: int) -> jnp.ndarray:
    """Extract ``n_words`` packed words of the suffix starting at base ``pos``
    (arbitrary, not word-aligned).  Vectorized over a batch of positions.

    Returns (batch, n_words) uint32.  Bases past the end of the text read as 0
    ('A'); callers must depth-cap compares at text_len - pos themselves when
    exactness at the boundary matters (query.py does).
    """
    pos = jnp.asarray(pos)
    batch_shape = pos.shape
    pos = pos.reshape(-1)
    word_idx = (pos // BASES_PER_WORD).astype(jnp.int32)
    bit_off = (2 * (pos % BASES_PER_WORD)).astype(jnp.uint32)
    # Gather n_words+1 consecutive words, then funnel-shift pairs.
    offs = jnp.arange(n_words + 1, dtype=jnp.int32)
    idx = word_idx[:, None] + offs[None, :]
    idx = jnp.clip(idx, 0, packed.shape[0] - 1)
    in_range = (word_idx[:, None] + offs[None, :]) < packed.shape[0]
    w = jnp.where(in_range, packed[idx], jnp.uint32(0))
    hi = w[:, :-1]
    lo = w[:, 1:]
    sh = bit_off[:, None]
    # When sh == 0 the `lo >> 32` path is UB; guard it.
    out = jnp.where(
        sh == 0,
        hi,
        (hi << sh) | (lo >> (jnp.uint32(32) - sh)),
    )
    return out.reshape(*batch_shape, n_words)
