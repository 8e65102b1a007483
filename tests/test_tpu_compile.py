"""The served read programs compile for a TPU v5e at the paper's sizes.

Nothing runs on a chip: the TPU compiler compiles for a described
``v5e:2x2`` topology and these tests assert what it accepts — the block
layouts of the Pallas tier scan at any tier count, and the XLA base and
FM reads over a 250 M-row index.  Interpret-mode tests cannot see these
refusals.  The topology is described inside a fixture (never at import):
only one process at a time may load the TPU library.
"""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs.dna_suffix import CONFIG
from repro.core import codec
from repro.core.tablet import TabletStore, TierStack
from repro.kernels import fm_scan, ops, tier_scan

N = CONFIG.text_len                       # 250 M bases / rows
B = CONFIG.query_batch                    # 1024 patterns
W = codec.packed_length(CONFIG.max_query_len)   # 7 packed words
ROWS = 65536                              # rows per delta tier


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shp, dtype):
        return jax.ShapeDtypeStruct(shp, dtype, sharding=one_chip)
    return make


def _compile(fn, *args) -> str:
    return fn.lower(*args).compile().as_text()


def _base_store(shape) -> TabletStore:
    return TabletStore(
        text_packed=shape((codec.packed_length(N),), jnp.uint32),
        text_codes=shape((N,), jnp.int32), sa=shape((N,), jnp.int32),
        n_real=N, n_pad=N, is_dna=True,
        max_query_len=CONFIG.max_query_len)


def _patterns(shape):
    return shape((B, W), jnp.uint32), shape((B,), jnp.int32)


@pytest.mark.parametrize("T", [1, 3])
def test_tier_scan_pallas_compiles(shape, T):
    text = _compile(
        jax.jit(tier_scan.tier_scan_pallas),
        shape((B, W), jnp.uint32), shape((B,), jnp.int32),
        shape((T, W, ROWS), jnp.uint32), shape((T, ROWS), jnp.int32),
        shape((T, 8), jnp.int32))
    assert "tpu_custom_call" in text


def test_base_query_compiles_at_250m_rows(shape):
    from repro.core.planner import _query_single
    _compile(_query_single, _base_store(shape), *_patterns(shape))


def test_fused_read_with_tiers_compiles_at_250m_rows(shape, monkeypatch):
    """The merged read a table with a sealed run and a memtable serves on
    a TPU: base binary search + the Pallas tier scan, one program."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)   # TPU branch
    T = 2
    OV = 128                              # pow2 >= max_query_len - 1
    K = ROWS.bit_length()
    stack = TierStack(
        text_packed=shape((T, codec.packed_length(ROWS)), jnp.uint32),
        text_codes=shape((T, ROWS), jnp.int32),
        sa=shape((T, ROWS), jnp.int32),
        n_real=shape((T,), jnp.int32), n_rows=shape((T,), jnp.int32),
        offset=shape((T,), jnp.int32), lo=shape((T,), jnp.int32),
        hi=shape((T,), jnp.int32),
        ov_rank=shape((T, OV), jnp.int32), hi_rank=shape((T, OV), jnp.int32),
        pad_cnt=shape((T, ROWS + 1), jnp.int32),
        rmq=shape((T, K, ROWS), jnp.int32),
        num_tiers=T, rows=ROWS, is_dna=True,
        max_query_len=CONFIG.max_query_len)
    text = _compile(ops.fused_single, _base_store(shape), stack,
                    *_patterns(shape))
    assert "tpu_custom_call" in text


def test_fm_read_compiles_at_250m(shape):
    """The frozen tier's read (XLA backward search + LF walk) over a
    250 Mbp FM-index left in HBM."""
    rows = N + 1
    nblk = -(-rows // fm_scan.SB)
    wm = -(-rows // 32)
    arrays = fm_scan.FMArrays(
        bwt=shape((nblk * fm_scan.WPB,), jnp.uint32),
        occ=shape((nblk + 1, 4), jnp.int32), cc=shape((4,), jnp.int32),
        marked=shape((wm,), jnp.uint32),
        marked_rank=shape((wm,), jnp.int32),
        samples=shape((-(-rows // 32),), jnp.int32),
        sent_row=shape((), jnp.int32), n=shape((), jnp.int32),
        is_dna=True, sample_rate=32, vocab=4)
    _compile(ops.fm_search, arrays, *_patterns(shape))



def test_looped_distributed_build_compiles_for_four_chips(topo):
    """The four-tablet build (``core/dsa.py``): one ``while`` loop whose
    shift picks its blocks with a ``lax.switch`` of ``ppermute``s, which
    the TPU compiler must accept inside the loop."""
    import functools

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.dsa import build_suffix_array_sharded
    mesh = Mesh(np.array(topo.devices[:4]), ("tablets",))
    n = 1 << 12          # the program is the same at any length
    build = jax.jit(jax.shard_map(
        functools.partial(build_suffix_array_sharded, n_real=n - 3,
                          axis_name="tablets"),
        mesh=mesh, in_specs=(P("tablets"),),
        out_specs=(P("tablets"), P("tablets"), P())))
    text = _compile(build, jax.ShapeDtypeStruct(
        (n,), jnp.int32, sharding=NamedSharding(mesh, P("tablets"))))
    assert "collective-permute" in text and "while" in text
