"""The distributed suffix-array build (``core/dsa.py``) on four host
devices: the same suffix array as the one-device build and the naive
oracle, the rounds it reports, and a program that does not grow with
the text.  All of it runs in one subprocess with four forced devices
(the other tests must see one), whose results the cases below read."""
import json

import pytest

from conftest import run_multidevice

CODE = r"""
import json, math
import jax, numpy as np
from repro.api import SuffixTable
from repro.core.codec import random_dna
from repro.core.dsa import (build_suffix_array_distributed,
                            build_suffix_array_sharded)
from repro.core.suffix_array import build_suffix_array, suffix_array_naive

mesh = jax.make_mesh((4,), ("t",))
P = jax.sharding.PartitionSpec


def longest_repeat(codes, n_pad):
    # longest common prefix of two suffixes of the padded text: pads
    # sort below every base, the end below a pad
    s = [int(c) + 1 for c in codes] + [0] * (n_pad - len(codes))
    order = sorted(range(n_pad), key=lambda i: s[i:])
    best = 0
    for a, b in zip(order, order[1:]):
        x, y, l = s[a:], s[b:], 0
        while l < min(len(x), len(y)) and x[l] == y[l]:
            l += 1
        best = max(best, l)
    return best


rng = np.random.default_rng(7)
texts = {
    "random_1000": random_dna(1000, seed=1),
    "random_1003": random_dna(1003, seed=2),
    "repeat_longer_than_m": np.tile(rng.integers(0, 4, 37), 22)[:801]
                              .astype(np.uint8),
}
out = {}
for name, codes in texts.items():
    sa, pad, run = build_suffix_array_distributed(codes, mesh, "t")
    n_pad = len(codes) + pad
    lrs = longest_repeat(codes, n_pad)
    out[name] = {
        "n": len(codes), "m": n_pad // 4, "longest_repeat": lrs,
        "vs_one_device": bool((np.asarray(sa)[pad:] == np.asarray(
            build_suffix_array(codes))).all()),
        "vs_naive": bool((np.asarray(sa)[pad:] ==
                          suffix_array_naive(codes)).all()),
        "rounds": run.rounds, "devices": run.devices,
        "rounds_needed": 1 + math.ceil(math.log2(lrs + 1)),
    }

hlo = {}
for n in (1 << 12, 1 << 16):
    build = jax.jit(jax.shard_map(
        lambda c, n=n: build_suffix_array_sharded(c, n_real=n,
                                                  axis_name="t"),
        mesh=mesh, in_specs=(P("t"),), out_specs=(P("t"), P("t"), P())))
    hlo[n] = len(build.lower(
        jax.ShapeDtypeStruct((n,), np.int32)).as_text())
out["hlo_chars"] = hlo

table = SuffixTable.from_codes(texts["random_1003"], max_query_len=32)
out["stats"] = table.stats()
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def built():
    stdout = run_multidevice(CODE, n_devices=4, timeout=300)
    return json.loads(stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("text", ["random_1000", "random_1003",
                                  "repeat_longer_than_m"])
def test_same_suffix_array_as_one_device_and_naive(built, text):
    got = built[text]
    assert got["vs_one_device"] and got["vs_naive"], got
    assert got["devices"] == 4


def test_repeat_longer_than_a_tablet_runs_the_far_shifts(built):
    got = built["repeat_longer_than_m"]
    # rounds with k >= m took ranks from blocks beyond the neighbour
    assert got["longest_repeat"] > got["m"]
    assert 2 ** (got["rounds"] - 2) >= got["m"]
    assert got["n"] % 4 != 0


@pytest.mark.parametrize("text", ["random_1000", "random_1003",
                                  "repeat_longer_than_m"])
def test_rounds_are_the_rounds_needed(built, text):
    got = built[text]
    assert got["rounds"] == got["rounds_needed"], got


def test_program_does_not_grow_with_the_text(built):
    small, large = (built["hlo_chars"][k] for k in ("4096", "65536"))
    assert abs(large - small) < 0.1 * small, (small, large)


def test_table_records_the_distributed_build(built):
    stats = built["stats"]
    build = stats["build"]
    assert build["mode"] == "distributed"
    assert build["devices"] == 4
    assert build["rounds"] == built["random_1003"]["rounds"]
    assert build["compile_s"] > 0
    assert build["n_bases"] == 1003
    for span in ("build.compile", "build.rounds"):
        assert stats["latency"][span]["n"] == 1, span
