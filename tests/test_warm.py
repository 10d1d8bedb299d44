"""One warm run per algorithm, made in one place: every variant's build
returns an unwarmed thunk, and the table builder
(:meth:`repro.core.programs.VariantSite.workloads`) calls every program
exactly once. Calls are counted through the programs the process's cache
hands out, never through timings."""

import collections
import dataclasses

import pytest

from repro.core import programs
from repro.core.spans import collect


def _counting(calls, key, program):
    """``program`` that counts each call under ``key``."""

    def counted(*args, **kwargs):
        calls[key] += 1
        return program(*args, **kwargs)

    return counted


class _CountingCache:
    """A program cache whose programs count their calls under their key."""

    def __init__(self, cache, calls):
        self.cache, self.calls = cache, calls

    def get(self, key, build):
        return _counting(self.calls, key, self.cache.get(key, build))


def _matmul_blocks(request):
    """The matmul site, interpreted on the CPU: one program per tiling and
    XLA's dot."""
    from repro.autotune.variants import matmul_blocks_site

    return matmul_blocks_site(m=256, k=256, n=256, blocks=((128, 128, 128), (256, 256, 256)))


def _attention_layer(request):
    """A sliding layer of the tiny attention model through its family."""
    request.getfixturevalue("tiny_attention_model")
    from repro.core.family import get_family

    return get_family("kernel_variants").variant_site({
        "site": "attention", "config": "tiny-attention", "layer": "sliding",
        "size": 512, "seed": 1})


def _gram(request):
    from repro.expressions.generalized import FAMILIES

    return FAMILIES["gram"](n=32)


def _chain(request):
    from repro.core.family import get_family

    return get_family("chain").variant_site({"n_matrices": 4, "lo": 8, "hi": 24, "seed": 3})


def _moe_dispatch(request):
    from repro.autotune.variants import moe_dispatch_site

    return moe_dispatch_site(tokens=64, d=32, e=4, top_k=2, d_ff=16)


def _ssd_chunk(request):
    from repro.autotune.variants import ssd_chunk_site

    return ssd_chunk_site(b=1, s=64, h=2, p=8, n=8, chunks=(16, 32))


SITES = {"matmul_blocks": _matmul_blocks, "attention_layer": _attention_layer,
         "gram": _gram, "chain": _chain, "moe_dispatch": _moe_dispatch,
         "ssd_chunk": _ssd_chunk}


@pytest.mark.parametrize("builder", sorted(SITES))
def test_a_table_builder_warms_each_program_once(builder, monkeypatch, request):
    calls = collections.Counter()
    monkeypatch.setattr(programs, "PROGRAMS", _CountingCache(programs.PROGRAMS, calls))
    site = SITES[builder](request)

    with collect({}) as t:
        table = site.workloads(seed=1)
    assert len(table) >= 2
    assert list(calls.values()) == [1] * len(table), dict(calls)
    assert t["warm_calls"] == len(table)

    calls.clear()
    inputs = site.make_inputs(1)
    with collect({}) as t:
        cold = {v.name: v.build(*inputs) for v in site.variants}
    assert not calls and "warm_calls" not in t
    for thunk in cold.values():
        thunk()
    assert list(calls.values()) == [1] * len(cold), dict(calls)


def test_a_sweep_session_runs_each_thunk_twice_before_its_first_step(monkeypatch):
    """Before the first Procedure-4 step each thunk has run twice: the
    table builder's warm run, then the timer's calibration call, which is
    kept as the first measurement because it settles on one call per
    sample."""
    from repro.core.family import get_family
    from repro.core.measure import WallClockTimer
    from repro.core.sweep import SweepSpec, build_sweep_session

    # every call a sample of one: the calibration must not ask for inner repeats
    monkeypatch.setattr(WallClockTimer, "MIN_MEASURABLE_S", 0.0)
    calls = collections.Counter()
    family = get_family("kernel_variants")
    real = family.variant_site

    def counted_build(v):
        return lambda *arrays: _counting(calls, v.name, v.build(*arrays))

    def variant_site(params):
        site = real(params)
        return dataclasses.replace(site, variants=tuple(
            dataclasses.replace(v, build=counted_build(v)) for v in site.variants))

    monkeypatch.setattr(type(family), "variant_site", staticmethod(variant_site))
    spec = SweepSpec(name="warm", backend="wall_clock", n_shards=1, max_measurements=6,
                     families={"kernel_variants": {"sites": ["matmul"], "sizes": [128],
                                                   "per_size": 1}})
    (inst,) = spec.expand()
    with collect({}) as t:
        session = build_sweep_session(spec, inst)
    names = set(session.meta["flops"])
    assert len(names) >= 2 and t["warm_calls"] == len(names)
    assert session.timer.inner_repeats == {name: 1 for name in names}
    assert calls == {name: 2 for name in names}
    assert t["calibration_samples_kept"] == len(names)
