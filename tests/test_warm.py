"""One warm run per algorithm, made in one place: the variant and algorithm
builders return unwarmed thunks, and each table builder's
``workloads(warmup=True)`` calls every program exactly once. Calls are
counted through a wrapped program, never through timings."""

import collections
import dataclasses
import importlib

import pytest

from repro.core.spans import collect


def _counting(calls, key, program):
    """``program`` that counts each call under ``key``."""

    def counted(*args, **kwargs):
        calls[key] += 1
        return program(*args, **kwargs)

    return counted


def _matmul_blocks(monkeypatch, calls, request):
    """The matmul site, interpreted on the CPU: the Pallas kernel counted
    per tiling, XLA's dot under its own name."""
    from repro.autotune import variants

    ops = importlib.import_module("repro.kernels.matmul.ops")
    real = ops.matmul

    def matmul(a, b, *, block_m, block_n, block_k, **kw):
        calls[f"blocks_{block_m}x{block_n}x{block_k}"] += 1
        return real(a, b, block_m=block_m, block_n=block_n, block_k=block_k, **kw)

    monkeypatch.setattr(ops, "matmul", matmul)
    monkeypatch.setattr(variants, "_xla_dot", _counting(calls, "xla_dot", variants._xla_dot))
    site = variants.matmul_blocks_site(m=256, k=256, n=256,
                                       blocks=((128, 128, 128), (256, 256, 256)))
    return lambda warmup: site.workloads(seed=1, warmup=warmup)


def _attention_layer(monkeypatch, calls, request):
    """A sliding layer of the tiny attention model through its family."""
    request.getfixturevalue("tiny_attention_model")
    from repro.autotune import variants
    from repro.core.family import get_family

    real = variants._attention_program
    monkeypatch.setattr(variants, "_attention_program", lambda name, fn, **static: (
        _counting(calls, name, real(name, fn, **static))))
    site = get_family("kernel_variants").variant_site({
        "site": "attention", "config": "tiny-attention", "layer": "sliding",
        "size": 512, "seed": 1})
    return lambda warmup: site.workloads(seed=1, warmup=warmup)


class _CountingCache:
    """A program cache whose programs count their calls under their key."""

    def __init__(self, cache, calls):
        self.cache, self.calls = cache, calls

    def get(self, key, build):
        return _counting(self.calls, key, self.cache.get(key, build))


def _gram(monkeypatch, calls, request):
    from repro.expressions import generalized

    monkeypatch.setattr(generalized, "_PROGRAMS",
                        _CountingCache(generalized._PROGRAMS, calls))
    family = generalized.FAMILIES["gram"](n=32)
    return lambda warmup: family.workloads(32, seed=1, warmup=warmup)


def _chain(monkeypatch, calls, request):
    from repro.expressions import algorithms
    from repro.expressions.instances import random_instance

    real = algorithms.chain_program
    monkeypatch.setattr(algorithms, "chain_program",
                        lambda alg: _counting(calls, alg.name, real(alg)))
    chain = random_instance(4, 8, 24, seed=3)
    mats = algorithms.make_chain_inputs(chain.dims, seed=3)
    algs = chain.algorithms()
    return lambda warmup: algorithms.build_workloads(algs, mats, warmup=warmup)


TABLE_BUILDERS = {"matmul_blocks": _matmul_blocks, "attention_layer": _attention_layer,
                  "gram": _gram, "chain": _chain}


@pytest.mark.parametrize("builder", sorted(TABLE_BUILDERS))
def test_a_table_builder_warms_each_program_once(builder, monkeypatch, request):
    calls = collections.Counter()
    workloads = TABLE_BUILDERS[builder](monkeypatch, calls, request)

    with collect({}) as t:
        table = workloads(True)
    assert len(table) >= 2
    assert list(calls.values()) == [1] * len(table), dict(calls)
    assert t["warm_calls"] == len(table)

    calls.clear()
    with collect({}) as t:
        cold = workloads(False)
    assert not calls and "warm_calls" not in t
    for thunk in cold.values():
        thunk()
    assert list(calls.values()) == [1] * len(cold), dict(calls)


def test_a_sweep_session_runs_each_thunk_three_times_before_its_first_step(monkeypatch):
    """Before the first Procedure-4 step each thunk has run three times:
    the table builder's warm run, the timer's calibration call and the
    first measurement."""
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
    assert calls == {name: 3 for name in names}
